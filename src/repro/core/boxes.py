"""Box geometry: parameterization, corners, rotated-BEV 3D IoU.

A 3D box is the paper's seven-tuple ``[x, y, z, l, w, h, theta]`` in LiDAR
coordinates (x forward, y left, z up): center ``(x, y, z)``, size
``(l, w, h)`` (length along heading, width across, height up), heading
``theta`` measured from the +x axis in the x-y plane.

Rotated-rectangle intersection uses Sutherland-Hodgman clipping with fixed
buffers so everything is jit/vmap-compatible.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import ops

# Maximum vertices for the clipped polygon buffer. The intersection of two
# convex quadrilaterals has at most 8 vertices; 16 leaves headroom for the
# interleaved emit pattern.
_MAX_VERTS = 16


def corners_bev(boxes: jnp.ndarray) -> jnp.ndarray:
    """BEV (x-y) corners of boxes ``(..., 7) -> (..., 4, 2)`` in CCW order."""
    x, y = boxes[..., 0], boxes[..., 1]
    l, w = boxes[..., 3], boxes[..., 4]
    th = boxes[..., 6]
    c, s = jnp.cos(th), jnp.sin(th)
    # Local corner offsets (CCW): (+l/2,+w/2), (-l/2,+w/2), (-l/2,-w/2), (+l/2,-w/2)
    dx = jnp.stack([l / 2, -l / 2, -l / 2, l / 2], axis=-1)
    dy = jnp.stack([w / 2, w / 2, -w / 2, -w / 2], axis=-1)
    cx = x[..., None] + dx * c[..., None] - dy * s[..., None]
    cy = y[..., None] + dx * s[..., None] + dy * c[..., None]
    return jnp.stack([cx, cy], axis=-1)


def corners_3d(boxes: jnp.ndarray) -> jnp.ndarray:
    """Eight 3D corners ``(..., 7) -> (..., 8, 3)`` (bottom 4 then top 4)."""
    bev = corners_bev(boxes)  # (..., 4, 2)
    z, h = boxes[..., 2], boxes[..., 5]
    zlo = (z - h / 2)[..., None]
    zhi = (z + h / 2)[..., None]
    bot = jnp.concatenate([bev, jnp.broadcast_to(zlo[..., None], bev.shape[:-1] + (1,))], axis=-1)
    top = jnp.concatenate([bev, jnp.broadcast_to(zhi[..., None], bev.shape[:-1] + (1,))], axis=-1)
    return jnp.concatenate([bot, top], axis=-2)


def _polygon_area(pts: jnp.ndarray, n: jnp.ndarray) -> jnp.ndarray:
    """Shoelace area of the first ``n`` vertices of ``pts`` (MAX_VERTS, 2)."""
    m = pts.shape[0]
    idx = jnp.arange(m)
    valid = idx < n
    nxt = jnp.where(idx + 1 < n, idx + 1, 0)
    x, y = pts[:, 0], pts[:, 1]
    cross = x * y[nxt] - x[nxt] * y
    return 0.5 * jnp.abs(jnp.sum(jnp.where(valid, cross, 0.0)))


def _clip_against_edge(poly: jnp.ndarray, n: jnp.ndarray, p0: jnp.ndarray, p1: jnp.ndarray):
    """Clip polygon (buffer ``poly`` with ``n`` valid CCW verts) against the
    half-plane to the left of the directed edge ``p0 -> p1``."""
    e = p1 - p0

    def inside(q):
        return e[0] * (q[1] - p0[1]) - e[1] * (q[0] - p0[0]) >= 0.0

    def intersect(a, b):
        # Line a-b with the infinite line p0-p1.
        da = e[0] * (a[1] - p0[1]) - e[1] * (a[0] - p0[0])
        db = e[0] * (b[1] - p0[1]) - e[1] * (b[0] - p0[0])
        t = da / jnp.where(jnp.abs(da - db) < 1e-12, 1e-12, da - db)
        return a + t * (b - a)

    out = jnp.zeros_like(poly)

    def body(i, carry):
        out, m = carry
        active = i < n
        cur = poly[i]
        nxt_i = jnp.where(i + 1 < n, i + 1, 0)
        nxt = poly[nxt_i]
        cur_in = inside(cur)
        nxt_in = inside(nxt)
        ipt = intersect(cur, nxt)
        # Emit cur if inside.
        emit1 = jnp.logical_and(active, cur_in)
        out = jnp.where(emit1, out.at[m].set(cur), out)
        m = m + emit1.astype(jnp.int32)
        # Emit intersection if the edge crosses the clip line.
        emit2 = jnp.logical_and(active, cur_in != nxt_in)
        out = jnp.where(emit2, out.at[m].set(ipt), out)
        m = m + emit2.astype(jnp.int32)
        return out, m

    out, m = jax.lax.fori_loop(0, poly.shape[0], body, (out, jnp.int32(0)))
    return out, m


def rect_intersection_area(c1: jnp.ndarray, c2: jnp.ndarray) -> jnp.ndarray:
    """Intersection area of two convex quads given CCW corners (4, 2)."""
    poly = jnp.zeros((_MAX_VERTS, 2), dtype=c1.dtype).at[:4].set(c1)
    n = jnp.int32(4)

    def clip_one(k, carry):
        poly, n = carry
        p0 = c2[k]
        p1 = c2[(k + 1) % 4]
        return _clip_against_edge(poly, n, p0, p1)

    # Unrolled over the 4 clip edges (static count).
    for k in range(4):
        poly, n = _clip_against_edge(poly, n, c2[k], c2[(k + 1) % 4])
    return _polygon_area(poly, n)


def iou_bev(b1: jnp.ndarray, b2: jnp.ndarray) -> jnp.ndarray:
    """Rotated BEV IoU between two boxes (7,) each."""
    c1 = corners_bev(b1)
    c2 = corners_bev(b2)
    inter = rect_intersection_area(c1, c2)
    a1 = b1[3] * b1[4]
    a2 = b2[3] * b2[4]
    union = a1 + a2 - inter
    return jnp.where(union > 1e-9, inter / union, 0.0)


def iou_3d(b1: jnp.ndarray, b2: jnp.ndarray) -> jnp.ndarray:
    """Full 3D IoU between two boxes (7,) each (the paper's accuracy basis)."""
    c1 = corners_bev(b1)
    c2 = corners_bev(b2)
    inter_bev = rect_intersection_area(c1, c2)
    zlo = jnp.maximum(b1[2] - b1[5] / 2, b2[2] - b2[5] / 2)
    zhi = jnp.minimum(b1[2] + b1[5] / 2, b2[2] + b2[5] / 2)
    inter_h = jnp.maximum(zhi - zlo, 0.0)
    inter = inter_bev * inter_h
    v1 = b1[3] * b1[4] * b1[5]
    v2 = b2[3] * b2[4] * b2[5]
    union = v1 + v2 - inter
    return jnp.where(union > 1e-9, inter / union, 0.0)


def pairwise_iou_3d(boxes1: jnp.ndarray, boxes2: jnp.ndarray) -> jnp.ndarray:
    """Pairwise 3D IoU: (N, 7) x (M, 7) -> (N, M)."""
    return jax.vmap(lambda a: jax.vmap(lambda b: iou_3d(a, b))(boxes2))(boxes1)


def pairwise_iou_bev(boxes1: jnp.ndarray, boxes2: jnp.ndarray) -> jnp.ndarray:
    return jax.vmap(lambda a: jax.vmap(lambda b: iou_bev(a, b))(boxes2))(boxes1)


def aabb_iou_2d(a: jnp.ndarray, b: jnp.ndarray,
                backend: str | None = None) -> jnp.ndarray:
    """Pairwise axis-aligned 2D IoU. a: (N, 4) [x1,y1,x2,y2]; b: (M, 4).

    Dispatches through the ops registry (kernels/iou2d): the ref path is
    the closed-form jnp broadcast, the pallas path tiles the (N, M)
    matrix on the MXU.
    """
    return ops.iou2d(a, b, backend=backend)


def points_in_box_bev(points_xy: jnp.ndarray, box: jnp.ndarray) -> jnp.ndarray:
    """Boolean mask of points (P, 2) inside the BEV rectangle of ``box`` (7,)."""
    th = box[6]
    c, s = jnp.cos(th), jnp.sin(th)
    rel = points_xy - box[:2]
    # Rotate into the box frame.
    lx = rel[:, 0] * c + rel[:, 1] * s
    ly = -rel[:, 0] * s + rel[:, 1] * c
    return (jnp.abs(lx) <= box[3] / 2) & (jnp.abs(ly) <= box[4] / 2)


def points_in_box_3d(points: jnp.ndarray, box: jnp.ndarray) -> jnp.ndarray:
    """Boolean mask of points (P, 3) inside the 3D box (7,)."""
    bev = points_in_box_bev(points[:, :2], box)
    zok = jnp.abs(points[:, 2] - box[2]) <= box[5] / 2
    return bev & zok


def project_box3d_to_2d(box: jnp.ndarray, tr: jnp.ndarray,
                        P: jnp.ndarray) -> jnp.ndarray:
    """Project a 3D box (7,) in LiDAR frame to the image: Tr (3,4) LiDAR ->
    camera, then P (3,4) camera -> pixel. Returns [x1,y1,x2,y2].

    This is the paper's "Preparation" step 2: anchor-frame 3D results are
    projected to the image plane to seed 2D tracking.
    """
    corners = corners_3d(box)  # (8, 3)
    hom = jnp.concatenate([corners, jnp.ones((8, 1), dtype=corners.dtype)], axis=-1)
    cam = jnp.matmul(hom, tr.T, precision=jax.lax.Precision.HIGHEST)  # (8, 3)
    cam_h = jnp.concatenate([cam, jnp.ones((8, 1), dtype=cam.dtype)], axis=-1)
    uvw = jnp.matmul(cam_h, P.T, precision=jax.lax.Precision.HIGHEST)  # (8, 3)
    w = jnp.where(jnp.abs(uvw[:, 2]) < 1e-6, 1e-6, uvw[:, 2])
    u = uvw[:, 0] / w
    v = uvw[:, 1] / w
    return jnp.stack([u.min(), v.min(), u.max(), v.max()])


def heading_vector(theta: jnp.ndarray) -> jnp.ndarray:
    """Unit heading vector in the x-y plane from yaw angle."""
    return jnp.stack([jnp.cos(theta), jnp.sin(theta)], axis=-1)
