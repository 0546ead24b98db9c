"""3D bounding box estimation from point clusters (§3.3, Eqs. 1-2, Fig. 9-10).

Given a purified point cluster and the RANSAC surface plane, estimate the
box seven-tuple [x, y, z, l, w, h, theta]:

* Associated objects reuse the previous frame's size; the heading is derived
  from the plane normal and the previous heading (Eq. 1, with the
  perpendicular side-surface case handled by a 90-degree rotation); the
  center is the surface center displaced by half the relevant extent along
  the inward direction (Eq. 2).
* New objects get the fleet-average size and a two-hypothesis disambiguation:
  build both candidate boxes and keep the one containing more cluster points
  (Fig. 10).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import boxes as box_ops


class BoxEstParams(NamedTuple):
    xi_deg: float = 30.0       # paper §4: xi = 30 degrees
    # Heading-continuity clamp (paper §3.3: "physically impossible to change
    # its heading dramatically in one frame", 0.1 s in KITTI): when the
    # plane-derived heading of an *associated* object deviates from the
    # previous heading beyond this angle, keep the previous heading. This
    # suppresses diagonal corner-plane RANSAC fits.
    max_turn_deg: float = 20.0
    # Center estimation: "surface" = the paper's Eq. (2) (surface center +
    # half extent); "extent" = L-shape fit (visible near-face extremes in
    # the heading frame + half extents); "hybrid" = compute both candidates
    # and keep the box containing more cluster points. Measured on the
    # synthetic benchmark (EXPERIMENTS.md): surface 0.68 F1 > hybrid 0.65 >
    # extent 0.56 — the paper's Eq. (2) wins and stays the default.
    center_mode: str = "surface"


def _unit(v: jnp.ndarray) -> jnp.ndarray:
    n = jnp.linalg.norm(v, axis=-1, keepdims=True)
    return v / jnp.where(n < 1e-9, 1.0, n)


def _rot90(v: jnp.ndarray) -> jnp.ndarray:
    return jnp.stack([-v[..., 1], v[..., 0]], axis=-1)


def heading_from_normal(normal: jnp.ndarray, prev_heading: jnp.ndarray,
                        params: BoxEstParams = BoxEstParams()):
    """Eq. (1): derive the current heading vector from the surface normal.

    Args:
      normal: (3,) plane normal from RANSAC.
      prev_heading: (2,) unit heading of the associated box at t-1.

    Returns:
      (heading (2,), is_frontal bool) — is_frontal means the found surface is
      the front/rear (normal ~ parallel to heading); otherwise it is a side
      surface (normal ~ perpendicular).
    """
    v = _unit(normal[:2])
    cosang = jnp.clip(jnp.sum(v * prev_heading), -1.0, 1.0)
    ang = jnp.arccos(cosang)
    xi = jnp.deg2rad(params.xi_deg)
    par_same = ang < xi                  # v ~ h_{t-1}
    par_opp = ang > jnp.pi - xi          # v ~ -h_{t-1}
    is_frontal = par_same | par_opp
    h_frontal = jnp.where(par_same, v, -v)
    # Side surface: rotate the normal by 90 or 270 degrees, keeping the
    # candidate closest to the previous heading (continuity argument in the
    # paper: heading cannot flip within 0.1 s).
    c1 = _rot90(v)
    c2 = -c1
    h_side = jnp.where(jnp.sum(c1 * prev_heading) >= jnp.sum(c2 * prev_heading), c1, c2)
    h = jnp.where(is_frontal, h_frontal, h_side)
    return _unit(h), is_frontal


def center_from_surface(surface_center: jnp.ndarray, heading: jnp.ndarray,
                        is_frontal: jnp.ndarray, size_lwh: jnp.ndarray,
                        z_center: jnp.ndarray) -> jnp.ndarray:
    """Eq. (2): displace the surface center to the box center.

    The visible surface faces the sensor, so the center lies half an extent
    *away from the sensor* along the heading axis (frontal surface) or the
    lateral axis (side surface). The paper writes the displacement with a
    fixed sign; we resolve the sign so the displacement points away from the
    origin, which is the geometrically consistent reading.
    """
    ext = jnp.where(is_frontal, size_lwh[0], size_lwh[1])
    axis = jnp.where(is_frontal, heading, _rot90(heading))
    away = _unit(surface_center[:2])
    sgn = jnp.where(jnp.sum(axis * away) >= 0.0, 1.0, -1.0)
    cxy = surface_center[:2] + 0.5 * ext * sgn * axis
    return jnp.concatenate([cxy, z_center[None]])


def center_from_extents(points_xy: jnp.ndarray, mask: jnp.ndarray,
                        heading: jnp.ndarray, size_lw: jnp.ndarray
                        ) -> jnp.ndarray:
    """L-shape center fit: in the heading frame, the sensor sees the NEAR
    faces, so the near extreme of the cluster along each axis plus half the
    known extent locates the center. Falls back to the far extreme when the
    origin is beyond the far side."""
    u = heading
    v = _rot90(heading)
    n = jnp.maximum(jnp.sum(mask), 1)

    def axis_center(axis_vec, extent):
        c = jnp.matmul(points_xy, axis_vec,
                       precision=jax.lax.Precision.HIGHEST)  # (P,)
        lo = jnp.min(jnp.where(mask, c, 1e9))
        hi = jnp.max(jnp.where(mask, c, -1e9))
        origin = 0.0  # sensor at the LiDAR origin
        near_is_lo = jnp.abs(lo - origin) <= jnp.abs(hi - origin)
        return jnp.where(near_is_lo, lo + extent / 2, hi - extent / 2)

    cu = axis_center(u, size_lw[0])
    cv = axis_center(v, size_lw[1])
    return cu * u + cv * v


class EstimateInputs(NamedTuple):
    points: jnp.ndarray        # (P, 3) cluster buffer
    inlier_mask: jnp.ndarray   # (P,) RANSAC surface inliers
    cluster_mask: jnp.ndarray  # (P,) filtered cluster membership
    normal: jnp.ndarray        # (3,) RANSAC plane normal
    plane_ok: jnp.ndarray      # bool
    associated: jnp.ndarray    # bool: has a previous-frame box
    prev_box: jnp.ndarray      # (7,) previous box (undefined if new)
    avg_size: jnp.ndarray      # (3,) fleet average (l, w, h)


def estimate_box(inp: EstimateInputs,
                 params: BoxEstParams = BoxEstParams()):
    """Estimate one object's box. Returns (box (7,), ok bool)."""
    pts = inp.points
    cm = inp.cluster_mask
    im = inp.inlier_mask & cm
    n_in = jnp.maximum(jnp.sum(im), 1)
    n_cl = jnp.maximum(jnp.sum(cm), 1)

    surface_center = jnp.sum(jnp.where(im[:, None], pts, 0.0), axis=0) / n_in
    zmin = jnp.min(jnp.where(cm, pts[:, 2], 1e9))
    zmax = jnp.max(jnp.where(cm, pts[:, 2], -1e9))

    # --- associated path -------------------------------------------------
    prev_h = box_ops.heading_vector(inp.prev_box[6])
    size_assoc = inp.prev_box[3:6]
    h_assoc, is_frontal = heading_from_normal(inp.normal, prev_h, params)
    # Heading-continuity clamp (see BoxEstParams.max_turn_deg).
    turn_cos = jnp.clip(jnp.sum(h_assoc * prev_h), -1.0, 1.0)
    too_sharp = turn_cos < jnp.cos(jnp.deg2rad(params.max_turn_deg))
    h_assoc = jnp.where(too_sharp, prev_h, h_assoc)
    z_assoc = zmin + size_assoc[2] / 2
    th_assoc = jnp.arctan2(h_assoc[1], h_assoc[0])

    def boxify(cxy):
        return jnp.concatenate([cxy, z_assoc[None], size_assoc,
                                th_assoc[None]])

    c_surface = center_from_surface(surface_center, h_assoc, is_frontal,
                                    size_assoc, z_assoc)[:2]
    c_extent = center_from_extents(pts[:, :2], cm, h_assoc, size_assoc[:2])
    if params.center_mode == "extent":
        box_assoc = boxify(c_extent)
    elif params.center_mode == "surface":
        box_assoc = boxify(c_surface)
    else:  # hybrid: the candidate containing more cluster points wins.
        box_s = boxify(c_surface)
        box_e = boxify(c_extent)
        in_s = jnp.sum(box_ops.points_in_box_3d(pts, box_s) & cm)
        in_e = jnp.sum(box_ops.points_in_box_3d(pts, box_e) & cm)
        box_assoc = jnp.where(in_s >= in_e, box_s, box_e)

    # --- new-object path (Fig. 10) ---------------------------------------
    size_new = inp.avg_size
    v = _unit(inp.normal[:2])
    z_new = zmin + size_new[2] / 2

    def new_center(heading, frontal):
        c_s = center_from_surface(surface_center, heading, frontal,
                                  size_new, z_new)
        if params.center_mode == "surface":
            return c_s
        c_e = jnp.concatenate([
            center_from_extents(pts[:, :2], cm, heading, size_new[:2]),
            z_new[None]])
        if params.center_mode == "extent":
            return c_e
        box_s = jnp.concatenate([c_s, size_new,
                                 jnp.arctan2(heading[1], heading[0])[None]])
        box_e = jnp.concatenate([c_e, size_new,
                                 jnp.arctan2(heading[1], heading[0])[None]])
        in_s = jnp.sum(box_ops.points_in_box_3d(pts, box_s) & cm)
        in_e = jnp.sum(box_ops.points_in_box_3d(pts, box_e) & cm)
        return jnp.where(in_s >= in_e, c_s, c_e)

    # Hypothesis A: surface is frontal (normal ~ heading).
    ha = v
    ca = new_center(ha, jnp.bool_(True))
    box_a = jnp.concatenate([ca, size_new, jnp.arctan2(ha[1], ha[0])[None]])
    # Hypothesis B: surface is lateral (heading = normal rotated 90 deg).
    hb = _rot90(v)
    cb = new_center(hb, jnp.bool_(False))
    box_b = jnp.concatenate([cb, size_new, jnp.arctan2(hb[1], hb[0])[None]])
    in_a = jnp.sum(box_ops.points_in_box_3d(pts, box_a) & cm)
    in_b = jnp.sum(box_ops.points_in_box_3d(pts, box_b) & cm)
    box_new = jnp.where(in_a >= in_b, box_a, box_b)

    box = jnp.where(inp.associated, box_assoc, box_new)
    ok = inp.plane_ok & (jnp.sum(cm) >= 3)
    # Fallback for clusters without a usable plane: centroid box with average
    # (or previous) size and previous (or zero) heading.
    centroid = jnp.sum(jnp.where(cm[:, None], pts, 0.0), axis=0) / n_cl
    fb_size = jnp.where(inp.associated, size_assoc, size_new)
    fb_th = jnp.where(inp.associated, inp.prev_box[6], 0.0)
    fb_z = zmin + fb_size[2] / 2
    fallback = jnp.concatenate([
        centroid[:2], fb_z[None], fb_size, fb_th[None]])
    box = jnp.where(ok, box, fallback)
    have_pts = jnp.sum(cm) > 0
    return box, have_pts


def estimate_boxes(points: jnp.ndarray, inlier_masks: jnp.ndarray,
                   cluster_masks: jnp.ndarray, normals: jnp.ndarray,
                   plane_ok: jnp.ndarray, associated: jnp.ndarray,
                   prev_boxes: jnp.ndarray, avg_size: jnp.ndarray,
                   params: BoxEstParams = BoxEstParams()):
    """Vectorized box estimation over objects (leading O axis)."""
    def one(p, im, cm, n, pok, a, pb):
        return estimate_box(EstimateInputs(p, im, cm, n, pok, a, pb, avg_size), params)
    return jax.vmap(one)(points, inlier_masks, cluster_masks, normals,
                         plane_ok, associated, prev_boxes)
