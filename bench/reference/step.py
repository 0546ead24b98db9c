"""Plain reference of one fleet round's device work: Moby's per-frame step.

The semantics the benchmark holds the program to, written out in plain
``jax.numpy`` with no kernel, no ops registry and nothing imported from the
program: the frame-offloading scheduler (paper §3.4, policy ``fos``), the
anchor step (project the cloud's 3D boxes to 2D, reseed the tracker) and the
transform step (SORT tracking with auction association, point projection
and labelling, cluster compaction, Algorithm 1 filtration, RANSAC surface
fit, box estimation by Eqs. 1-2 with the two-hypothesis rule for new
objects) selected per stream by the anchor flag, then F1/precision/recall
at a 3D IoU of 0.4 against the evaluable ground truth. It follows the
program's algorithm choices and PRNG use as they stood when the benchmark
was written, so that the same inputs give the same answers.

Every matrix product goes through :func:`dot` so that its precision is one
knob: ``"highest"`` is the configuration's float32 (the program pins
``Precision.HIGHEST``); ``"high"`` and ``"default"`` emulate the TPU's
three-pass and one-pass bfloat16 products (each float32 operand split into
a bfloat16 head and tail by bit mask; three passes drop the tail-by-tail
term, one pass keeps head-by-head only). They are the controls that the
comparison deciding ``correct`` is checked against; the emulation gives
the same numbers on every backend.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
_BIG = 1e9
_NEG = -1e9
_MAX_VERTS = 16


class Params(NamedTuple):
    """The configuration's algorithm constants (paper §4 and the program's
    defaults when the benchmark was written). Hashable: a jit static."""
    img_h: int
    img_w: int
    max_obj: int
    precision: str = "highest"
    pts_per_obj: int = 256
    iou_assoc: float = 0.3
    f_t: float = 4.5
    m_t: int = 24
    s_t: float = 12.0
    max_filter_iter: int = 3
    ransac_iters: int = 30
    inlier_thresh: float = 0.10
    max_abs_nz: float = 0.7
    xi_deg: float = 30.0
    max_turn_deg: float = 20.0
    max_age: int = 3
    n_t: int = 4
    q_t: float = 0.7
    f1_iou: float = 0.4


def _bf16_head(x):
    """``x`` with its mantissa cut to bfloat16's 8 bits (by bit mask, so
    that no compiler can fold the rounding away)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def dot(spec: str, a, b, precision: str):
    """``jnp.einsum(spec, a, b)`` in the named precision: ``"highest"``
    (float32), ``"high"`` (three bfloat16 passes: head*head + head*tail +
    tail*head) or ``"default"`` (one bfloat16 pass, head*head)."""
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=_HI)
    ah, bh = _bf16_head(a), _bf16_head(b)
    out = jnp.einsum(spec, ah, bh, precision=_HI)
    if precision == "default":
        return out
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    al, bl = _bf16_head(a - ah), _bf16_head(b - bh)
    return out + jnp.einsum(spec, ah, bl, precision=_HI) \
        + jnp.einsum(spec, al, bh, precision=_HI)


# ---------------------------------------------------------------------------
# Box geometry
# ---------------------------------------------------------------------------

def corners_bev(boxes):
    x, y = boxes[..., 0], boxes[..., 1]
    l, w, th = boxes[..., 3], boxes[..., 4], boxes[..., 6]
    c, s = jnp.cos(th), jnp.sin(th)
    dx = jnp.stack([l / 2, -l / 2, -l / 2, l / 2], axis=-1)
    dy = jnp.stack([w / 2, w / 2, -w / 2, -w / 2], axis=-1)
    cx = x[..., None] + dx * c[..., None] - dy * s[..., None]
    cy = y[..., None] + dx * s[..., None] + dy * c[..., None]
    return jnp.stack([cx, cy], axis=-1)


def corners_3d(box):
    bev = corners_bev(box)                                  # (4, 2)
    zlo = jnp.full((4, 1), box[2] - box[5] / 2)
    zhi = jnp.full((4, 1), box[2] + box[5] / 2)
    return jnp.concatenate([jnp.concatenate([bev, zlo], -1),
                            jnp.concatenate([bev, zhi], -1)], axis=0)


def _clip(poly, n, p0, p1):
    """Sutherland-Hodgman: keep the part of ``poly`` left of p0->p1."""
    e = p1 - p0

    def side(q):
        return e[0] * (q[1] - p0[1]) - e[1] * (q[0] - p0[0])

    def body(i, carry):
        out, m = carry
        active = i < n
        cur = poly[i]
        nxt = poly[jnp.where(i + 1 < n, i + 1, 0)]
        da, db = side(cur), side(nxt)
        cur_in, nxt_in = da >= 0.0, db >= 0.0
        t = da / jnp.where(jnp.abs(da - db) < 1e-12, 1e-12, da - db)
        ipt = cur + t * (nxt - cur)
        emit1 = active & cur_in
        out = jnp.where(emit1, out.at[m].set(cur), out)
        m = m + emit1.astype(jnp.int32)
        emit2 = active & (cur_in != nxt_in)
        out = jnp.where(emit2, out.at[m].set(ipt), out)
        m = m + emit2.astype(jnp.int32)
        return out, m

    return jax.lax.fori_loop(0, poly.shape[0], body,
                             (jnp.zeros_like(poly), jnp.int32(0)))


def _area(pts, n):
    idx = jnp.arange(pts.shape[0])
    nxt = jnp.where(idx + 1 < n, idx + 1, 0)
    x, y = pts[:, 0], pts[:, 1]
    cross = x * y[nxt] - x[nxt] * y
    return 0.5 * jnp.abs(jnp.sum(jnp.where(idx < n, cross, 0.0)))


def iou_3d(b1, b2):
    c1, c2 = corners_bev(b1), corners_bev(b2)
    poly = jnp.zeros((_MAX_VERTS, 2), c1.dtype).at[:4].set(c1)
    n = jnp.int32(4)
    for k in range(4):
        poly, n = _clip(poly, n, c2[k], c2[(k + 1) % 4])
    inter_bev = _area(poly, n)
    zlo = jnp.maximum(b1[2] - b1[5] / 2, b2[2] - b2[5] / 2)
    zhi = jnp.minimum(b1[2] + b1[5] / 2, b2[2] + b2[5] / 2)
    inter = inter_bev * jnp.maximum(zhi - zlo, 0.0)
    union = b1[3] * b1[4] * b1[5] + b2[3] * b2[4] * b2[5] - inter
    return jnp.where(union > 1e-9, inter / union, 0.0)


def points_in_box(points, box):
    c, s = jnp.cos(box[6]), jnp.sin(box[6])
    rel = points[:, :2] - box[:2]
    lx = rel[:, 0] * c + rel[:, 1] * s
    ly = -rel[:, 0] * s + rel[:, 1] * c
    return (jnp.abs(lx) <= box[3] / 2) & (jnp.abs(ly) <= box[4] / 2) \
        & (jnp.abs(points[:, 2] - box[2]) <= box[5] / 2)


def box_to_2d(box, tr, p, prec):
    hom = jnp.concatenate([corners_3d(box), jnp.ones((8, 1))], axis=-1)
    cam = dot("nk,jk->nj", hom, tr, prec)
    uvw = dot("nk,jk->nj", jnp.concatenate([cam, jnp.ones((8, 1))], -1),
              p, prec)
    w = jnp.where(jnp.abs(uvw[:, 2]) < 1e-6, 1e-6, uvw[:, 2])
    u, v = uvw[:, 0] / w, uvw[:, 1] / w
    return jnp.stack([u.min(), v.min(), u.max(), v.max()])


def iou2d(a, b):
    ax1, ay1, ax2, ay2 = a[:, 0:1], a[:, 1:2], a[:, 2:3], a[:, 3:4]
    bx1, by1, bx2, by2 = (b[None, :, i] for i in range(4))
    ix = jnp.maximum(jnp.minimum(ax2, bx2) - jnp.maximum(ax1, bx1), 0.0)
    iy = jnp.maximum(jnp.minimum(ay2, by2) - jnp.maximum(ay1, by1), 0.0)
    inter = ix * iy
    union = jnp.maximum((ax2 - ax1) * (ay2 - ay1), 0.0) \
        + jnp.maximum((bx2 - bx1) * (by2 - by1), 0.0) - inter
    return jnp.where(union > 1e-9, inter / union, 0.0)


# ---------------------------------------------------------------------------
# SORT tracker in the image plane
# ---------------------------------------------------------------------------

class Tracks(NamedTuple):
    x: jnp.ndarray          # (T, 7) [u, v, s, r, du, dv, ds]
    p: jnp.ndarray          # (T, 7, 7)
    active: jnp.ndarray
    age: jnp.ndarray
    hits: jnp.ndarray
    track_id: jnp.ndarray
    box3d: jnp.ndarray      # (T, 7)
    has_box3d: jnp.ndarray
    next_id: jnp.ndarray


def _fh():
    f = jnp.eye(7).at[0, 4].set(1.0).at[1, 5].set(1.0).at[2, 6].set(1.0)
    h = jnp.zeros((4, 7)).at[jnp.arange(4), jnp.arange(4)].set(1.0)
    return f, h


def _to_z(box):
    w = jnp.maximum(box[..., 2] - box[..., 0], 1e-3)
    h = jnp.maximum(box[..., 3] - box[..., 1], 1e-3)
    return jnp.stack([box[..., 0] + w / 2, box[..., 1] + h / 2, w * h,
                      w / h], axis=-1)


def _to_box(z):
    s = jnp.maximum(z[..., 2], 1e-3)
    r = jnp.maximum(z[..., 3], 1e-3)
    w = jnp.sqrt(s * r)
    h = s / w
    return jnp.stack([z[..., 0] - w / 2, z[..., 1] - h / 2,
                      z[..., 0] + w / 2, z[..., 1] + h / 2], axis=-1)


def init_tracks(t: int) -> Tracks:
    return Tracks(x=jnp.zeros((t, 7)), p=jnp.tile(jnp.eye(7)[None] * 10.0,
                                                  (t, 1, 1)),
                  active=jnp.zeros((t,), bool),
                  age=jnp.zeros((t,), jnp.int32),
                  hits=jnp.zeros((t,), jnp.int32),
                  track_id=jnp.full((t,), -1, jnp.int32),
                  box3d=jnp.zeros((t, 7)), has_box3d=jnp.zeros((t,), bool),
                  next_id=jnp.int32(0))


def predict(tr: Tracks, prec):
    f, _ = _fh()
    q = jnp.diag(jnp.array([1, 1, 1, 1, 0.01, 0.01, 0.0001], jnp.float32))
    x = dot("tj,ij->ti", tr.x, f, prec)
    x = x.at[:, 6].set(jnp.where(x[:, 2] + x[:, 6] <= 0, 0.0, x[:, 6]))
    if prec == "highest":
        p = jnp.einsum("ij,tjk,lk->til", f, tr.p, f, precision=_HI)
    else:
        p = dot("tik,lk->til", dot("ij,tjk->tik", f, tr.p, prec), f, prec)
    p = p + q[None]
    x = jnp.where(tr.active[:, None], x, tr.x)
    p = jnp.where(tr.active[:, None, None], p, tr.p)
    return tr._replace(x=x, p=p), _to_box(x[:, :4])


def update(tr: Tracks, t2d, dets, max_age, prec):
    _, h = _fh()
    r = jnp.diag(jnp.array([1, 1, 10, 10], jnp.float32))
    matched = (t2d >= 0) & tr.active
    z = _to_z(dets[jnp.clip(t2d, 0, dets.shape[0] - 1)])

    def kalman(x, p, zi):
        hp = dot("ij,jk->ik", h, p, prec)
        y = zi - dot("ij,j->i", h, x, prec)
        s = dot("ij,kj->ik", hp, h, prec) + r
        k = jnp.linalg.solve(s, hp).T
        x2 = x + dot("ij,j->i", k, y, prec)
        p2 = dot("ij,jk->ik", jnp.eye(7) - dot("ij,jk->ik", k, h, prec),
                 p, prec)
        return x2, p2

    x2, p2 = jax.vmap(kalman)(tr.x, tr.p, z)
    age = jnp.where(matched, 0, tr.age + 1)
    return tr._replace(
        x=jnp.where(matched[:, None], x2, tr.x),
        p=jnp.where(matched[:, None, None], p2, tr.p), age=age,
        hits=jnp.where(matched, tr.hits + 1, tr.hits),
        active=tr.active & (age <= max_age))


def spawn(tr: Tracks, dets, dvalid, d2t):
    t, d = tr.x.shape[0], dets.shape[0]
    free = ~tr.active
    need = dvalid & (d2t < 0)
    free_rank = jnp.cumsum(free.astype(jnp.int32)) - 1
    need_rank = jnp.cumsum(need.astype(jnp.int32)) - 1
    slot_rank = jnp.where(free, free_rank, -1)
    rank_to_det = jnp.full((t,), -1, jnp.int32).at[
        jnp.where(need, jnp.clip(need_rank, 0, t - 1), t - 1)].max(
        jnp.where(need & (need_rank < t), jnp.arange(d, dtype=jnp.int32), -1))
    take = jnp.where(slot_rank >= 0,
                     rank_to_det[jnp.clip(slot_rank, 0, t - 1)], -1)
    spawning = (take >= 0) & free & (slot_rank < jnp.sum(need))
    x_new = jnp.zeros_like(tr.x).at[:, :4].set(
        _to_z(dets[jnp.clip(take, 0, d - 1)]))
    ids = tr.next_id + jnp.cumsum(spawning.astype(jnp.int32)) - 1
    onehot = (take[:, None] == jnp.arange(d)[None, :]) & spawning[:, None]
    d2t = jnp.where(jnp.any(onehot, axis=0), jnp.argmax(onehot, axis=0),
                    d2t).astype(jnp.int32)
    tr = tr._replace(
        x=jnp.where(spawning[:, None], x_new, tr.x),
        p=jnp.where(spawning[:, None, None],
                    jnp.tile(jnp.eye(7)[None] * 10.0, (t, 1, 1)), tr.p),
        active=tr.active | spawning,
        age=jnp.where(spawning, 0, tr.age),
        hits=jnp.where(spawning, 1, tr.hits),
        track_id=jnp.where(spawning, ids, tr.track_id),
        has_box3d=jnp.where(spawning, False, tr.has_box3d),
        next_id=tr.next_id + jnp.sum(spawning))
    return tr, d2t


def set_box3d(tr: Tracks, d2t, boxes3d, ok):
    t = tr.x.shape[0]
    onehot = (d2t[:, None] == jnp.arange(t)[None, :]) & ok[:, None] \
        & (d2t >= 0)[:, None]
    has = jnp.any(onehot, axis=0)
    src = jnp.argmax(onehot, axis=0)
    return tr._replace(box3d=jnp.where(has[:, None], boxes3d[src], tr.box3d),
                       has_box3d=tr.has_box3d | has)


# ---------------------------------------------------------------------------
# Association: auction on the 1e-3-quantized IoU benefit
# ---------------------------------------------------------------------------

def _auction_phase(benefit, prices, eps, max_iter=4000):
    n = benefit.shape[0]

    def cond(st):
        p2o, _, _, it = st
        return jnp.any(p2o < 0) & (it < max_iter)

    def body(st):
        p2o, o2p, prices, it = st
        unassigned = p2o < 0
        values = benefit - prices[None, :]
        padded = jnp.concatenate([values, jnp.full((n, 1), _NEG)], axis=1)
        top2 = jax.lax.top_k(padded, 2)[0]
        best_j = jnp.argmax(values, axis=1)
        bid = prices[best_j] + top2[:, 0] - top2[:, 1] + eps
        bids = jnp.full((n, n), _NEG).at[jnp.arange(n), best_j].set(
            jnp.where(unassigned, bid, _NEG))
        best_bid = jnp.max(bids, axis=0)
        winner = jnp.argmax(bids, axis=0)
        has_bid = best_bid > _NEG / 2
        ar = jnp.arange(n)
        won = unassigned & has_bid[best_j] & (winner[best_j] == ar)
        cur = jnp.clip(p2o, 0, n - 1)
        evicted = (p2o >= 0) & has_bid[cur] & (winner[cur] != ar)
        p2o = jnp.where(won, best_j.astype(jnp.int32),
                        jnp.where(evicted, -1, p2o))
        o2p = jnp.where(has_bid, winner.astype(jnp.int32), o2p)
        return p2o, o2p, jnp.where(has_bid, best_bid, prices), it + 1

    init = (jnp.full((n,), -1, jnp.int32), jnp.full((n,), -1, jnp.int32),
            prices, jnp.int32(0))
    p2o, _, prices, _ = jax.lax.while_loop(cond, body, init)
    return p2o, prices


def associate(tboxes, tvalid, dboxes, dvalid, thresh):
    t, d = tboxes.shape[0], dboxes.shape[0]
    n = max(t, d)
    iou = iou2d(tboxes, dboxes)
    benefit = jnp.where(tvalid[:, None] & dvalid[None, :], iou, 0.0)
    benefit = jnp.round(benefit * 1000.0) / 1000.0
    sq = jnp.zeros((n, n)).at[:t, :d].set(benefit)
    prices = jnp.zeros((n,))
    eps = 0.1
    while True:
        p2o, prices = _auction_phase(sq, prices, eps)
        if eps <= 1e-4:
            break
        eps = max(eps / 10.0, 1e-4)
    t2d = p2o[:t]
    t2d = jnp.where(t2d >= d, -1, t2d)
    m_iou = iou[jnp.arange(t), jnp.clip(t2d, 0, d - 1)]
    good = (t2d >= 0) & (m_iou >= thresh) & tvalid
    t2d = jnp.where(good, t2d, -1)
    onehot = (t2d[:, None] == jnp.arange(d)[None, :]) & good[:, None]
    d2t = jnp.where(jnp.any(onehot, axis=0), jnp.argmax(onehot, axis=0),
                    -1).astype(jnp.int32)
    return t2d, d2t


# ---------------------------------------------------------------------------
# Projection, clusters, filtration, RANSAC, box estimation
# ---------------------------------------------------------------------------

def point_labels(points, label_img, tr, p, prm: Params):
    """Label each LiDAR point with the instance id of the pixel it lands
    on (0 off-image or behind the camera)."""
    n = points.shape[0]
    hom = jnp.concatenate([points, jnp.ones((n, 1))], axis=-1)
    cam = dot("nk,jk->nj", hom, tr, prm.precision)
    pix = dot("nk,jk->nj", jnp.concatenate([cam, jnp.ones((n, 1))], -1), p,
              prm.precision)
    depth = pix[:, 2]
    w = jnp.where(jnp.abs(depth) < 1e-6, 1e-6, depth)
    u, v = pix[:, 0] / w, pix[:, 1] / w
    vis = (depth > 0.1) & (u >= 0) & (u < prm.img_w) & (v >= 0) \
        & (v < prm.img_h)
    ui = jnp.clip(jnp.round(u).astype(jnp.int32), 0, prm.img_w - 1)
    vi = jnp.clip(jnp.round(v).astype(jnp.int32), 0, prm.img_h - 1)
    return jnp.where(vis, label_img[vi, ui], 0)


def clusters(points, labels, prm: Params):
    """The first ``pts_per_obj`` points (in point order) of each slot."""
    def one(obj_id):
        m = labels == obj_id
        idx = jnp.argsort(~m)[:prm.pts_per_obj]
        v = m[idx]
        return jnp.where(v[:, None], points[idx], 0.0), v

    return jax.vmap(one)(jnp.arange(1, prm.max_obj + 1, dtype=jnp.int32))


def filter_cluster(points, valid, prior, has_prior, prm: Params):
    """Algorithm 1 with the associated-object centre prior and the
    best-ball fallback."""
    d_origin = jnp.where(valid, jnp.linalg.norm(points, axis=-1), _BIG)
    n_valid = jnp.sum(valid)
    d_prior = jnp.where(valid, jnp.linalg.norm(points - prior, axis=-1), _BIG)
    crit0 = jnp.where(has_prior, jnp.argmin(d_prior), jnp.argmin(d_origin))

    def cond(st):
        idx, _, it, _, _ = st
        return (jnp.sum(idx) < prm.m_t) & (it < prm.max_filter_iter) \
            & (n_valid > 0)

    def body(st):
        _, crit, it, best_idx, best_n = st
        idx = valid & (jnp.linalg.norm(points - points[crit], axis=-1)
                       < prm.f_t)
        n = jnp.sum(idx)
        best_idx = jnp.where(n > best_n, idx, best_idx)
        best_n = jnp.maximum(n, best_n)
        cand = jnp.where(d_origin >= d_origin[crit] + prm.s_t, d_origin, _BIG)
        nxt = jnp.where(jnp.min(cand) < _BIG, jnp.argmin(cand), crit)
        return idx, nxt, it + 1, best_idx, best_n

    z = jnp.zeros_like(valid)
    idx, _, _, best_idx, best_n = jax.lax.while_loop(
        cond, body, (z, crit0, jnp.int32(0), z, jnp.int32(0)))
    out = jnp.where(jnp.sum(idx) >= prm.m_t, idx,
                    jnp.where(best_n > 0, best_idx, idx))
    return out & valid


def ransac(key, points, valid, prm: Params):
    """Best near-vertical plane of each cluster over ``ransac_iters``
    sampled triplets."""
    o = points.shape[0]
    keys = jax.random.split(key, o)

    def triplets(k, v):
        order = jnp.argsort(~v)
        u = jax.random.randint(k, (prm.ransac_iters, 3), 0,
                               jnp.maximum(jnp.sum(v), 1))
        return order[u]

    tri = jax.vmap(triplets)(keys, valid)                   # (O, K, 3)

    def planes(pts, tri):
        p0, p1, p2 = pts[tri[:, 0]], pts[tri[:, 1]], pts[tri[:, 2]]
        n = jnp.cross(p1 - p0, p2 - p0)
        norm = jnp.linalg.norm(n, axis=-1, keepdims=True)
        n = n / jnp.where(norm < 1e-8, 1.0, norm)
        return n, -jnp.sum(n * p0, axis=-1), norm[:, 0] > 1e-8

    normals, offsets, tri_ok = jax.vmap(planes)(points, tri)
    dist = jnp.abs(dot("opc,okc->opk", points, normals, prm.precision)
                   + offsets[:, None, :])
    counts = jnp.sum((dist < prm.inlier_thresh) & valid[:, :, None],
                     axis=1).astype(jnp.int32)
    counts = jnp.where(tri_ok & (jnp.abs(normals[..., 2]) <= prm.max_abs_nz),
                       counts, 0)
    best = jnp.argmax(counts, axis=1)
    n_best = jnp.take_along_axis(normals, best[:, None, None], axis=1)[:, 0]
    d_best = jnp.take_along_axis(offsets, best[:, None], axis=1)[:, 0]
    dist = jnp.abs(dot("opc,oc->op", points, n_best, prm.precision)
                   + d_best[:, None])
    inliers = (dist < prm.inlier_thresh) & valid
    num = jnp.take_along_axis(counts, best[:, None], axis=1)[:, 0]
    return n_best, inliers, num >= 3


def _unit(v):
    n = jnp.linalg.norm(v, axis=-1, keepdims=True)
    return v / jnp.where(n < 1e-9, 1.0, n)


def _rot90(v):
    return jnp.stack([-v[..., 1], v[..., 0]], axis=-1)


def _surface_center(sc, heading, frontal, size, z):
    ext = jnp.where(frontal, size[0], size[1])
    axis = jnp.where(frontal, heading, _rot90(heading))
    sgn = jnp.where(jnp.sum(axis * _unit(sc[:2])) >= 0.0, 1.0, -1.0)
    return jnp.concatenate([sc[:2] + 0.5 * ext * sgn * axis, z[None]])


def estimate_box(pts, inl, cm, normal, plane_ok, assoc, prev, avg, prm):
    """Eqs. 1-2 for an associated object, the two-hypothesis rule
    (Fig. 10) for a new one, a centroid box without a usable plane."""
    im = inl & cm
    sc = jnp.sum(jnp.where(im[:, None], pts, 0.0), 0) \
        / jnp.maximum(jnp.sum(im), 1)
    zmin = jnp.min(jnp.where(cm, pts[:, 2], 1e9))
    # associated: heading from the normal and the previous heading
    prev_h = jnp.stack([jnp.cos(prev[6]), jnp.sin(prev[6])])
    size_a = prev[3:6]
    v = _unit(normal[:2])
    ang = jnp.arccos(jnp.clip(jnp.sum(v * prev_h), -1.0, 1.0))
    xi = jnp.deg2rad(prm.xi_deg)
    par_same, par_opp = ang < xi, ang > jnp.pi - xi
    frontal = par_same | par_opp
    c1 = _rot90(v)
    h_side = jnp.where(jnp.sum(c1 * prev_h) >= jnp.sum(-c1 * prev_h), c1, -c1)
    h_a = _unit(jnp.where(frontal, jnp.where(par_same, v, -v), h_side))
    sharp = jnp.clip(jnp.sum(h_a * prev_h), -1.0, 1.0) \
        < jnp.cos(jnp.deg2rad(prm.max_turn_deg))
    h_a = jnp.where(sharp, prev_h, h_a)
    z_a = zmin + size_a[2] / 2
    c_a = _surface_center(sc, h_a, frontal, size_a, z_a)[:2]
    box_assoc = jnp.concatenate([c_a, z_a[None], size_a,
                                 jnp.arctan2(h_a[1], h_a[0])[None]])
    # new: frontal (heading = normal) or lateral (normal turned 90 degrees)
    z_n = zmin + avg[2] / 2

    def hyp(h, fr):
        c = _surface_center(sc, h, jnp.bool_(fr), avg, z_n)
        return jnp.concatenate([c, avg, jnp.arctan2(h[1], h[0])[None]])

    box_a, box_b = hyp(v, True), hyp(_rot90(v), False)
    in_a = jnp.sum(points_in_box(pts, box_a) & cm)
    in_b = jnp.sum(points_in_box(pts, box_b) & cm)
    box = jnp.where(assoc, box_assoc, jnp.where(in_a >= in_b, box_a, box_b))
    ok = plane_ok & (jnp.sum(cm) >= 3)
    centroid = jnp.sum(jnp.where(cm[:, None], pts, 0.0), 0) \
        / jnp.maximum(jnp.sum(cm), 1)
    fb_size = jnp.where(assoc, size_a, avg)
    fallback = jnp.concatenate([centroid[:2], (zmin + fb_size[2] / 2)[None],
                                fb_size,
                                jnp.where(assoc, prev[6], 0.0)[None]])
    return jnp.where(ok, box, fallback), jnp.sum(cm) > 0


# ---------------------------------------------------------------------------
# F1 at a 3D IoU threshold (greedy matching)
# ---------------------------------------------------------------------------

def f1_score(det, dval, gt, gval, thresh):
    iou = jax.vmap(lambda a: jax.vmap(lambda b: iou_3d(a, b))(gt))(det)
    d, g = iou.shape
    iou = jnp.where(dval[:, None] & gval[None, :], iou, 0.0)

    def body(_, carry):
        cur, dused, gused = carry
        flat = jnp.argmax(cur)
        di, gi = flat // g, flat % g
        take = cur[di, gi] >= thresh
        dused = dused.at[di].set(dused[di] | take)
        gused = gused.at[gi].set(gused[gi] | take)
        cur = cur.at[di, :].set(jnp.where(take, 0.0, cur[di, :]))
        cur = cur.at[:, gi].set(jnp.where(take, 0.0, cur[:, gi]))
        return cur, dused, gused

    _, dused, _ = jax.lax.fori_loop(
        0, min(d, g), body, (iou, jnp.zeros((d,), bool),
                             jnp.zeros((g,), bool)))
    tp = jnp.sum(dused)
    n_det, n_gt = jnp.sum(dval), jnp.sum(gval)
    prec = jnp.where(n_det > 0, tp / jnp.maximum(n_det, 1), 0.0)
    rec = jnp.where(n_gt > 0, tp / jnp.maximum(n_gt, 1), 0.0)
    f1 = jnp.where(prec + rec > 0,
                   2 * prec * rec / jnp.maximum(prec + rec, 1e-9), 0.0)
    return jnp.where((n_gt == 0) & (n_det == 0), 1.0, f1), prec, rec


# ---------------------------------------------------------------------------
# One stream, one frame
# ---------------------------------------------------------------------------

class Sched(NamedTuple):
    frames_since_test: jnp.ndarray
    test_inflight: jnp.ndarray
    buf_boxes: jnp.ndarray
    buf_valid: jnp.ndarray
    anchor_pending: jnp.ndarray


class State(NamedTuple):
    tracks: Tracks
    avg_size: jnp.ndarray
    key: jax.Array
    sched: Sched
    inflight_boxes: jnp.ndarray
    inflight_valid: jnp.ndarray


def init_state(seed, prm: Params) -> State:
    """Stream state; its PRNG key is ``jax.random.key(seed)``."""
    d = prm.max_obj
    return State(
        tracks=init_tracks(2 * d),
        avg_size=jnp.asarray([4.0, 1.7, 1.6], jnp.float32),
        key=jax.random.key(seed),
        sched=Sched(frames_since_test=jnp.int32(0),
                    test_inflight=jnp.bool_(False),
                    buf_boxes=jnp.zeros((d, 7)),
                    buf_valid=jnp.zeros((d,), bool),
                    anchor_pending=jnp.bool_(True)),
        inflight_boxes=jnp.zeros((d, 7)), inflight_valid=jnp.zeros((d,), bool))


def _anchor(st: State, b3, v3, tr, p, prm):
    boxes2d = jax.vmap(lambda b: box_to_2d(b, tr, p, prm.precision))(b3)
    tracks, pred = predict(st.tracks, prm.precision)
    t2d, d2t = associate(pred, tracks.active, boxes2d, v3, prm.iou_assoc)
    tracks = update(tracks, t2d, boxes2d, prm.max_age, prm.precision)
    tracks, d2t = spawn(tracks, boxes2d, v3, d2t)
    tracks = set_box3d(tracks, d2t, b3, v3)
    n = jnp.sum(v3)
    mean = jnp.sum(jnp.where(v3[:, None], b3[:, 3:6], 0.0), 0) \
        / jnp.maximum(n, 1)
    avg = jnp.where(n > 0, mean, st.avg_size)
    return (tracks, avg, st.key), (b3, v3, d2t)


def _transform(st: State, pts, b2, v2, limg, tr, p, prm):
    key, sub = jax.random.split(st.key)
    tracks, pred = predict(st.tracks, prm.precision)
    t2d, d2t = associate(pred, tracks.active, b2, v2, prm.iou_assoc)
    tracks = update(tracks, t2d, b2, prm.max_age, prm.precision)
    tracks, d2t = spawn(tracks, b2, v2, d2t)
    labels = point_labels(pts, limg, tr, p, prm)
    cl, cv = clusters(pts, labels, prm)
    ti = jnp.clip(d2t, 0, st.tracks.x.shape[0] - 1)
    prior_ok = (d2t >= 0) & tracks.has_box3d[ti]
    keep = jax.vmap(lambda a, b, c, d: filter_cluster(a, b, c, d, prm))(
        cl, cv, tracks.box3d[ti][:, :3], prior_ok)
    normal, inliers, plane_ok = ransac(sub, cl, keep, prm)
    boxes, ok = jax.vmap(
        lambda a, b, c, d, e, f, g: estimate_box(a, b, c, d, e, f, g,
                                                 st.avg_size, prm))(
        cl, inliers, keep, normal, plane_ok, prior_ok, tracks.box3d[ti])
    valid = ok & v2
    tracks = set_box3d(tracks, d2t, boxes, valid)
    return (tracks, st.avg_size, key), (boxes, valid, d2t)


def stream_step(st: State, inp, test_arrived, tr, p, prm: Params):
    """One frame of one stream: (state, FrameInputs row, arrival flag) ->
    (state, [is_anchor, send_test, f1, precision, recall, n_assoc,
    n_valid])."""
    points, det2d, val2d, label_img, det3d, val3d, gt, gvis = inp
    sc = st.sched
    anchor = sc.anchor_pending
    send_test = (~anchor) & (sc.frames_since_test >= prm.n_t - 1) \
        & (~sc.test_inflight)
    (tracks, avg, key), (boxes, valid, d2t) = jax.lax.cond(
        anchor,
        lambda op: _anchor(op[0], op[5], op[6], tr, p, prm),
        lambda op: _transform(op[0], op[1], op[2], op[3], op[4], tr, p, prm),
        (st, points, det2d, val2d, label_img, det3d, val3d))
    # the cloud's answer to a test frame is that frame's own 3D detections
    tb = jnp.where(test_arrived, st.inflight_boxes, sc.buf_boxes)
    tv = jnp.where(test_arrived, st.inflight_valid, sc.buf_valid)
    buf_b = jnp.where(send_test, boxes, sc.buf_boxes)
    buf_v = jnp.where(send_test, valid, sc.buf_valid)
    tf1, _, _ = f1_score(buf_b, buf_v, tb, tv, prm.f1_iou)
    bad = sc.test_inflight & test_arrived & (tf1 < prm.q_t)
    sched = Sched(
        frames_since_test=jnp.where(send_test | anchor, 0,
                                    sc.frames_since_test + 1),
        test_inflight=(sc.test_inflight & ~test_arrived) | send_test,
        buf_boxes=buf_b, buf_valid=buf_v,
        anchor_pending=jnp.where(anchor, False, sc.anchor_pending) | bad)
    f1, prec, rec = f1_score(boxes, valid, gt, gvis, prm.f1_iou)
    packed = jnp.stack([anchor.astype(jnp.float32),
                        send_test.astype(jnp.float32), f1, prec, rec,
                        jnp.sum((d2t >= 0) & valid).astype(jnp.float32),
                        jnp.sum(valid).astype(jnp.float32)])
    st = State(tracks=tracks, avg_size=avg, key=key, sched=sched,
               inflight_boxes=jnp.where(send_test, det3d, st.inflight_boxes),
               inflight_valid=jnp.where(send_test, val3d, st.inflight_valid))
    return st, packed


@functools.partial(jax.jit, static_argnames=("prm",))
def fleet_round(state: State, inp, test_arrived, tr, p, prm: Params):
    """Every stream's frame of one round (streams on the leading axis)."""
    return jax.vmap(lambda s, i, a: stream_step(s, i, a, tr, p, prm))(
        state, inp, test_arrived)


def init_fleet(n_streams: int, prm: Params) -> State:
    """Stream i's PRNG key is ``jax.random.key(i)``."""
    return jax.vmap(lambda i: init_state(i, prm))(jnp.arange(n_streams))
