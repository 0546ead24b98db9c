"""Device-resident fleet stepping: S streams, one dispatch per frame.

:func:`make_fleet_step` builds a jitted function advancing every stream in
one call — ``jax.vmap`` over streams of the fused (``lax.cond``-selected)
anchor/transform step plus the vmapped frame-offloading scheduler. The host
supplies only test-arrival flags (it owns the network clock) and fetches
one small packed stats array per frame, replacing the seed engine's ~3 jit
calls and several ``.item()`` syncs *per stream-frame*.

The step takes its hot-op implementations (point projection, IoU,
RANSAC scoring) from ``params.backend`` — the static TransformParams
string resolved through the ops registry — so the whole vmapped fleet
jits cleanly under either the ref or the Pallas backend.

**Sharded megafleet** (``mesh=``): the builder accepts a 1-D ``streams``
device mesh (``launch.mesh.make_fleet_mesh``). It shards every (S, ...)
carry/input buffer along it and runs per shard under ``jax.shard_map``.
The step has no cross-stream math; the uplink and cloud-pool contention
that couples streams stays on the host (fleet.engine). On a 1-device
mesh the step is bitwise identical to the unsharded path
(tests/test_sharded_fleet.py). The carry is donated, so device memory
stays flat in run length and fleet size
(``runtime.hlo_analysis.donated_params`` checks the compiled HLO).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import metrics, scheduler, transform

# Stage names (``jax.named_scope``) of the step's work around the transform,
# beside ``transform.STAGE_*``: the scheduler's pre and post, and the F1
# score with the packing of the stats row.
STAGE_SCHEDULER = "scheduler"
STAGE_SCORE = "score"

# Columns of the packed per-stream stats row (the one host fetch per frame).
COL_IS_ANCHOR = 0
COL_SEND_TEST = 1
COL_F1 = 2
COL_PRECISION = 3
COL_RECALL = 4
COL_N_ASSOC = 5
COL_N_VALID = 6
N_COLS = 7
# The host appends two more columns (modelled times; fleet.engine).
COL_LATENCY = 7
COL_ONBOARD = 8


class FrameInputs(NamedTuple):
    """One frame of per-stream inputs; every array has a leading S axis
    when passed to the vmapped step (see serving.tape for the recording)."""
    points: jnp.ndarray      # (S, N, 3)
    det2d: jnp.ndarray       # (S, D, 4)
    val2d: jnp.ndarray       # (S, D)
    label_img: jnp.ndarray   # (S, H, W)
    det3d: jnp.ndarray       # (S, D, 7)
    val3d: jnp.ndarray       # (S, D)
    gt_boxes: jnp.ndarray    # (S, D, 7)
    gt_visible: jnp.ndarray  # (S, D)


class FleetState(NamedTuple):
    """All device-resident per-stream state, stacked on a leading S axis."""
    moby: transform.MobyState          # tracker + avg size + PRNG key
    sched: scheduler.SchedulerState    # frame-offloading state machine
    inflight_boxes: jnp.ndarray        # (S, D, 7) latched test payloads
    inflight_valid: jnp.ndarray        # (S, D)


def init_fleet_state(n_streams: int, max_obj: int,
                     key_base: int = 0, stream_seeds=None) -> FleetState:
    """Stream i's PRNG seed is ``key_base + i`` so stream 0 of a fleet
    matches a single-stream engine seeded with ``key_base`` (parity).
    ``stream_seeds`` (length-S ints) overrides the per-stream seeds —
    relabeling streams (tape, device, seed together) then permutes the
    fleet exactly (tests/test_heterogeneity.py)."""
    if stream_seeds is None:
        seeds = key_base + jnp.arange(n_streams)
    else:
        if len(stream_seeds) != n_streams:
            raise ValueError(f"got {len(stream_seeds)} stream seeds for "
                             f"{n_streams} streams")
        seeds = jnp.asarray(stream_seeds, jnp.int32)
    keys = jax.vmap(jax.random.key)(seeds)
    moby = jax.vmap(lambda k: transform.init_state(2 * max_obj, k))(keys)
    sched = scheduler.init_scheduler_fleet(n_streams, max_obj)
    return FleetState(
        moby=moby, sched=sched,
        inflight_boxes=jnp.zeros((n_streams, max_obj, 7), jnp.float32),
        inflight_valid=jnp.zeros((n_streams, max_obj), bool))


def _stream_step(state: FleetState, inp: FrameInputs,
                 test_arrived: jnp.ndarray, t: jnp.ndarray,
                 calib, params, sparams, use_fos: bool):
    """One stream, one frame — fully traceable (no host branching)."""
    with jax.named_scope(STAGE_SCHEDULER):
        if use_fos:
            actions = scheduler.scheduler_pre(state.sched, sparams)
        else:
            actions = scheduler.SchedulerActions(send_test=jnp.bool_(False),
                                                 run_as_anchor=t == 0)
    mstate, out = transform.fused_step(
        state.moby, inp.points, inp.det2d, inp.val2d, inp.label_img,
        inp.det3d, inp.val3d, actions.run_as_anchor, calib, params)

    # The cloud's answer for an in-flight test frame is that frame's own 3D
    # detections, latched on-device at send time — the host only supplies
    # the *arrival timing* (it owns the network clock).
    with jax.named_scope(STAGE_SCHEDULER):
        tb = jnp.where(test_arrived, state.inflight_boxes,
                       state.sched.buf_boxes)
        tv = jnp.where(test_arrived, state.inflight_valid,
                       state.sched.buf_valid)
        sched_state = state.sched
        if use_fos:
            sched_state = scheduler.scheduler_post(
                sched_state, actions, out.boxes3d, out.valid, test_arrived,
                tb, tv, sparams)
        new_ib = jnp.where(actions.send_test, inp.det3d, state.inflight_boxes)
        new_iv = jnp.where(actions.send_test, inp.val3d, state.inflight_valid)

    with jax.named_scope(STAGE_SCORE):
        f1, prec, rec = metrics.f1_score(out.boxes3d, out.valid,
                                         inp.gt_boxes, inp.gt_visible)
        n_assoc = jnp.sum((out.det_to_track >= 0) & out.valid)
        n_valid = jnp.sum(out.valid)
        packed = jnp.stack([
            actions.run_as_anchor.astype(jnp.float32),
            actions.send_test.astype(jnp.float32),
            f1, prec, rec,
            n_assoc.astype(jnp.float32), n_valid.astype(jnp.float32)])
    return FleetState(mstate, sched_state, new_ib, new_iv), packed


def make_fleet_step(calib, params, sparams, use_fos: bool = True,
                    mesh=None):
    """Jitted (state, FrameInputs[S], test_arrived[S], t) -> (state, (S, N_COLS)).

    The carry (arg 0) is donated: per-frame stepping reuses the state
    buffers in place. With ``mesh`` (a 1-D ``streams`` mesh) every
    (S, ...) input and output is sharded along the stream axis, and each
    shard steps its own streams under ``jax.shard_map`` (XLA cannot
    partition a Pallas kernel by itself). The step has no cross-stream
    math, so the partitioned dispatch is embarrassingly parallel
    (contention stays on the host, which already computes it globally)."""
    step = functools.partial(_stream_step, calib=calib, params=params,
                             sparams=sparams, use_fos=use_fos)
    vstep = jax.vmap(step, in_axes=(0, 0, 0, None))
    if mesh is None:
        return jax.jit(vstep, donate_argnums=(0,))
    s = P("streams")
    vstep = jax.shard_map(vstep, mesh=mesh, in_specs=(s, s, s, P()),
                          out_specs=(s, s), check_vma=False)
    s_sh = NamedSharding(mesh, s)
    r_sh = NamedSharding(mesh, P())
    return jax.jit(vstep, donate_argnums=(0,),
                   in_shardings=(s_sh, s_sh, s_sh, r_sh),
                   out_shardings=(s_sh, s_sh))
