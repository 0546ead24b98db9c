"""Device-resident fleet stepping: S streams, one dispatch per frame.

:func:`make_fleet_step` builds a jitted function advancing every stream in
one call — ``jax.vmap`` over streams of the fused (``lax.cond``-selected)
anchor/transform step plus the vmapped frame-offloading scheduler. The host
supplies only test-arrival flags (it owns the network clock) and fetches
one small packed stats array per frame, replacing the seed engine's ~3 jit
calls and several ``.item()`` syncs *per stream-frame*.

:func:`make_fleet_scan` wraps the same step in ``lax.scan`` over frames
with an on-device network/cloud time model, so an entire fleet run is a
single dispatch (benchmark mode).

Both modes take their hot-op implementations (point projection, IoU,
RANSAC scoring) from ``params.backend`` — the static TransformParams
string resolved through the ops registry — so the whole vmapped fleet
jits cleanly under either the ref or the Pallas backend.

**Sharded megafleet** (``mesh=``): both builders accept a 1-D ``streams``
device mesh (``launch.mesh.make_fleet_mesh``). Both shard every
(S, ...) carry/input buffer along it and run per shard under
``jax.shard_map``; the scan twin adds a cross-shard ``psum`` of the
round's sender count — the one scalar that couples streams — so the
shared-uplink byte total / bandwidth shares and
the cloud GPU-pool queue depth stay *globally* consistent, not
per-shard-local. The replicated pool state (busy clocks, round-robin
pointer) is then recomputed identically on every shard. On a 1-device
mesh both modes are bitwise identical to the unsharded path
(tests/test_sharded_fleet.py). The carry is donated on both dispatches,
so device memory stays flat in run length and fleet size
(``runtime.hlo_analysis.donated_params`` checks the compiled HLO).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import metrics, scheduler, transform
from repro.serving.common import ComponentTimes, nominal_transform_time

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
# Scan mode appends two more columns (modelled times).
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


def onboard_time_vec(comp: ComponentTimes, n_assoc: jnp.ndarray,
                     n_new: jnp.ndarray, use_tba: bool,
                     use_fos: bool) -> jnp.ndarray:
    """Traceable twin of serving.common.onboard_transform_time."""
    t = comp.seg_2d + comp.point_proj + comp.filtration
    total = jnp.maximum(n_assoc + n_new, 1.0)
    frac_new = n_new / total
    t = t + frac_new * comp.bbox_est_new + (1 - frac_new) * comp.bbox_est_assoc
    if use_tba:
        t = t + comp.tba
    if use_fos:
        t = t + comp.fos
    return t


class ScanNetParams(NamedTuple):
    """On-device network + cloud model for scan (benchmark) mode.

    A one-tick fair-share approximation of SharedUplink + CloudBatcher:
    transfer time is rtt + bits / (trace bandwidth / concurrent senders),
    and same-frame cloud requests form one batch on a single server.
    """
    bw_mbps: jnp.ndarray       # (T,) synthesized cell-uplink trace
    trace_dt: float
    rtt_s: float
    frame_dt: float
    pc_mbits: float            # LiDAR frame upload size
    result_mbits: float        # detections download size
    infer_s: float             # cloud detector, batch of 1
    marginal: float            # marginal batch cost (CloudBatcherConfig)
    max_batch: int             # detector batch-size ceiling (chunks beyond)
    n_gpus: int = 1            # cloud GPU pool size (CloudBatcherConfig)
    # Batch window (CloudBatcherConfig.window_s; None = round batching).
    # Mirrored from the host batcher: a window also closes a batch when
    # the next request arrived more than window_s after the batch opener.
    # All of a scan round's requests arrive at the same modeled instant
    # (net_t + up), exactly like the host engine's per-round
    # ``submit_batch([t + up] * n)`` — so for any window_s >= 0 the
    # window never splits a round and chunking stays at max_batch, in
    # agreement with CloudBatcher._batches on simultaneous arrivals
    # (tests/test_sharded_fleet.py::TestScanWindowAgreement).
    window_s: float = None


class ScanConsts(NamedTuple):
    """Per-run constants of the scan body, passed as explicit (sharded)
    operands rather than closures so the body runs unchanged under
    ``shard_map`` — per-stream (S,) vectors carry a ``streams`` spec and
    arrive per-shard as (S/D,) slices, the trace is replicated. Values
    are pre-rounded to f32 exactly as the closure path converted them, so
    the unsharded and sharded twins stay bitwise identical."""
    bw_trace: jnp.ndarray      # (T,) cell-uplink trace, replicated
    edge_cost_s: jnp.ndarray   # (S,) modeled on-device frame cost
    edge_infer_s: jnp.ndarray  # (S,) edge detector latency (onboard mode)
    ob_base: jnp.ndarray       # (S,) seg+proj+filtration time
    ob_new: jnp.ndarray        # (S,) bbox estimation, unassociated det
    ob_assoc: jnp.ndarray      # (S,) bbox estimation, tracked det
    ob_tba: jnp.ndarray        # (S,) tracking-based adjustment time
    ob_fos: jnp.ndarray        # (S,) FOS scoring time


def make_fleet_scan(n_streams: int, calib, params, sparams,
                    comp: ComponentTimes, net: ScanNetParams,
                    use_fos: bool = True, onboard_anchors: bool = False,
                    edge_infer_s: float = 0.0,
                    charge_fos: bool = None, mesh=None):
    """A whole fleet run in one dispatch. Returns ``(fn, consts)``: the
    jitted ``fn(consts, state, FrameInputs stacked (F, S, ...), n_frames)
    -> (state, (F, S, N_COLS + 2))`` and its :class:`ScanConsts`.

    The constants are an operand, never a closure: XLA would fold closed-
    over constants into the unsharded program's arithmetic but not into
    the per-shard one, and the two would then differ in the last bit.

    ``onboard_anchors`` mirrors the engine's ``moby_onboard`` mode: anchor
    frames run the 3D detector on the edge (``edge_infer_s``) and do not
    contend for the uplink/cloud; test frames still go to the cloud.
    ``charge_fos`` controls the per-frame FOS scoring cost in the on-board
    time model (defaults to ``use_fos``; engines pass False for policies
    that never offload test frames).

    ``mesh`` (a 1-D ``streams`` mesh) runs the scan per shard under
    ``jax.shard_map``: every (S, ...) carry/tape buffer is partitioned along
    the stream axis, while the round's sender count — the single scalar
    coupling streams through the shared uplink (byte total, bandwidth
    shares) and the cloud GPU pool (queue depth) — is ``psum``-ed across
    shards, so the contention model stays globally consistent and every
    shard recomputes identical replicated pool clocks. The carry
    (``state``) is donated in both variants.
    """
    if charge_fos is None:
        charge_fos = use_fos
    step = functools.partial(_stream_step, calib=calib, params=params,
                             sparams=sparams, use_fos=use_fos)
    vstep = jax.vmap(step, in_axes=(0, 0, 0, None))
    axis = "streams" if mesh is not None else None

    # Per-run constants: host-f64 component sums rounded to f32 once, in
    # the same order the host engine rounds them (bitwise contract with
    # the host engine's uniform-fleet parity; see ScanConsts).
    def svec(v):
        return jnp.asarray(
            np.broadcast_to(np.asarray(v, np.float64), (n_streams,)),
            jnp.float32)

    consts = ScanConsts(
        bw_trace=jnp.asarray(net.bw_mbps, jnp.float32),
        # Modeled nominal on-device frame cost (scheduler telemetry).
        edge_cost_s=svec(nominal_transform_time(comp, params.use_tba,
                                                charge_fos)),
        edge_infer_s=svec(edge_infer_s),
        ob_base=svec(comp.seg_2d + comp.point_proj + comp.filtration),
        ob_new=svec(comp.bbox_est_new),
        ob_assoc=svec(comp.bbox_est_assoc),
        ob_tba=svec(comp.tba),
        ob_fos=svec(comp.fos))

    def scan_core(cs: ScanConsts, state, walls, inflight_at, busy, rr,
                  ts, stacked: FrameInputs):
        def body(carry, xs):
            state, walls, inflight_at, busy, rr = carry
            t, inp = xs
            test_arrived = walls >= inflight_at
            net_t = t.astype(jnp.float32) * net.frame_dt
            if use_fos:
                # Telemetry for cost-aware policies — the traceable twin
                # of FleetEngine._observe_telemetry: each stream observes
                # its fair share of the current trace bandwidth plus the
                # modeled edge/offload frame costs. The share divides by
                # the GLOBAL fleet size, so shards agree with the host.
                idx_now = (net_t / net.trace_dt).astype(jnp.int32) \
                    % cs.bw_trace.shape[0]
                bw_share = cs.bw_trace[idx_now] / float(n_streams)
                offload = cs.edge_infer_s if onboard_anchors else (
                    2.0 * net.rtt_s
                    + (net.pc_mbits + net.result_mbits) / bw_share
                    + net.infer_s)
                state = state._replace(sched=scheduler.observe_telemetry(
                    state.sched, bw_mbps=bw_share,
                    edge_cost_s=cs.edge_cost_s, offload_cost_s=offload))
            state, packed = vstep(state, inp, test_arrived, t)
            is_anchor = packed[:, COL_IS_ANCHOR] > 0.5
            send_test = packed[:, COL_SEND_TEST] > 0.5

            # Shared uplink: all of this frame's senders split the cell
            # rate (on-board anchors stay off the network). Under a mesh
            # the sender count is summed across shards — it carries both
            # the uplink byte total (n_up * pc_mbits) and the GPU-pool
            # queue depth, so shares and queueing are fleet-global.
            cloud_anchor = jnp.zeros_like(is_anchor) if onboard_anchors \
                else is_anchor
            n_up = jnp.sum(cloud_anchor | send_test)
            if axis is not None:
                n_up = jax.lax.psum(n_up, axis)
            idx = ((net_t + net.rtt_s) / net.trace_dt).astype(jnp.int32) \
                % cs.bw_trace.shape[0]
            share = cs.bw_trace[idx] \
                / jnp.maximum(n_up, 1).astype(jnp.float32)
            up = net.rtt_s + net.pc_mbits / share
            down = net.rtt_s + net.result_mbits / share

            # Cloud batcher: the round's requests are chunked at
            # max_batch like CloudBatcher (approximation: every request
            # completes with the round's last chunk). A configured batch
            # window never splits a round here — the round's requests all
            # arrive at the same modeled instant, mirroring the host
            # batcher's behavior on simultaneous arrivals (ScanNetParams
            # .window_s). With a G-GPU pool the chunks spread evenly over
            # per-GPU queues, each serving its share serially — the
            # on-device twin of CloudBatcher's round-robin dispatch.
            # Pool clocks (busy, rr) derive only from the psum-ed n_up,
            # so every shard holds identical replicated copies.
            n_req = jnp.maximum(n_up, 1).astype(jnp.float32)
            b_eff = jnp.minimum(n_req, float(net.max_batch))
            n_chunks = jnp.ceil(n_req / float(net.max_batch))
            if net.n_gpus == 1:
                start = jnp.maximum(busy, net_t + up)
                infer_b = n_chunks * net.infer_s \
                    * (1.0 + net.marginal * (b_eff - 1))
                done = start + infer_b
                busy = jnp.where(n_up > 0, done, busy)
            else:
                # Chunk j of the round goes to GPU (rr + j) % G — the
                # rotating round-robin pointer persists across rounds
                # (like CloudBatcher._rr), so consecutive 1-chunk rounds
                # still spread over the pool instead of re-queueing on
                # GPU 0.
                chunk_s = net.infer_s * (1.0 + net.marginal * (b_eff - 1))
                n_chunks_i = n_chunks.astype(jnp.int32)
                g = jnp.arange(net.n_gpus, dtype=jnp.int32)
                base = n_chunks_i // net.n_gpus
                extra = n_chunks_i - base * net.n_gpus
                n_g = (base + (jnp.mod(g - rr, net.n_gpus) < extra)) \
                    .astype(jnp.float32)                          # (G,)
                start_g = jnp.maximum(busy, net_t + up)
                done_g = start_g + n_g * chunk_s
                done = jnp.max(jnp.where(n_g > 0, done_g, -jnp.inf))
                busy = jnp.where((n_g > 0) & (n_up > 0), done_g, busy)
                rr = jnp.where(n_up > 0,
                               jnp.mod(rr + n_chunks_i, net.n_gpus), rr)
            roundtrip = (done - net_t) + down

            n_assoc = packed[:, COL_N_ASSOC]
            n_new = jnp.maximum(packed[:, COL_N_VALID] - n_assoc, 0.0)
            # Traceable twin of serving.common.onboard_transform_time,
            # from the precomputed per-stream coefficient vectors.
            total = jnp.maximum(n_assoc + n_new, 1.0)
            frac_new = n_new / total
            onboard = cs.ob_base + frac_new * cs.ob_new \
                + (1.0 - frac_new) * cs.ob_assoc
            if params.use_tba:
                onboard = onboard + cs.ob_tba
            if charge_fos:
                onboard = onboard + cs.ob_fos
            anchor_latency = cs.edge_infer_s if onboard_anchors \
                else roundtrip
            latency = jnp.where(is_anchor, anchor_latency, onboard)
            onboard = jnp.where(is_anchor, 0.0, onboard)

            inflight_at = jnp.where(test_arrived, jnp.inf, inflight_at)
            inflight_at = jnp.where(send_test, walls + roundtrip,
                                    inflight_at)
            walls = walls + jnp.where(is_anchor,
                                      jnp.maximum(net.frame_dt, latency),
                                      net.frame_dt)
            out = jnp.concatenate(
                [packed, latency[:, None], onboard[:, None]], axis=1)
            return (state, walls, inflight_at, busy, rr), out

        carry = (state, walls, inflight_at, busy, rr)
        (state, _, _, _, _), outs = jax.lax.scan(body, carry, (ts, stacked))
        return state, outs

    if mesh is None:
        core = scan_core
    else:
        s = P("streams")
        cs_specs = ScanConsts(bw_trace=P(), edge_cost_s=s, edge_infer_s=s,
                              ob_base=s, ob_new=s, ob_assoc=s,
                              ob_tba=s, ob_fos=s)
        core = jax.shard_map(
            scan_core, mesh=mesh,
            in_specs=(cs_specs, s, s, s, P(), P(), P(), P(None, "streams")),
            out_specs=(s, P(None, "streams")),
            check_vma=False)

    def run(cs: ScanConsts, state, stacked: FrameInputs, n_frames: int):
        busy0 = jnp.float32(0.0) if net.n_gpus == 1 \
            else jnp.zeros((net.n_gpus,), jnp.float32)
        return core(cs, state,
                    jnp.zeros((n_streams,), jnp.float32),
                    jnp.full((n_streams,), jnp.inf, jnp.float32),
                    busy0,
                    jnp.int32(0),    # round-robin GPU pointer (G > 1)
                    jnp.arange(n_frames, dtype=jnp.int32),
                    stacked)

    fn = jax.jit(run, static_argnames=("n_frames",), donate_argnums=(1,))
    return fn, consts
