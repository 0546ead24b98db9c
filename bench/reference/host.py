"""Plain reference of a drive: W rounds of an S-vehicle fleet.

Each round runs :func:`bench.reference.step.fleet_round` for every vehicle,
then does the fleet's bookkeeping on the host the way the configuration
states it: this round's anchor and test uploads split one cell uplink
(a bandwidth trace with the published statistics of the named cellular
trace, AR(1) at 100 ms, fair shares), the cloud's requests of the round
are served as batches on a GPU pool, and each stream-frame gets its
modelled latency: an anchor waits for its round trip, any other frame
costs the on-board component model (Fig. 15 of the paper) for its share
of new and tracked objects. A test frame's answer arrives once the
stream's own clock passes its round trip.

Nothing here is imported from the program; the numbers come from the
configuration file's ``deployment`` section.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import step as ref_step

COL_IS_ANCHOR, COL_SEND_TEST, COL_N_ASSOC, COL_N_VALID = 0, 1, 5, 6
TRACE_DT = 0.1


def uplink_trace(stats: dict, seed: int, seconds: float = 600.0
                 ) -> np.ndarray:
    """AR(1) (rho 0.98 per 100 ms) Mbps series with the trace's mean and
    standard deviation, clipped to its range; seeded from the trace's name
    and the run's seed."""
    rng = np.random.default_rng(
        zlib.crc32(stats["name"].encode()) % (2 ** 31) + seed)
    n = int(seconds / TRACE_DT)
    rho = 0.98
    x = np.empty(n)
    x[0] = 0.0
    innov = rng.normal(0, 1, n)
    for i in range(1, n):
        x[i] = rho * x[i - 1] + np.sqrt(1 - rho ** 2) * innov[i]
    return np.clip(stats["mean"] + stats["std"] * x, stats["lo"], stats["hi"])


def transfer_s(trace: np.ndarray, t: float, n_bytes: int, n_sharers: int,
               rtt_s: float) -> float:
    """Seconds to push ``n_bytes`` from time ``t`` at a 1/n_sharers share
    of the trace, after one RTT of request overhead."""
    share = 1.0 / max(int(n_sharers), 1)
    remaining = n_bytes * 8 / 1e6
    elapsed = rtt_s
    i = int((t + elapsed) / TRACE_DT)
    while remaining > 0:
        bw = trace[i % len(trace)] * share
        sent = bw * TRACE_DT
        if sent >= remaining:
            elapsed += remaining / bw
            remaining = 0.0
        else:
            remaining -= sent
            elapsed += TRACE_DT
            i += 1
    return elapsed


class GpuPool:
    """Round batching on G cloud GPUs: a round's requests (arriving
    together) form batches of at most ``max_batch``, dispatched
    round-robin; a batch of b costs ``infer_s * (1 + marginal * (b-1))``
    and starts when its GPU is free."""

    def __init__(self, infer_s: float, marginal: float, max_batch: int,
                 n_gpus: int):
        self.infer_s, self.marginal = infer_s, marginal
        self.max_batch, self.n_gpus = max_batch, n_gpus
        self.busy = [0.0] * n_gpus
        self.rr = 0

    def serve(self, arrive: float, n: int) -> list:
        done = []
        for lo in range(0, n, self.max_batch):
            b = min(self.max_batch, n - lo)
            g = self.rr % self.n_gpus
            self.rr = (g + 1) % self.n_gpus
            finish = max(self.busy[g], arrive) \
                + self.infer_s * (1.0 + self.marginal * (max(b, 1) - 1))
            self.busy[g] = finish
            done += [finish] * b
        return done


def cloud_infer_s(dep: dict) -> float:
    """The cloud detector's single-frame latency: published GFLOPs over
    the cloud GPU's sustained rate, plus its fixed overhead."""
    det, gpu = dep["detector"], dep["cloud_gpu"]
    return det["gflops"] * 1e9 / (gpu["peak_flops"] * det["efficiency"]) \
        + gpu["fixed_overhead_s"]


def onboard_s(comp: dict, n_assoc: float, n_new: float) -> float:
    """On-board time of a transform frame: 2D segmentation, projection,
    filtration, box estimation weighted by new versus tracked objects,
    tracking-based association and the test-frame scoring."""
    t = comp["seg_2d"] + comp["point_proj"] + comp["filtration"]
    frac_new = n_new / max(n_assoc + n_new, 1)
    t += frac_new * comp["bbox_est_new"] \
        + (1 - frac_new) * comp["bbox_est_assoc"]
    return t + comp["tba"] + comp["fos"]


def drive(stack, prm: ref_step.Params, tr: np.ndarray, p: np.ndarray,
          dep: dict, frame_dt: float, seed: int) -> dict:
    """Serve ``stack`` (the (S, W, ...) tape arrays, program field order)
    for W rounds from a fresh state. Returns (S, W) arrays ``kind``,
    ``latency_s``, ``onboard_s``, ``f1``, ``precision``, ``recall``."""
    s_n, w_n = stack[0].shape[:2]
    trace = uplink_trace(dep["uplink"], seed)
    rtt = dep["uplink"]["rtt_s"]
    cl = dep["cloud"]
    pool = GpuPool(cloud_infer_s(dep), cl["marginal"], cl["max_batch"],
                   cl["n_gpus"])
    comp = dep["edge_components_s"]
    pc_bytes, res_bytes = dep["pc_bytes"], dep["result_bytes"]
    trj, pj = jnp.asarray(tr), jnp.asarray(p)
    state = ref_step.init_fleet(s_n, prm)
    walls = np.zeros(s_n)
    inflight_at = np.full(s_n, np.inf)
    net_t = 0.0
    cols = np.zeros((s_n, w_n, 9), np.float32)
    for t in range(w_n):
        arrived = walls >= inflight_at
        state, packed = ref_step.fleet_round(
            state, tuple(jnp.asarray(a[:, t]) for a in stack),
            jnp.asarray(arrived), trj, pj, prm)
        pk = np.asarray(packed)
        is_anchor = pk[:, COL_IS_ANCHOR] > 0.5
        send_test = pk[:, COL_SEND_TEST] > 0.5
        inflight_at[arrived] = np.inf
        senders = is_anchor | send_test
        n_up = int(senders.sum())
        roundtrip = np.zeros(s_n)
        if n_up:
            up = transfer_s(trace, net_t, pc_bytes, n_up, rtt)
            down = transfer_s(trace, net_t, res_bytes, n_up, rtt)
            done = pool.serve(net_t + up, n_up)
            for j, s in enumerate(np.flatnonzero(senders)):
                roundtrip[s] = (done[j] - net_t) + down
        lat = np.zeros(s_n)
        onb = np.zeros(s_n)
        for s in range(s_n):
            if is_anchor[s]:
                lat[s] = roundtrip[s]
            else:
                n_assoc = int(pk[s, COL_N_ASSOC])
                n_new = max(int(pk[s, COL_N_VALID]) - n_assoc, 0)
                onb[s] = lat[s] = onboard_s(comp, n_assoc, n_new)
            if send_test[s]:
                inflight_at[s] = walls[s] + roundtrip[s]
        cols[:, t, :7] = pk
        cols[:, t, 7] = lat
        cols[:, t, 8] = onb
        walls += np.where(is_anchor, np.maximum(frame_dt, lat), frame_dt)
        net_t += frame_dt
    jax.block_until_ready(state)
    kind = np.where(cols[..., COL_IS_ANCHOR] > 0.5, "anchor",
                    np.where(cols[..., COL_SEND_TEST] > 0.5, "test",
                             "transform"))
    return {"kind": kind, "latency_s": cols[..., 7], "onboard_s": cols[..., 8],
            "f1": cols[..., 2], "precision": cols[..., 3],
            "recall": cols[..., 4]}
