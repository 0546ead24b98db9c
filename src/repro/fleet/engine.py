"""FleetEngine: batched multi-stream Moby serving.

Runs S concurrent vehicle streams through a single device-resident step per
frame (see fleet.step). Fleet-level resource contention is modelled on the
host, where the network/cloud clocks live:

* **Shared uplink** — all of a frame's anchor/test uploads split one cell's
  trace bandwidth (runtime.netsim.SharedUplink), so transfer times degrade
  with fleet size;
* **Cloud batcher** — the round's requests are batched round-robin onto a
  pool of cloud GPUs (fleet.cloud.CloudBatcher): per-item inference
  amortizes, queueing delay grows (and relaxes with pool size) — the
  frame-offloading schedulers of different vehicles now interact through
  anchor latency.

Streams may run on *heterogeneous edge hardware*: ``device`` accepts a
profile name, a per-stream list, or a mix spec, stacked into a
``profiles.ProfileVector`` — per-stream component times, edge inference
and scheduler cost telemetry (a uniform vector reproduces the scalar
``device=`` path bitwise; tests/test_heterogeneity.py).

:meth:`FleetEngine.run` serves the fleet: one jitted dispatch and one
packed stats fetch per frame for the whole fleet, with byte-accurate
netsim timing on the host. With S=1 both the inputs (serving.tape) and
the timing reduce exactly to the single-stream ``MobyEngine`` — enforced
by tests/test_fleet.py.

Sharded megafleet: ``mesh=`` (None / ``"auto"`` / a device count / a 1-D
``streams`` Mesh from ``launch.mesh.make_fleet_mesh``) partitions the
stream axis of every carry and input buffer across devices. The step is
embarrassingly parallel: contention stays on the host, fleet-global. A
1-device mesh reproduces the unsharded path bitwise
(tests/test_sharded_fleet.py).
"""
from __future__ import annotations

import contextlib
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import projection, scheduler, transform
from repro.data import scenes
from repro.fleet import cloud as cloud_lib
from repro.fleet import step as step_lib
from repro.launch import mesh as mesh_lib
from repro.obs import observe as obs_lib
from repro.runtime import netsim, profiles
from repro.serving import tape as tape_lib
from repro.serving.common import (PC_BYTES, RESULT_BYTES, ComponentTimes,
                                  RunReport, modeled_frame_costs,
                                  onboard_transform_time)

_NULL_CTX = contextlib.nullcontext()


def report_from_packed(packed_sf: np.ndarray,
                       devices: Optional[Sequence[str]] = None) -> RunReport:
    """Build a RunReport from a (S, F, COL_ONBOARD+1) packed stats array
    (the scheduler's anchor/test bits are mutually exclusive, so the kind
    string per frame is lossless). ``devices`` stamps the per-stream
    device-profile names onto the report."""
    p = packed_sf
    is_anchor = p[:, :, step_lib.COL_IS_ANCHOR] > 0.5
    send_test = p[:, :, step_lib.COL_SEND_TEST] > 0.5
    kind = np.where(is_anchor, "anchor",
                    np.where(send_test, "test", "transform")).astype("<U12")
    return RunReport(kind=kind,
                     latency_s=p[:, :, step_lib.COL_LATENCY],
                     onboard_s=p[:, :, step_lib.COL_ONBOARD],
                     f1=p[:, :, step_lib.COL_F1],
                     precision=p[:, :, step_lib.COL_PRECISION],
                     recall=p[:, :, step_lib.COL_RECALL],
                     device=None if devices is None
                     else np.asarray(list(devices)))


class FleetEngine:
    def __init__(self, scene_cfg: scenes.SceneConfig, detector: str,
                 n_streams: int, trace: str = "belgium2", mode: str = "moby",
                 use_fos: bool = True, use_tba: bool = True,
                 tparams: Optional[transform.TransformParams] = None,
                 sparams: Optional[scheduler.SchedulerParams] = None,
                 seed: int = 0, comp: Optional[ComponentTimes] = None,
                 tapes: Optional[Sequence[tape_lib.FrameTape]] = None,
                 cloud_cfg: Optional[cloud_lib.CloudBatcherConfig] = None,
                 backend: Optional[str] = None,
                 device: profiles.DeviceSpec = "jetson_tx2",
                 stream_seeds: Optional[Sequence[int]] = None,
                 obs: Optional[obs_lib.ObsConfig] = None,
                 mesh=None):
        if mode not in ("moby", "moby_onboard"):
            raise ValueError(f"FleetEngine serves moby modes, got {mode!r}")
        self.cfg = scene_cfg
        self.detector = detector
        self.n_streams = n_streams
        self.mode = mode
        self.use_fos = use_fos
        self.use_tba = use_tba
        # Edge device profiles, one per stream (runtime.profiles): a name,
        # an S-list, or a mix spec resolve to a ProfileVector — modeled
        # component times, edge inference and scheduler telemetry are all
        # per-stream. The cloud side stays on the 2080Ti profile.
        self.pvec = profiles.profile_vector(device, n_streams)
        self.stream_devices = self.pvec.names
        # Stacked (S,)-field component model for the telemetry (an
        # explicitly passed scalar `comp` broadcasts to every stream),
        # plus per-stream scalar slices for the host loop.
        self.comp = comp or profiles.component_times_vector(self.pvec)
        self.comps = [profiles.component_slice(self.comp, s)
                      for s in range(n_streams)]
        self.seed = seed
        if stream_seeds is not None and len(stream_seeds) != n_streams:
            raise ValueError(f"got {len(stream_seeds)} stream seeds for "
                             f"{n_streams} streams")
        self.stream_seeds = None if stream_seeds is None \
            else tuple(int(s) for s in stream_seeds)
        self.frame_dt = scene_cfg.dt
        base = tparams or transform.TransformParams()
        # Ops backend threaded to every vmapped stream step via the static
        # TransformParams ("ref" / "pallas"; None keeps tparams.backend).
        # Resolved + pinned at construction (see resolve_backend_params).
        self.tparams = transform.resolve_backend_params(
            base._replace(use_tba=use_tba), backend)
        self.sparams = sparams or scheduler.SchedulerParams()
        # FOS scoring cost applies only to test-offloading policies (see
        # serving.engine).
        self._charge_fos = use_fos and \
            scheduler.get_policy(self.sparams.policy).uses_tests
        tr, p = scenes.make_calibration(scene_cfg)
        self.calib = projection.Calibration(
            tr=jnp.asarray(tr), p=jnp.asarray(p),
            height=scene_cfg.img_h, width=scene_cfg.img_w)
        self.uplink = netsim.SharedUplink(trace, seed=seed)
        infer = profiles.detector_latency(detector, profiles.RTX_2080TI)
        cc = cloud_cfg or cloud_lib.CloudBatcherConfig()
        if cc.infer_s is None:
            # Fill the detector-derived per-frame latency (presets set
            # n_gpus/window without knowing the detector).
            cc = cloud_lib.replace_config(cc, infer_s=infer)
        self.cloud_cfg = cc
        self.batcher = cloud_lib.CloudBatcher(self.cloud_cfg)
        # Observability config (repro.obs): None/all-off keeps every hook
        # below a single pointer test — disabled runs are bitwise
        # identical (tests/test_obs.py).
        self.obs_config = obs
        self._given_tapes = list(tapes) if tapes is not None else None
        self._stack: Optional[tape_lib.FrameTape] = None
        # Stream-axis device mesh (launch.mesh): None / "auto" / a device
        # count / a ready Mesh. Resolved once; the step shards every
        # (S, ...) buffer along it and contention stays fleet-global.
        self.mesh = mesh_lib.resolve_fleet_mesh(mesh, n_streams)
        self.n_shards = 1 if self.mesh is None \
            else int(self.mesh.devices.size)
        self._step = step_lib.make_fleet_step(
            self.calib, self.tparams, self.sparams, use_fos,
            mesh=self.mesh)

    # ------------------------------------------------------------------
    def _stacked(self, n_frames: int) -> tape_lib.FrameTape:
        if self._given_tapes is not None:
            # Caller-supplied data plane: validate, never substitute.
            if len(self._given_tapes) != self.n_streams:
                raise ValueError(
                    f"got {len(self._given_tapes)} tapes for "
                    f"{self.n_streams} streams")
            if self._given_tapes[0].n_frames < n_frames:
                raise ValueError(
                    f"tapes hold {self._given_tapes[0].n_frames} frames, "
                    f"run asked for {n_frames}")
        if self._stack is None or self._stack.points.shape[1] < n_frames:
            tapes = self._given_tapes or tape_lib.record_fleet_tapes(
                self.cfg, self.detector, n_frames, self.n_streams,
                seed=self.seed)
            self._stack = tape_lib.stack_tapes(tapes)
        return tape_lib.FrameTape(*(a[:, :n_frames] for a in self._stack))

    def _edge_infer(self) -> np.ndarray:
        """(S,) per-stream edge inference latency from the profile vector."""
        return np.asarray(
            profiles.detector_latency(self.detector, self.pvec), np.float64)

    def _observe_telemetry(self, state: step_lib.FleetState,
                           obs: Optional[obs_lib.Observer] = None
                           ) -> step_lib.FleetState:
        """Per-frame telemetry for cost-aware policies: every stream of
        the fleet shares the cell, so each observes its fair share of the
        current trace bandwidth; edge/offload costs are per-stream vectors
        from the profile vector (slow streams see their own frame cost, so
        the adaptive budget anchors them on their own cadence). ``obs``
        records the same host-computed values for the decision audit."""
        bw = self.uplink.current_bw_mbps(n_sharers=self.n_streams)
        edge, off = modeled_frame_costs(
            self.comp, self.detector, bw, self.uplink.rtt_s, self.use_tba,
            self._charge_fos, onboard_anchors=self.mode == "moby_onboard",
            edge_device=self.pvec)
        if obs is not None:
            obs.note_telemetry(bw, edge, off)
        sched = scheduler.observe_telemetry(state.sched, bw_mbps=bw,
                                            edge_cost_s=edge,
                                            offload_cost_s=off)
        return state._replace(sched=sched)

    def _put(self, a: np.ndarray, spec: P) -> jax.Array:
        """Host array -> device. Under a mesh it goes straight into its
        ``spec`` shards (``P()``: a copy on every chip) instead of landing
        whole on device 0 first."""
        if self.mesh is None:
            return jnp.asarray(a)
        return jax.device_put(a, NamedSharding(self.mesh, spec))

    def _frame_inputs(self, stack: tape_lib.FrameTape,
                      t: int) -> step_lib.FrameInputs:
        return step_lib.FrameInputs(
            *(self._put(a[:, t], P("streams")) for a in stack))

    # ------------------------------------------------------------------
    def run(self, n_frames: int) -> RunReport:
        """Orchestrated serving: one device dispatch + one stats fetch per
        frame for all S streams; byte-accurate shared-uplink/cloud timing.

        Observed, each round is a ``fleet/round`` span whose phases are
        its children (``parent`` = the round's index): ``fleet/inputs``
        (every host->device put of the round: the tape's frame, the test
        arrivals and the round index, with their ``puts``, the ``bytes``
        that land on the chips, the ``chips`` they are split over and
        ``bytes_per_chip``), ``fleet/telemetry``, ``fleet/dispatch`` (the
        launch alone), ``fleet/fetch`` (with its ``chips``) and
        ``fleet/contention`` (uplink, cloud pool and latency bookkeeping,
        with the round's ``senders``); ``fleet/prologue`` and
        ``fleet/epilogue`` hold the run's set-up and report."""
        s_n = self.n_streams
        obs = obs_lib.make_observer(
            self.obs_config, n_streams=s_n, devices=self.stream_devices,
            policy=self.sparams.policy if self.use_fos else "",
            detector=self.detector, frame_dt=self.frame_dt,
            n_shards=self.n_shards)
        with obs.measured_span("fleet/prologue") if obs is not None \
                else _NULL_CTX:
            stack = self._stacked(n_frames)
            want_audit = obs is not None and obs.cfg.want_audit
            if obs is not None:
                # Bytes one round's puts land on the chips (the same every
                # round): the stream-sharded frame and arrivals once, the
                # replicated round index on every chip.
                n_puts = len(stack) + 2
                in_bytes = sum(a[:, 0].nbytes for a in stack) + s_n \
                    + np.dtype(np.int32).itemsize * self.n_shards
            self.batcher.sink = obs
            state = self._init_state()
            edge_inf = self._edge_infer()   # (S,), frame-invariant
            walls = np.zeros(s_n)
            inflight_at = np.full(s_n, np.inf)
            self.uplink.reset()
            self.batcher.reset()
            out = np.zeros((s_n, n_frames, step_lib.COL_ONBOARD + 1),
                           np.float32)

        for t in range(n_frames):
            with obs.measured_span("fleet/round", frame=t) \
                    if obs is not None else _NULL_CTX:
                # Set by the previous round's contention.
                arrived = walls >= inflight_at
                with obs.measured_span(
                        "fleet/inputs", parent=t, frame=t, bytes=in_bytes,
                        puts=n_puts, chips=self.n_shards,
                        bytes_per_chip=in_bytes // self.n_shards) \
                        if obs is not None else _NULL_CTX:
                    inp = self._frame_inputs(stack, t)
                    arrived_d = self._put(arrived, P("streams"))
                    t_d = self._put(np.int32(t), P())
                pre_tel = None
                with obs.measured_span("fleet/telemetry", parent=t,
                                       frame=t) if obs is not None \
                        else _NULL_CTX:
                    if self.use_fos:
                        state = self._observe_telemetry(state, obs)
                    if want_audit:
                        # The only obs-added fetch: the state-resident
                        # policy inputs at decision time (one small (2, S)
                        # array).
                        pre_tel = np.asarray(
                            scheduler.decision_telemetry(state.sched))
                with obs.measured_span("fleet/dispatch", jit_fn=self._step,
                                       parent=t, frame=t) \
                        if obs is not None else _NULL_CTX:
                    state, packed = self._step(state, inp, arrived_d, t_d)
                with obs.measured_span("fleet/fetch", parent=t, frame=t,
                                       chips=self.n_shards) \
                        if obs is not None else _NULL_CTX:
                    pk = np.asarray(packed)        # the one fetch per frame
                with obs.measured_span("fleet/contention", parent=t,
                                       frame=t) if obs is not None \
                        else _NULL_CTX as phase:
                    n_up = self._contend(t, pk, arrived, edge_inf, walls,
                                         inflight_at, out, obs, pre_tel)
                    if obs is not None:
                        phase["senders"] = n_up
        with obs.measured_span("fleet/epilogue") if obs is not None \
                else _NULL_CTX:
            report = report_from_packed(out, devices=self.stream_devices)
            report.frame_dt = self.frame_dt
            if obs is not None:
                self.batcher.sink = None
                obs.finalize(report, busy_s_g=self.batcher.busy_s_g)
        return report

    def _contend(self, t: int, pk: np.ndarray, arrived: np.ndarray,
                 edge_inf: np.ndarray, walls: np.ndarray,
                 inflight_at: np.ndarray, out: np.ndarray,
                 obs: Optional[obs_lib.Observer], pre_tel) -> int:
        """Round ``t``'s fleet-level contention from its fetched stats
        ``pk``: this round's uploads share the cell uplink and its cloud
        requests are served as one batch. Writes the round's latencies
        into ``out`` and advances ``walls``, ``inflight_at`` and the
        uplink clock in place; returns the round's sender count.
        ``pre_tel``: the audit's decision-time telemetry, or None."""
        s_n = self.n_streams
        is_anchor = pk[:, step_lib.COL_IS_ANCHOR] > 0.5
        send_test = pk[:, step_lib.COL_SEND_TEST] > 0.5
        inflight_at[arrived] = np.inf

        cloud_anchor = is_anchor & (self.mode != "moby_onboard")
        senders = cloud_anchor | send_test
        n_up = int(senders.sum())
        roundtrip = np.zeros(s_n)
        if n_up:
            up = self.uplink.transfer_time(PC_BYTES, n_sharers=n_up)
            down = self.uplink.transfer_time(RESULT_BYTES, n_sharers=n_up)
            idxs = np.flatnonzero(senders)
            done = self.batcher.submit_batch([self.uplink.t + up] * n_up)
            for j, s in enumerate(idxs):
                roundtrip[s] = (done[j] - self.uplink.t) + down
            if obs is not None:
                bd = self.uplink.transfer_breakdown(
                    PC_BYTES, up, n_sharers=n_up)
                obs.record_uplink("up", self.uplink.t, up, n_up,
                                  PC_BYTES, bd["eff_mbps"])
                bdd = self.uplink.transfer_breakdown(
                    RESULT_BYTES, down, n_sharers=n_up)
                for d in sorted(set(done)):
                    obs.record_uplink("down", d, down, done.count(d),
                                      RESULT_BYTES, bdd["eff_mbps"])

        lat = np.zeros(s_n)
        onb = np.zeros(s_n)
        for s in range(s_n):
            if is_anchor[s]:
                lat[s] = edge_inf[s] \
                    if self.mode == "moby_onboard" else roundtrip[s]
            else:
                n_assoc = int(pk[s, step_lib.COL_N_ASSOC])
                n_new = max(int(pk[s, step_lib.COL_N_VALID]) - n_assoc, 0)
                onb[s] = onboard_transform_time(
                    self.comps[s], n_assoc, n_new, self.use_tba,
                    self._charge_fos)
                lat[s] = onb[s]
            if send_test[s]:
                inflight_at[s] = walls[s] + roundtrip[s]

        if pre_tel is not None:
            kinds = np.where(is_anchor, "anchor",
                             np.where(send_test, "test", "transform"))
            obs.audit_frame(t, kinds, pre_tel[0], pre_tel[1])
        out[:, t, :step_lib.N_COLS] = pk
        out[:, t, step_lib.COL_LATENCY] = lat
        out[:, t, step_lib.COL_ONBOARD] = onb
        walls += np.where(is_anchor, np.maximum(self.frame_dt, lat),
                          self.frame_dt)
        self.uplink.advance(self.frame_dt)
        return n_up

    # ------------------------------------------------------------------
    def _init_state(self) -> step_lib.FleetState:
        state = step_lib.init_fleet_state(self.n_streams, self.cfg.max_obj,
                                          stream_seeds=self.stream_seeds)
        if self.mesh is not None:
            # Place the carry shards before the first dispatch so frame 0
            # compiles against stream-sharded (not replicated) operands.
            state = jax.device_put(
                state, NamedSharding(self.mesh, P("streams")))
        return state
