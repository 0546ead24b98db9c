"""Moby serving engine: the full edge-cloud system of Fig. 4.

Orchestrates, per stream and per frame:
  * frame treatment (anchor / test / transform) via the offloading
    scheduler (core.scheduler),
  * the on-device path (2D detection -> tracking association -> 2D->3D
    transformation) as jitted steps,
  * the cloud path (3D detector on anchor/test frames) over the 4G netsim,
  * **recomputation** (§3.4): while blocked on an anchor result, buffered
    intermediate outputs are replayed through the transformation so the
    wait is hidden,
  * end-to-end latency accounting on calibrated device profiles
    (DESIGN.md §3: no TX2/4G in this container), and accuracy vs the
    simulator's ground truth.

Deployment modes reproduce the paper's baselines: ``moby``, ``edge_only``,
``cloud_only``, plus ``moby_onboard`` (anchors run the 3D detector on the
edge — the Fig. 14 comparison setting).
"""
from __future__ import annotations

import contextlib
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import metrics, projection, scheduler, transform
from repro.data import scenes
from repro.obs import observe as obs_lib
from repro.runtime import netsim, profiles
from repro.serving import tape as tape_lib
from repro.serving.common import (PC_BYTES, RESULT_BYTES, ComponentTimes,
                                  FrameRecord, RunReport,
                                  modeled_frame_costs,
                                  onboard_transform_time)


# Process-wide jitted steps (params are static: plain NamedTuples).
_JIT_TRANSFORM = jax.jit(transform.transform_step,
                         static_argnames=("params",))
_JIT_ANCHOR = jax.jit(transform.anchor_step, static_argnames=("params",))

# Disabled-observability stand-in for `with obs.measured_span(...)`.
_NULL_CTX = contextlib.nullcontext()


@jax.jit
def _frame_stats(boxes3d, valid, gt_boxes, gt_visible, det_to_track):
    """All per-frame scalars the host loop needs, packed into one small
    array so each frame costs a single host-device fetch (the former
    ``int(jnp.sum(...))`` / ``float(f1)`` reads were one sync each)."""
    f1, prec, rec = metrics.f1_score(boxes3d, valid, gt_boxes, gt_visible)
    n_assoc = jnp.sum((det_to_track >= 0) & valid)
    n_valid = jnp.sum(valid)
    return jnp.stack([f1, prec, rec, n_assoc.astype(jnp.float32),
                      n_valid.astype(jnp.float32)])


class MobyEngine:
    def __init__(self, scene_cfg: scenes.SceneConfig, detector: str,
                 trace: str = "belgium2", mode: str = "moby",
                 use_fos: bool = True, use_tba: bool = True,
                 tparams: Optional[transform.TransformParams] = None,
                 sparams: Optional[scheduler.SchedulerParams] = None,
                 seed: int = 0,
                 comp: Optional[ComponentTimes] = None,
                 tape: Optional[tape_lib.FrameTape] = None,
                 backend: Optional[str] = None,
                 device: str = "jetson_tx2",
                 obs: Optional[obs_lib.ObsConfig] = None):
        self.cfg = scene_cfg
        self.detector = detector
        self.mode = mode
        self.use_fos = use_fos
        self.use_tba = use_tba
        # The edge device profile is the single modeled-latency source:
        # component times (unless explicitly overridden) and edge
        # inference both come from it (runtime.profiles).
        self.profile = profiles.get_profile(device)
        self.comp = comp or profiles.component_times(self.profile)
        self.net = netsim.NetworkSim(trace, seed=seed)
        self.stream = scenes.SceneStream(scene_cfg, seed=seed)
        self.calib = projection.Calibration(
            tr=jnp.asarray(self.stream.tr), p=jnp.asarray(self.stream.p),
            height=scene_cfg.img_h, width=scene_cfg.img_w)
        base = tparams or transform.TransformParams()
        # Ops backend for the transformation hot path ("ref" / "pallas");
        # None keeps tparams.backend. Resolved + pinned at construction.
        self.tparams = transform.resolve_backend_params(
            base._replace(use_tba=use_tba), backend)
        self.sparams = sparams or scheduler.SchedulerParams()
        # The per-frame FOS scoring cost only applies when the active
        # policy actually offloads test frames (the paper's fos policy
        # does; periodic/always/never_anchor don't).
        self._charge_fos = use_fos and \
            scheduler.get_policy(self.sparams.policy).uses_tests
        self.rng = np.random.default_rng(seed + 1)
        self.noise = scenes.DETECTOR_PROFILES[detector]
        self.frame_dt = scene_cfg.dt
        # Optional pre-recorded data plane (serving.tape). When set, all
        # per-frame inputs come from the tape instead of live scene
        # rendering + lazy oracle draws — the exact inputs FleetEngine
        # consumes, which is what makes fleet parity testable.
        self.tape = tape
        # Jitted per-frame steps, shared process-wide so many engines (one
        # per benchmark configuration) reuse one compilation cache.
        self._transform_step = _JIT_TRANSFORM
        self._anchor_step = _JIT_ANCHOR
        # Observability switch (repro.obs); None/all-off keeps every run
        # hook a single pointer test.
        self.obs_config = obs

    # ------------------------------------------------------------------
    def _cloud_parts(self) -> tuple:
        """(upload, cloud inference, download) legs of one cloud trip —
        split out so the observer can record the legs individually;
        ``_cloud_roundtrip`` sums them in this exact order, so the summed
        latency is bitwise what the unsplit version produced."""
        tx = self.net.transfer_time(PC_BYTES)
        infer = profiles.detector_latency(self.detector,
                                          profiles.RTX_2080TI)
        back = self.net.transfer_time(RESULT_BYTES)
        return tx, infer, back

    def _cloud_roundtrip(self, obs=None, t0: float = 0.0,
                         record_gpu: bool = False) -> float:
        tx, infer, back = self._cloud_parts()
        if obs is not None:
            bd = self.net.transfer_breakdown(PC_BYTES, tx)
            obs.record_uplink("up", t0, tx, 1, PC_BYTES, bd["eff_mbps"])
            if record_gpu:
                obs.on_cloud_batch(0, t0 + tx, t0 + tx + infer, 1, t0 + tx)
            bdd = self.net.transfer_breakdown(RESULT_BYTES, back)
            obs.record_uplink("down", t0 + tx + infer, back, 1,
                              RESULT_BYTES, bdd["eff_mbps"])
        return tx + infer + back

    def _edge_infer(self) -> float:
        return profiles.detector_latency(self.detector, self.profile)

    def _onboard_transform_time(self, n_assoc: int, n_new: int) -> float:
        return onboard_transform_time(self.comp, n_assoc, n_new,
                                      self.use_tba, self._charge_fos)

    def _observe_telemetry(self,
                           sstate: scheduler.SchedulerState,
                           obs=None) -> scheduler.SchedulerState:
        """Per-frame telemetry for cost-aware policies: the bandwidth the
        netsim currently delivers plus modeled edge/offload frame costs
        from the active device profiles."""
        bw = self.net.current_bw_mbps()
        edge, off = modeled_frame_costs(
            self.comp, self.detector, bw, self.net.rtt_s, self.use_tba,
            self._charge_fos, onboard_anchors=self.mode == "moby_onboard",
            edge_device=self.profile)
        if obs is not None:
            obs.note_telemetry(bw, edge, off)
        return scheduler.observe_telemetry(sstate, bw_mbps=bw,
                                           edge_cost_s=edge,
                                           offload_cost_s=off)

    # ------------------------------------------------------------------
    def run(self, n_frames: int) -> RunReport:
        if self.tape is not None and self.tape.n_frames < n_frames:
            raise ValueError(f"tape holds {self.tape.n_frames} frames, "
                             f"run asked for {n_frames}")
        if self.mode in ("edge_only", "cloud_only"):
            return self._run_baseline(n_frames)
        return self._run_moby(n_frames)

    def _run_baseline(self, n_frames: int) -> RunReport:
        obs = obs_lib.make_observer(
            self.obs_config, n_streams=1, devices=(self.profile.name,),
            policy=self.mode, detector=self.detector,
            frame_dt=self.frame_dt)
        recs = []
        for t, frame in enumerate(self.stream.frames(n_frames)):
            det, val = scenes.oracle_detect_3d(frame, self.rng, self.noise)
            lat = self._edge_infer() if self.mode == "edge_only" \
                else self._cloud_roundtrip(obs, t0=t * self.frame_dt)
            f1, p, r = metrics.f1_score(
                jnp.asarray(det), jnp.asarray(val),
                jnp.asarray(frame.gt_boxes),
                jnp.asarray(frame.visible_gt()))
            recs.append(FrameRecord(t, self.mode, lat,
                                    lat if self.mode == "edge_only" else 0.0,
                                    float(f1), float(p), float(r)))
            self.net.advance(self.frame_dt)
            if obs is not None and obs.cfg.want_audit:
                # Baselines make no scheduling decision; the audit still
                # gets its one row per stream-frame (telemetry zeros).
                obs.audit_frame(t, self.mode, 0.0, 0.0)
        report = RunReport.from_records(recs, device=self.profile.name)
        report.frame_dt = self.frame_dt
        if obs is not None:
            obs.finalize(report)
        return report

    def _run_moby(self, n_frames: int) -> RunReport:
        obs = obs_lib.make_observer(
            self.obs_config, n_streams=1, devices=(self.profile.name,),
            policy=self.sparams.policy if self.use_fos else "no_fos",
            detector=self.detector, frame_dt=self.frame_dt)
        want_audit = obs is not None and obs.cfg.want_audit
        recs: List[FrameRecord] = []
        mstate = transform.init_state(max_tracks=2 * self.cfg.max_obj,
                                      key=jax.random.key(0))
        sstate = scheduler.init_scheduler(self.cfg.max_obj)
        # In-flight test frame: (arrival_wall_time, boxes, valid) or None.
        inflight = None
        # Buffered intermediate outputs for recomputation (§3.4).
        recompute_buf = []
        wall = 0.0

        frame_iter = None if self.tape is not None \
            else self.stream.frames(n_frames)

        for t in range(n_frames):
            tf = self.tape.frame(t) if self.tape is not None else None
            frame = next(frame_iter) if frame_iter is not None else None
            if want_audit:
                # Decision-time scheduler state, fetched *before* the step
                # updates it (one extra (2,) fetch per frame, audit only).
                pre_tel = np.asarray(scheduler.decision_telemetry(sstate))
            if self.use_fos:
                sstate = self._observe_telemetry(sstate, obs)
                actions = scheduler.scheduler_pre(sstate, self.sparams)
            else:
                actions = scheduler.SchedulerActions(
                    jnp.bool_(False), jnp.bool_(t == 0))
            is_anchor = bool(actions.run_as_anchor)
            send_test = bool(actions.send_test) and self.use_fos

            if is_anchor:
                if tf is not None:
                    det3d, val3d = tf.det3d, tf.val3d
                else:
                    det3d, val3d = scenes.oracle_detect_3d(frame, self.rng,
                                                           self.noise)
                if self.mode == "moby_onboard":
                    latency = self._edge_infer()
                else:
                    latency = self._cloud_roundtrip(obs, t0=wall,
                                                    record_gpu=True)
                with obs.measured_span("anchor_step",
                                       jit_fn=self._anchor_step,
                                       frame=t) if obs is not None \
                        else _NULL_CTX:
                    mstate, out = self._anchor_step(
                        mstate, jnp.asarray(det3d), jnp.asarray(val3d),
                        self.calib, params=self.tparams)
                # Recomputation: replay buffered frames through the
                # transformation while waiting — hidden latency, so it does
                # not add to `latency`; we verify it fits in the wait.
                recompute_time = len(recompute_buf) * (
                    self.comp.bbox_est_assoc + self.comp.point_proj)
                assert recompute_time <= max(latency, 1e-9) + 1.0
                recompute_buf.clear()
            else:
                if tf is not None:
                    boxes2d, val2d, label_img = tf.det2d, tf.val2d, \
                        tf.label_img
                    points = tf.points
                else:
                    boxes2d, val2d, label_img = scenes.oracle_detect_2d(
                        frame, self.rng)
                    points = frame.points
                with obs.measured_span("transform_step",
                                       jit_fn=self._transform_step,
                                       frame=t) if obs is not None \
                        else _NULL_CTX:
                    mstate, out = self._transform_step(
                        mstate, jnp.asarray(points), jnp.asarray(boxes2d),
                        jnp.asarray(val2d), jnp.asarray(label_img),
                        self.calib, params=self.tparams)
                recompute_buf.append(t)
                if len(recompute_buf) > 8:
                    recompute_buf.pop(0)

            # Test-frame transport (parallel with on-device processing).
            if send_test and inflight is None:
                if tf is not None:
                    tdet, tval = tf.det3d, tf.val3d
                else:
                    tdet, tval = scenes.oracle_detect_3d(frame, self.rng,
                                                         self.noise)
                arrive = wall + self._cloud_roundtrip(obs, t0=wall)
                inflight = (arrive, jnp.asarray(tdet), jnp.asarray(tval))

            test_arrived = inflight is not None and wall >= inflight[0]
            tb = inflight[1] if test_arrived else sstate.buf_boxes
            tv = inflight[2] if test_arrived else sstate.buf_valid
            if self.use_fos:
                sstate = scheduler.scheduler_post(
                    sstate, actions, out.boxes3d, out.valid,
                    jnp.bool_(test_arrived), tb, tv, self.sparams)
            if test_arrived:
                inflight = None

            # One packed fetch per frame: f1/precision/recall + the
            # detection counts driving the on-board time model.
            gt_boxes = tf.gt_boxes if tf is not None else frame.gt_boxes
            gt_vis = tf.gt_visible if tf is not None else frame.visible_gt()
            with obs.measured_span("frame_stats_fetch",
                                   jit_fn=_frame_stats,
                                   frame=t) if obs is not None \
                    else _NULL_CTX:
                stats = np.asarray(_frame_stats(
                    out.boxes3d, out.valid, jnp.asarray(gt_boxes),
                    jnp.asarray(gt_vis), out.det_to_track))
            f1, p, r = float(stats[0]), float(stats[1]), float(stats[2])
            if is_anchor:
                onboard = 0.0
            else:
                n_assoc = int(stats[3])
                n_new = max(int(stats[4]) - n_assoc, 0)
                onboard = self._onboard_transform_time(n_assoc, n_new)
                latency = onboard

            kind = "anchor" if is_anchor else \
                ("test" if send_test else "transform")
            recs.append(FrameRecord(t, kind, latency, onboard, f1, p, r))
            if want_audit:
                obs.audit_frame(t, kind, pre_tel[0], pre_tel[1])
            wall += max(self.frame_dt, latency if is_anchor else 0.0)
            self.net.advance(self.frame_dt)
        report = RunReport.from_records(recs, device=self.profile.name)
        report.frame_dt = self.frame_dt
        if obs is not None:
            obs.finalize(report)
        return report
