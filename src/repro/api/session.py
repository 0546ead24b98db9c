"""Session: run a :class:`Scenario` end to end and get a RunReport.

The one entry point the ROADMAP's scale/speed/scenario PRs plug into:
``Session(scenario).run(n_frames)`` internally picks the single-stream
``MobyEngine`` (S=1) or the batched ``FleetEngine`` (S>1), threads the
scheduler-policy and ops-backend strings through the jit-static params,
and returns the canonical :class:`repro.serving.common.RunReport` with
scenario/policy provenance stamped on it.
"""
from __future__ import annotations

from typing import Optional, Union

from repro.api.scenario import Scenario, scenario as _scenario
from repro.fleet.engine import FleetEngine
from repro.obs import ObsConfig, export_artifacts
from repro.serving.common import RunReport
from repro.serving.engine import MobyEngine


class Session:
    """A live serving run for one scenario.

    The engine is built eagerly (compilation caches, netsim, scene stream)
    so repeated :meth:`run` calls reuse it — the same contract the engines
    had, now behind one constructor::

        report = Session(api.scenario("fleet-16-congested")).run(32)
        report.mean_latency, report.anchor_rate, report.to_csv("out.csv")
    """

    def __init__(self, scn: Union[Scenario, str],
                 obs: Optional[ObsConfig] = None):
        if isinstance(scn, str):
            scn = _scenario(scn)
        self.scenario = scn
        # Observability (repro.obs): off by default; threaded into the
        # engine constructor, so every run of this session is observed.
        self.obs = obs
        sparams = scn.scheduler_params()
        devices = scn.stream_devices()  # fail fast on unknown devices
        if scn.n_streams < 1:
            raise ValueError(f"n_streams must be >= 1, got {scn.n_streams}")
        # Baselines (edge_only/cloud_only) are single-stream notions — a
        # fleet preset's baseline comparison runs on one stream rather
        # than rejecting the mode (FleetEngine serves moby modes only).
        # Single-stream engines run on stream 0's resolved device (the
        # mix spec's first class).
        if scn.n_streams == 1 or scn.mode in ("edge_only", "cloud_only"):
            self.engine = MobyEngine(
                scn.scene, scn.detector, trace=scn.trace, mode=scn.mode,
                use_fos=scn.use_fos, use_tba=scn.use_tba,
                tparams=scn.tparams, sparams=sparams, seed=scn.seed,
                comp=scn.comp, backend=scn.backend, device=devices[0],
                obs=obs)
        else:
            self.engine = FleetEngine(
                scn.scene, scn.detector, n_streams=scn.n_streams,
                trace=scn.trace, mode=scn.mode, use_fos=scn.use_fos,
                use_tba=scn.use_tba, tparams=scn.tparams, sparams=sparams,
                seed=scn.seed, comp=scn.comp, cloud_cfg=scn.cloud,
                backend=scn.backend, device=scn.device, obs=obs,
                mesh=scn.mesh)

    @property
    def n_streams(self) -> int:
        """Streams the built engine actually serves (1 for baselines)."""
        return getattr(self.engine, "n_streams", 1)

    def run(self, n_frames: int) -> RunReport:
        """Serve ``n_frames`` per stream."""
        report = self.engine.run(n_frames)
        report.scenario = self.scenario.name
        report.policy = self.scenario.scheduler_params().policy \
            if self.scenario.use_fos else ""
        # After the provenance stamp (so scenario/policy make it into the
        # metric labels and path placeholders): flush this run into the
        # registry and write whatever export paths the config names.
        if report.obs is not None:
            report.obs.flush_metrics(report)
        export_artifacts(report, self.obs)
        return report
