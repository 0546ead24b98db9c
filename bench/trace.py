"""Reduce a JAX profiler trace of the traced window to per-layer numbers.

:func:`load_profile` reads the ``.xplane.pb`` that ``jax.profiler`` wrote
and keeps two lists: every event on a TPU device plane (chip, line, name,
start, duration) and the host spans the benchmark and the
program annotate (``bench/drive``, ``moby/fleet/dispatch``,
``moby/fleet/fetch``). :func:`reduce` works on those lists alone, so a
small recorded trace can test it (``tests/bench/``).

The traced window runs from the first ``bench/drive`` span's start to the
last one's end. Within it:

* ``busy_s``: the union of the intervals in which an operation ran on a
  chip (line ``XLA Ops``), averaged over the chips used;
* ``device_ops``: the ten operations with the most device time, each
  named by the first characters of its HLO instruction;
* ``idle_gaps``: each chip's idle time, summed by what the host was
  doing meanwhile: inside the program's ``moby/fleet/dispatch`` or
  ``moby/fleet/fetch`` span, elsewhere inside a drive ("host loop,
  unspanned"), or between drives; averaged over the chips used, as
  ``busy_s`` is, so the gaps add up to the window less ``busy_s``;
* ``modules``: count and device seconds of each compiled program (line
  ``XLA Modules``), and ``ops``: the same for each operation, keyed by
  its HLO instruction name.

Every time is one chip's: an op's or a module's device seconds are summed
over the chips used and divided by their number (its count is over all).
"""
from __future__ import annotations

import pathlib
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DRIVE = "bench/drive"
HOST_SPANS = ("moby/fleet/dispatch", "moby/fleet/fetch")
UNSPANNED = "host loop, unspanned"
BETWEEN = "between drives"
TOP = 10
DESC = 120


def op_name(event_name: str) -> str:
    """A TPU op event is named by its HLO instruction's text
    (``%sort.12 = (...) sort(...)``); keep the instruction's name."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load_profile(profile_dir) -> dict:
    """The device events and annotated host spans of the newest trace
    under ``profile_dir``."""
    import jax
    paths = sorted(pathlib.Path(profile_dir).rglob("*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    data = jax.profiler.ProfileData.from_file(str(paths[-1]))
    device, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            chip = int(plane.name.rsplit(":", 1)[1].split()[0])
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                for ev in line.events:
                    device.append([chip, line.name, ev.name,
                                   float(ev.start_ns), float(ev.duration_ns)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == DRIVE or ev.name in HOST_SPANS:
                        host.append([ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)])
    return {"device": device, "host": host}


def _union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _label(t: float, host: Sequence[list]) -> str:
    inner = [h for h in host if h[1] <= t < h[1] + h[2]]
    for name in HOST_SPANS:
        if any(h[0] == name for h in inner):
            return name
    return UNSPANNED if any(h[0] == DRIVE for h in inner) else BETWEEN


def _segments(host: Sequence[list], w0: float, w1: float) -> List[tuple]:
    """[w0, w1) cut at every host span's edges, each piece labelled by
    what the host was doing in it."""
    cuts = sorted({w0, w1} | {x for h in host for x in (h[1], h[1] + h[2])
                              if w0 < x < w1})
    return [(a, b, _label((a + b) / 2, host)) for a, b in zip(cuts, cuts[1:])]


def _attribute(idle: Sequence[tuple], segs: Sequence[tuple]):
    """Split each idle interval over the labelled segments (both sorted
    and disjoint): yields (label, overlap ns)."""
    j = 0
    for a, b in idle:
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            lo, hi = max(a, segs[k][0]), min(b, segs[k][1])
            if hi > lo:
                yield segs[k][2], hi - lo
            k += 1


def reduce(events: dict, n_chips: int = 1) -> dict:
    host = events["host"]
    drives = [h for h in host if h[0] == DRIVE]
    if not drives:
        raise ValueError("the trace holds no bench/drive span")
    w0 = min(h[1] for h in drives)
    w1 = max(h[1] + h[2] for h in drives)
    ops = [e for e in events["device"] if e[1] == OPS_LINE and e[0] < n_chips]
    segs = _segments(host, w0, w1)
    busy_ns, gaps = 0.0, defaultdict(float)
    for chip in range(n_chips):
        mine = [(max(e[3], w0), min(e[3] + e[4], w1)) for e in ops
                if e[0] == chip and e[3] < w1 and e[3] + e[4] > w0]
        merged = _union(mine)
        busy_ns += sum(b - a for a, b in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        for label, ns in _attribute(idle, segs):
            gaps[label] += ns * 1e-9
    by_op: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    desc: Dict[str, str] = {}
    for e in ops:
        if w0 <= e[3] < w1:
            inst = op_name(e[2])
            desc.setdefault(inst, e[2][:DESC])
            by_op[inst][0] += 1
            by_op[inst][1] += e[4] * 1e-9 / n_chips
    by_module: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    for e in events["device"]:
        if e[1] == MODULES_LINE and e[0] < n_chips and w0 <= e[3] < w1:
            by_module[e[2]][0] += 1
            by_module[e[2]][1] += e[4] * 1e-9 / n_chips
    top = sorted(by_op.items(), key=lambda kv: -kv[1][1])[:TOP]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_ns * 1e-9 / n_chips,
        "device_ops": [[desc[k], v[1]] for k, v in top],
        "idle_gaps": sorted(([k, v / n_chips] for k, v in gaps.items()),
                            key=lambda kv: -kv[1])[:TOP],
        "ops": {k: v for k, v in by_op.items()},
        "modules": {k: v for k, v in by_module.items()},
    }
