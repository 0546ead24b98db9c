"""The benchmark's own copy of the program's tape recording.

A frozen copy of ``repro.serving.tape``: per frame of one vehicle stream,
the LiDAR points, the oracle 2D/3D detections, the detection-slot label
image and the evaluable ground truth, recorded from the seed with the
program's seeding convention (scene from ``seed + 101 * i``, detector noise
from one more). ``tests/bench/test_bench_gen.py`` checks that the tapes
equal the program's bit for bit.
"""
from __future__ import annotations

from typing import List, NamedTuple, Sequence

import numpy as np

from bench.gen import scenes


class FrameTape(NamedTuple):
    """Per-stream recording; every array has a leading frame axis F (and
    a stream axis S before it once stacked). Field order is the one the
    program's ``FrameTape`` and ``FrameInputs`` use."""
    points: np.ndarray       # (F, N, 3) float32
    det2d: np.ndarray        # (F, D, 4) float32 oracle 2D boxes
    val2d: np.ndarray        # (F, D) bool
    label_img: np.ndarray    # (F, H, W) int32, detection-slot ids
    det3d: np.ndarray        # (F, D, 7) float32 oracle cloud detections
    val3d: np.ndarray        # (F, D) bool
    gt_boxes: np.ndarray     # (F, D, 7) float32
    gt_visible: np.ndarray   # (F, D) bool (evaluable ground truth)


def record_tape(stream: scenes.SceneStream, detector: str, n_frames: int,
                rng: np.random.Generator) -> FrameTape:
    """Roll ``stream`` forward ``n_frames`` and record every input; the
    oracle detectors are sampled once per frame, 3D then 2D."""
    noise = scenes.DETECTOR_PROFILES[detector]
    cols = {k: [] for k in FrameTape._fields}
    for frame in stream.frames(n_frames):
        det3d, val3d = scenes.oracle_detect_3d(frame, rng, noise)
        det2d, val2d, label_img = scenes.oracle_detect_2d(frame, rng)
        cols["points"].append(frame.points)
        cols["det2d"].append(det2d.astype(np.float32))
        cols["val2d"].append(val2d.astype(bool))
        cols["label_img"].append(label_img.astype(np.int32))
        cols["det3d"].append(det3d.astype(np.float32))
        cols["val3d"].append(val3d.astype(bool))
        cols["gt_boxes"].append(frame.gt_boxes.astype(np.float32))
        cols["gt_visible"].append(frame.visible_gt().astype(bool))
    return FrameTape(**{k: np.stack(v) for k, v in cols.items()})


def record_fleet_tapes(cfg: scenes.SceneConfig, detector: str, n_frames: int,
                       n_streams: int, seed: int = 0) -> List[FrameTape]:
    """S decorrelated streams: stream i's scene from ``seed + 101 * i``,
    its detector noise from ``seed + 101 * i + 1``."""
    fleet = scenes.MultiStreamScenes(cfg, n_streams, seed=seed)
    return [record_tape(stream, detector, n_frames,
                        np.random.default_rng(fleet.stream_seed(i) + 1))
            for i, stream in enumerate(fleet.streams)]


def stack_tapes(tapes: Sequence[FrameTape]) -> FrameTape:
    """Stack per-stream tapes to (S, F, ...) arrays."""
    return FrameTape(*(np.stack(cols) for cols in zip(*tapes)))
