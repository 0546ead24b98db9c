"""Peaks of each chip and the work each kernel's algorithm needs.

A kernel's roofline share is the least time the chip could take for the
work the algorithm needs at the call's shapes (the larger of operations
over peak operations per second and bytes over peak memory bandwidth)
divided by the kernel's measured device time. The counts below follow
the algorithm, not an implementation, so a later kernel is judged on the
same work; they count what must be read and written once and the
arithmetic the result needs, and nothing for padding or layout.
"""
from __future__ import annotations

# device_kind -> peaks. Source: Google Cloud documentation, "TPU v5e":
# 197 TFLOP/s bf16, 394 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud TPU v5e documentation"},
    "TPU v5e": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                "source": "Google Cloud TPU v5e documentation"},
}

F32 = 4
I32 = 4


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}")
    return PEAKS[device_kind]


def point_proj_work(streams: int, n_points: int) -> dict:
    """Project each LiDAR point to the image and give the flat index of
    its pixel: read x, y, z (3 float32), write one int32 index (off-image
    points get an out-of-range index). Operations: the 3x4 affine map
    (9 multiplies, 9 adds), two divides, and the index v*W + u (2)."""
    n = streams * n_points
    return {"flops": 22.0 * n, "bytes": float(n * (3 * F32 + I32))}


def ransac_score_work(streams: int, objects: int, points: int,
                      hypotheses: int) -> dict:
    """Count each plane hypothesis's inliers in each cluster: read the
    points (3 float32) and their valid flags (one byte), the hypotheses'
    normals and offsets (4 float32), write one int32 count each.
    Operations: per (hypothesis, point) a 3-term dot product, the offset
    add, the absolute value and the compare (8)."""
    o = streams * objects
    return {"flops": 8.0 * o * hypotheses * points,
            "bytes": float(o * (points * (3 * F32 + 1)
                                + hypotheses * (4 * F32 + I32)))}


def min_seconds(work: dict, device_kind: str) -> tuple:
    """(least seconds, the bound that sets it: "compute" or "memory")."""
    pk = peaks(device_kind)
    t_c = work["flops"] / pk["flops"]
    t_m = work["bytes"] / pk["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
