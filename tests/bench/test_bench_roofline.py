"""Operation and byte counts at known shapes, and the peaks table."""
import pytest

from bench import roofline


def test_point_proj_work():
    w = roofline.point_proj_work(streams=2, n_points=1000)
    assert w["bytes"] == 2 * 1000 * 16          # xyz in, one index out
    assert w["flops"] == 2 * 1000 * 22


def test_ransac_score_work():
    w = roofline.ransac_score_work(streams=1, objects=2, points=256,
                                   hypotheses=30)
    assert w["flops"] == 8 * 2 * 30 * 256
    assert w["bytes"] == 2 * (256 * 13 + 30 * 20)


def test_v5e_bound_is_memory_for_the_projection():
    least, bound = roofline.min_seconds(
        roofline.point_proj_work(16, 120000), "TPU v5 lite")
    assert bound == "memory"
    assert least == pytest.approx(16 * 120000 * 16 / 819e9)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        roofline.min_seconds({"flops": 1.0, "bytes": 1.0}, "cpu")
