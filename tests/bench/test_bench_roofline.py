"""Operation and byte counts at known shapes, the peaks table, and the
per-chip shapes the roofline readers get."""
import pytest

from bench import harness, roofline
from conftest import ROOT, make_root


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


@pytest.mark.parametrize("reader", ["point_proj_roofline",
                                    "ransac_score_roofline"])
def test_a_two_chip_cell_hands_the_readers_half_its_streams(tmp_path,
                                                           reader):
    one = harness.Cell("tiny.pair", make_root(tmp_path / "one", streams=4))
    two = harness.Cell("tiny.pair", make_root(tmp_path / "two", streams=4,
                                              mesh=2, chips=2))
    assert harness.reader_shapes(one)["streams"] == 4
    assert harness.reader_shapes(two)["streams"] == 2
    assert {k: v for k, v in harness.reader_shapes(two).items()
            if k != "streams"} == {k: v for k, v in
                                   harness.reader_shapes(one).items()
                                   if k != "streams"}
    # The same per-chip kernel time: two chips each did half the work.
    kernel = reader.rsplit("_", 1)[0]
    read = harness.load_reader(ROOT, reader)

    def share(cell):
        return read({"kernel_ops": {kernel: ["k.1"]},
                     "trace": {"ops": {"k.1": [8, 1e-3]}},
                     "rounds_traced": 4, "device_kind": "TPU v5 lite",
                     "shapes": harness.reader_shapes(cell)})
    assert share(two) == pytest.approx(share(one) / 2, rel=1e-12)
