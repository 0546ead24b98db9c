"""The four-chip cell ``kitti-hdl64-mesh4.fleet64`` as the repository's
own ``BENCHMARK.json`` states it: 64 KITTI vehicles over a ``streams``
mesh of four chips, each chip stepping 16 of them with the sensor and
scene of ``kitti-hdl64``. Nothing here runs the cell."""
import json

import pytest

from bench import harness
from conftest import ROOT

CELL = "kitti-hdl64-mesh4.fleet64"


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(CELL, ROOT)


def test_the_cell_lays_64_streams_of_16_rounds_over_four_chips(cell):
    assert (cell.chips, cell.mesh) == (4, 4)
    assert (cell.streams, cell.rounds) == (64, 16)


def test_readers_get_one_chips_16_streams(cell):
    assert harness.reader_shapes(cell)["streams"] == 16


@pytest.mark.parametrize("key", ["sensor", "scene", "precision",
                                 "deployment", "reduced"])
def test_the_configuration_is_kittis_own(cell, key):
    kitti = json.loads(
        (ROOT / "bench" / "configs" / "kitti-hdl64.json").read_text())
    assert cell.config[key] == kitti[key]


def test_the_limits_hold_every_compared_number(cell):
    assert set(cell.limits) == {"kind_mismatch", "quality_mismatch",
                                "timing_mismatch", "answer_inconsistent",
                                "compiles_in_window"}
    assert all(isinstance(v, (int, float)) and v >= 0
               for v in cell.limits.values())
