"""The benchmark's own traffic generator records the program's tapes bit
for bit: at smoke size over several frames, and for one frame at each
configuration's sensor size."""
import json
import pathlib

import numpy as np
import pytest

from bench.gen import scenes as bench_scenes
from bench.gen import tape as bench_tape
from repro.data import scenes as prog_scenes
from repro.serving import tape as prog_tape

CONFIGS = pathlib.Path(__file__).resolve().parents[2] / "bench" / "configs"
SEED = 2 ** 33 + 17          # seeds go past 32 bits


def _fields(config_file: pathlib.Path) -> dict:
    cfg = json.loads(config_file.read_text())
    s, sc = cfg["sensor"], cfg["scene"]
    return dict(n_points=s["n_points"], img_h=s["img_h"], img_w=s["img_w"],
                dt=s["dt"], max_obj=sc["max_obj"],
                density_scale=sc["density_scale"], mean_objects=6)


def _assert_same(fields: dict, n_frames: int, n_streams: int) -> None:
    want = prog_tape.record_fleet_tapes(prog_scenes.SceneConfig(**fields),
                                        "pointpillar", n_frames, n_streams,
                                        seed=SEED)
    got = bench_tape.record_fleet_tapes(
        bench_scenes.SceneConfig(**fields), "pointpillar", n_frames,
        n_streams, seed=SEED)
    assert len(got) == len(want) == n_streams
    for g, w in zip(got, want):
        assert g._fields == w._fields
        for name, a, b in zip(g._fields, g, w):
            assert a.dtype == b.dtype, name
            np.testing.assert_array_equal(a, b, err_msg=name)


def test_smoke_size_tapes_equal_the_program():
    _assert_same(dict(max_obj=6, n_points=1024, img_h=48, img_w=160,
                      mean_objects=3, density_scale=4000.0), 8, 3)


@pytest.mark.parametrize("config", ["kitti-hdl64", "nuscenes-front"])
def test_one_frame_at_the_configuration_size(config):
    _assert_same(_fields(CONFIGS / f"{config}.json"), 1, 1)


def test_masks_are_painted_where_objects_are():
    cfg = bench_scenes.SceneConfig(max_obj=12, n_points=4096, img_h=375,
                                   img_w=1242, mean_objects=6)
    tapes = bench_tape.record_fleet_tapes(cfg, "pointpillar", 3, 2, seed=5)
    for t in tapes:
        assert (t.label_img > 0).any()
        assert t.label_img.max() <= cfg.max_obj
