"""The comparison that decides ``correct`` refuses its control and the
faults a serving cell can have.

The control is the plain reference put in the program's place with its
matrix products in three-pass bfloat16, one step below the
configuration's float32 at ``Precision.HIGHEST``, at the kitti cell's own
size; and with one-pass bfloat16 at a smaller size (30k points, the KITTI
camera, 2 vehicles, 8 rounds). The faults are
planted under a whole CPU run of the harness (its look for a chip
skipped; 4 vehicles, 6-round drives): a fleet step that hands back its
state unchanged, one that leaves half of the fleet out of a round, and
one that alters a single stream-frame's answer where the step produces
it. A fleet on one chip
exchanges nothing between chips, so that fault has no place here."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from conftest import make_root
from repro.fleet import step as step_lib

CONTROL_SENSOR = {"n_points": 30000, "img_h": 375, "img_w": 1242,
                  "dt": 0.1}


@pytest.mark.parametrize("scene_seed", [1, 4])
def test_one_pass_bfloat16_control_is_not_correct(tmp_path, scene_seed):
    root = make_root(tmp_path, sensor=CONTROL_SENSOR, streams=2, rounds=8,
                     scene_seed=scene_seed)
    cell = harness.Cell("tiny.pair", root)
    tapes = harness.record_tapes(cell, 1)
    ref = harness.reference_drive(cell, tapes, 1)
    control = harness.reference_drive(cell, tapes, 1, "default")
    numbers = dict(harness.compare([control], ref), compiles_in_window=0.0)
    assert not harness.verdict(numbers, cell.limits), numbers
    same = dict(harness.compare([ref], ref), compiles_in_window=0.0)
    assert harness.verdict(same, cell.limits)


def test_three_pass_bfloat16_control_is_not_correct_at_the_cell_size():
    """The control one step below the configuration's precision, on the
    cell's own tapes and sizes (a seed the chip read 17 on)."""
    cell = harness.Cell("kitti-hdl64.fleet16")
    seed = 3200000031
    tapes = harness.record_tapes(cell, seed)
    ref = harness.reference_drive(cell, tapes, seed)
    control = harness.reference_drive(cell, tapes, seed, "high")
    numbers = dict(harness.compare([control], ref), compiles_in_window=0.0)
    assert not harness.verdict(numbers, cell.limits), numbers


def _broken(fault):
    real_make = step_lib.make_fleet_step

    def make(*args, **kwargs):
        real = real_make(*args, **kwargs)

        def step(state, inp, arrived, t):
            if fault == "state_unchanged":
                fresh = jax.tree_util.tree_map(jnp.copy, state)
                _, packed = real(fresh, inp, arrived, t)
                return state, packed
            state, packed = real(state, inp, arrived, t)
            if fault == "half_the_fleet_left_out":
                packed = packed.at[packed.shape[0] // 2:].set(0.0)
            if fault == "one_answer_altered" and int(t) == 1:
                packed = packed.at[0, step_lib.COL_F1].add(0.25)
            return state, packed

        return step

    return make


@pytest.mark.parametrize("fault", ["state_unchanged",
                                   "half_the_fleet_left_out",
                                   "one_answer_altered"])
def test_a_broken_timed_path_is_not_correct(tmp_path, monkeypatch, fault):
    monkeypatch.setattr(step_lib, "make_fleet_step", _broken(fault))
    root = make_root(tmp_path, streams=4, rounds=6)
    res = harness.run("tiny.pair", 11, 1.0, False, time.perf_counter(),
                      check_device=False, root=root, log=lambda m: None)
    assert res["correct"] is False, res["compared"]


def test_the_unbroken_path_is_correct(tmp_path):
    root = make_root(tmp_path, streams=4, rounds=6)
    res = harness.run("tiny.pair", 11, 1.0, False, time.perf_counter(),
                      check_device=False, root=root, log=lambda m: None)
    assert res["correct"] is True, res["compared"]
