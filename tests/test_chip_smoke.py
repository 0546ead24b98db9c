"""CPU rehearsal of ``chip_smoke.py``: its phases at a tiny size, and its
refusal to run anywhere but on a TPU.

On the chip the script serves a KITTI-sized fleet on the Pallas backend
and checks it against ``ref``; here the same phase functions run on the
CPU (Pallas in interpret mode) at the ``smoke`` preset's size and must
find nothing wrong.
"""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro import api

jax.config.update("jax_platform_name", "cpu")

ROOT = pathlib.Path(__file__).resolve().parents[1]
FRAMES = 3


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _tiny(**kw):
    return api.scenario("smoke", seed=0, n_streams=2, n_points=512,
                        img_h=32, img_w=104, **kw)


def test_kitti_phases_agree_with_cpu_reference(smoke):
    _, served, problems = smoke.serve(_tiny(backend="pallas"), FRAMES,
                                      "A/pallas-interpret")
    assert problems == []
    assert set(served) == {"run", "scan"}
    assert served["run"].kind.shape == (2, FRAMES)
    assert smoke.reference_phase(served, _tiny(backend="ref"), FRAMES,
                                 jax.devices("cpu")[0]) == []


def test_sharded_phase_on_one_device_mesh(smoke):
    assert smoke.sharded_phase(_tiny(backend="ref"), 1, FRAMES) == []


def test_compare_reports_a_flipped_kind(smoke):
    kind = np.array([["anchor", "transform"], ["anchor", "test"]])
    zeros = np.zeros(kind.shape, np.float32)
    run = api.RunReport(kind=kind, latency_s=zeros, onboard_s=zeros,
                        f1=zeros, precision=zeros, recall=zeros)
    flipped = api.RunReport(kind=kind[:, ::-1], latency_s=zeros,
                            onboard_s=zeros, f1=zeros + 1e-4,
                            precision=zeros, recall=zeros)
    problems = smoke.compare(flipped, run, "flip")
    assert len(problems) == 2
    assert "kind differs at 4 of 4" in problems[0]
    assert problems[1].startswith("flip: f1 differs at 4")
    assert smoke.compare(run, run, "same", bitwise=True) == []


def _run_script(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_refuses_the_cpu():
    out = _run_script(ROOT)
    assert out.returncode != 0
    assert "needs a TPU, but JAX found platform 'cpu'" in out.stderr
    assert out.stdout.strip() == ""


def test_refuses_to_run_alone(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    out = _run_script(tmp_path)
    assert out.returncode != 0
    assert "no repro package" in out.stderr
    assert out.stdout.strip() == ""
