"""Golden regression fixtures for the serving surface.

Small seed-pinned ``RunReport.to_csv`` exports of the ``smoke``,
``fleet-16-congested`` and ``fleet-64-mixed`` presets (ref backend,
default policy), plus a pallas-backend leg of ``smoke`` (interpret mode
off-TPU), are checked in under ``tests/goldens/``. Any
scheduler/profile/engine/kernel change that moves the modeled numbers
shows up as a reviewable golden update instead of silent drift:

* regenerate after an intentional change with
  ``MOBY_REGEN_GOLDENS=1 PYTHONPATH=src python -m pytest
  tests/test_goldens.py``;
* on mismatch the freshly generated CSV is written to ``golden-diff/``
  (``GOLDEN_DIFF_DIR``) so CI can upload it as an artifact for review.

Exact-match columns: stream/frame/kind/scenario/policy/device (frame
treatment decisions must not flip). Float columns compare with a small
tolerance so cross-platform last-ulp jitter doesn't flake the tier.
"""
import csv
import io
import os
import pathlib

import jax
import numpy as np
import pytest

from repro import api

jax.config.update("jax_platform_name", "cpu")

GOLDEN_DIR = pathlib.Path(__file__).parent / "goldens"
DIFF_DIR = pathlib.Path(os.environ.get("GOLDEN_DIFF_DIR", "golden-diff"))

# (preset, frames, ops backend): small enough to diff by eye, long enough
# to cross the first test/anchor cycles of every stream. fleet-64-mixed
# exercises the heterogeneous-device path; the pallas leg guards the
# kernel backend's serving numbers (interpret mode on CPU).
GOLDENS = (("smoke", 16, "ref"),
           ("fleet-16-congested", 8, "ref"),
           ("fleet-64-mixed", 6, "ref"),
           ("smoke", 16, "pallas"))

_EXACT = ("stream", "frame", "kind", "scenario", "policy", "device")
_FLOAT = ("latency_s", "onboard_s", "f1", "precision", "recall")


def _golden_name(preset: str, backend: str) -> str:
    """ref goldens keep their pre-backend-matrix filenames."""
    stem = preset if backend == "ref" else f"{preset}-{backend}"
    return f"{stem}.csv"


def _generate(preset: str, frames: int, backend: str = "ref") -> str:
    """The golden contract: seed 0, pinned ops backend, preset defaults."""
    scn = api.scenario(preset, seed=0, backend=backend)
    return api.Session(scn).run(frames).to_csv()


def _rows(text: str):
    return list(csv.DictReader(io.StringIO(text)))


@pytest.mark.parametrize(
    "preset,frames,backend", GOLDENS,
    ids=[f"{g[0]}-{g[2]}" for g in GOLDENS])
def test_matches_golden(preset, frames, backend):
    path = GOLDEN_DIR / _golden_name(preset, backend)
    text = _generate(preset, frames, backend)
    if os.environ.get("MOBY_REGEN_GOLDENS"):
        GOLDEN_DIR.mkdir(exist_ok=True)
        path.write_text(text)
        pytest.skip(f"regenerated {path}")
    assert path.exists(), \
        f"missing golden {path}; run with MOBY_REGEN_GOLDENS=1 to create"
    got, want = _rows(text), _rows(path.read_text())
    try:
        assert len(got) == len(want), \
            f"{preset}: {len(got)} rows vs golden {len(want)}"
        assert got[0].keys() == want[0].keys(), "CSV columns changed"
        for g, w in zip(got, want):
            where = f"{preset} stream={w['stream']} frame={w['frame']}"
            for k in _EXACT:
                assert g[k] == w[k], f"{where}: {k} {g[k]!r} != {w[k]!r}"
            for k in _FLOAT:
                np.testing.assert_allclose(
                    float(g[k]), float(w[k]), rtol=1e-4, atol=1e-5,
                    err_msg=f"{where}: {k}")
    except AssertionError:
        # Leave the regenerated CSV behind for review (CI uploads it).
        DIFF_DIR.mkdir(exist_ok=True)
        (DIFF_DIR / _golden_name(preset, backend)).write_text(text)
        raise


def test_golden_covers_interesting_kinds():
    """The fixtures would not guard the scheduler if they only ever saw
    transform frames."""
    for preset, _, backend in GOLDENS:
        path = GOLDEN_DIR / _golden_name(preset, backend)
        kinds = {r["kind"] for r in _rows(path.read_text())}
        assert "anchor" in kinds and "transform" in kinds, (preset, kinds)


if __name__ == "__main__":
    pytest.main([__file__, "-v", "-x"])
