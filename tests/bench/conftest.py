"""The benchmark's tests import the ``bench`` package from the root of
the checkout, and the program from ``src/``."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


import json  # noqa: E402
import shutil  # noqa: E402

import pytest  # noqa: E402

TINY_SCENE = {"n_points": 1024, "img_h": 48, "img_w": 160, "dt": 0.1}


def make_root(tmp: pathlib.Path, config: str = "tiny",
              traffic: str = "pair", metric: str = None,
              sensor: dict = None, streams: int = 2,
              rounds: int = 4, scene_seed: int = 2302, mesh: int = None,
              chips: int = 1) -> pathlib.Path:
    """A checkout-like directory holding a one-cell BENCHMARK.json, the
    cell's configuration (with ``"mesh": {"streams": mesh}`` if given),
    traffic mix and limits as files, the metric readers (plus ``metric``,
    if given, as a reader of its own) and the program. The cell is
    ``<config>.<traffic>`` on ``chips`` chips."""
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    for d in ("configs", "traffic", "limits", "metrics"):
        (tmp / "bench" / d).mkdir(parents=True, exist_ok=True)
    (tmp / "src").symlink_to(ROOT / "src")
    for f in (ROOT / "bench" / "metrics").glob("*.py"):
        shutil.copy(f, tmp / "bench" / "metrics" / f.name)
    cfg = json.loads((ROOT / "bench" / "configs" / "kitti-hdl64.json")
                     .read_text())
    cfg["name"] = config
    cfg["sensor"] = dict(sensor or TINY_SCENE)
    cfg["scene"] = {"max_obj": 6, "density_scale": 4000.0}
    if mesh is not None:
        cfg["mesh"] = {"streams": mesh}
    (tmp / "bench" / "configs" / f"{config}.json").write_text(json.dumps(cfg))
    (tmp / "bench" / "traffic" / f"{traffic}.json").write_text(json.dumps(
        {"name": traffic, "streams": streams, "rounds_per_drive": rounds,
         "mean_objects": 3, "scene_seed": scene_seed, "policy": "fos",
         "loop": "closed",
         "trace_drives": 1}))
    cell = f"{config}.{traffic}"
    shutil.copy(ROOT / "bench" / "limits" / "kitti-hdl64.fleet16.json",
                tmp / "bench" / "limits" / f"{cell}.json")
    per_layer = [dict(m, workloads=[cell]) for m in real["per_layer"]]
    if metric:
        (tmp / "bench" / "metrics" / f"{metric}.py").write_text(
            "def read(ctx):\n    return 7.0\n")
        per_layer.append({"name": metric, "unit": "ms", "better": "lower",
                          "source": "program_span", "layer": "host loop",
                          "moves": "stream_frames_per_s",
                          "workloads": [cell]})
    spec = dict(real, configs=[{"name": config, "source": "test",
                                "file": f"bench/configs/{config}.json",
                                "reduced": [], "why": "test"}],
                workloads=[{"name": cell, "config": config,
                            "traffic": traffic, "chips": chips,
                            "why": "test"}],
                per_layer=per_layer)
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A run turns JAX's persistent compilation cache on; in the tests it
    stays off, and every setting a run changes is put back."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    names = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    for n, v in before.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()
