"""Backend registry for the hot-path ops.

Every compute hot spot (point projection, IoU matrix, RANSAC scoring,
pillar scatter, attention) is registered here under a short name with one
implementation per backend:

* ``ref``    — pure-jnp reference (the oracle the kernels are tested
  against; also the fastest path on CPU hosts).
* ``pallas`` — the Pallas TPU kernel. The kernel wrappers default their
  ``interpret`` switch from :func:`default_interpret`: compiled by Mosaic
  on a TPU, interpreted elsewhere, so the pallas path is *correct*
  everywhere and *fast* on TPU.

Resolution order for the active backend:

1. an explicit ``backend=`` argument ("ref" / "pallas" / "auto"),
2. the ``MOBY_BACKEND`` environment variable,
3. the platform default: "pallas" on TPU, "ref" elsewhere.

``None`` (or ``""``) means "defer to 2-3". ``"auto"`` is the *autotuned*
backend: :func:`get_impl` resolves it per **op** from the startup
micro-benchmark table (``repro.ops.autotune``) — the measured-fastest
implementation for each op on this host, rather than one per-process
choice. Consumers carry the backend as a plain string (hashable, so it
can live in NamedTuple params used as static jit arguments); resolution
happens at trace time.
"""
from __future__ import annotations

import functools
import os
from typing import Callable, Dict

BACKENDS = ("ref", "pallas")
AUTO = "auto"
_ENV_VAR = "MOBY_BACKEND"

# name -> {"ref": fn, "pallas": fn}
_REGISTRY: Dict[str, Dict[str, Callable]] = {}


@functools.lru_cache(maxsize=1)
def on_tpu() -> bool:
    import jax
    return jax.default_backend() == "tpu"


def default_interpret() -> bool:
    """Pallas interpret mode: on unless a real TPU is attached."""
    return not on_tpu()


def default_backend() -> str:
    """Backend used when nothing was requested explicitly. May return
    ``"auto"`` (MOBY_BACKEND=auto): per-op autotuned resolution."""
    env = os.environ.get(_ENV_VAR, "").strip().lower()
    if env:
        if env not in BACKENDS + (AUTO,):
            raise ValueError(
                f"{_ENV_VAR}={env!r}: expected one of "
                f"{BACKENDS + (AUTO,)}")
        return env
    return "pallas" if on_tpu() else "ref"


def resolve_backend(backend: str | None = None) -> str:
    """Explicit argument > MOBY_BACKEND env > platform default.

    Returns "ref", "pallas", or "auto" — the last meaning "per-op from the
    measured table" (resolved by :func:`get_impl` at lookup time)."""
    if backend is None or backend == "":
        return default_backend()
    if backend not in BACKENDS + (AUTO,):
        raise ValueError(f"unknown backend {backend!r}: expected one of "
                         f"{BACKENDS} (or 'auto')")
    return backend


def register_op(name: str, *, ref: Callable, pallas: Callable) -> None:
    """Register both implementations of a hot op. Idempotent per name."""
    _REGISTRY[name] = {"ref": ref, "pallas": pallas}


def get_impl(name: str, backend: str | None = None) -> Callable:
    """Look up an op's implementation for a (resolved) backend. "auto"
    resolves per op through the measured-latency table."""
    if name not in _REGISTRY:
        raise KeyError(f"op {name!r} is not registered; known ops: "
                       f"{sorted(_REGISTRY)}")
    resolved = resolve_backend(backend)
    if resolved == AUTO:
        from repro.ops import autotune  # deferred: autotune imports us
        resolved = autotune.best_backend(name)
    return _REGISTRY[name][resolved]


def list_ops() -> list[str]:
    return sorted(_REGISTRY)
