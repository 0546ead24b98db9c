"""Unified ops backend: one registry, two implementations per hot op.

Usage::

    from repro import ops

    counts = ops.ransac_score(pts, valid, normals, offsets, 0.1,
                              backend="pallas")   # or "ref" / "auto"

Backend resolution (see :mod:`repro.ops.registry`): explicit argument >
``MOBY_BACKEND`` env var > platform default (pallas on TPU, ref
elsewhere). ``"auto"`` resolves per *op* from the startup
micro-benchmark table (:mod:`repro.ops.autotune`) — the measured-fastest
implementation per op on this host. The pallas implementations run in
interpret mode when no TPU is attached, so both backends are runnable —
and parity-testable — on any host.
"""
from repro.ops.api import (decode_attention, flash_attention, iou2d,
                           label_points, pillar_scatter, point_proj,
                           ransac_score)
from repro.ops.autotune import (best_backend, clear_measurements,
                                measurement_table, set_measurements)
from repro.ops.registry import (AUTO, BACKENDS, default_backend,
                                default_interpret, get_impl, list_ops,
                                on_tpu, register_op, resolve_backend)

__all__ = [
    "AUTO", "BACKENDS", "best_backend", "clear_measurements",
    "decode_attention", "default_backend", "default_interpret",
    "flash_attention", "get_impl", "iou2d", "label_points", "list_ops",
    "measurement_table", "on_tpu", "pillar_scatter", "point_proj",
    "ransac_score", "register_op", "resolve_backend", "set_measurements",
]
