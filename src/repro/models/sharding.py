"""Activation-sharding hooks.

Model code calls :func:`constrain` with *logical* axis names; the launcher
installs a rules table (logical -> mesh axis) before tracing. Without an
installed table the hook is the identity, so models run unmodified on a
single device (tests, smoke runs).

The rules context optionally carries the mesh itself: with a mesh
installed, :func:`constrain` emits a fully explicit ``NamedSharding``
constraint (the stable ``jax.sharding`` surface, usable outside any
ambient mesh context). The mesh needs Auto axes, as ``launch.mesh``
builds them: ``with_sharding_constraint`` refuses Explicit ones.
"""
from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

_state = threading.local()


def current_rules():
    return getattr(_state, "rules", None)


def current_mesh():
    return getattr(_state, "mesh", None)


@contextlib.contextmanager
def activation_rules(rules: dict, mesh=None):
    prev, prev_mesh = current_rules(), current_mesh()
    _state.rules = rules
    _state.mesh = mesh
    try:
        yield
    finally:
        _state.rules = prev
        _state.mesh = prev_mesh


def constrain(x, logical_axes: tuple):
    """Apply a sharding constraint by logical axis names (None = unsharded).

    Identity when no rules are installed. With rules and a mesh installed
    (``activation_rules(rules, mesh=mesh)``) the constraint is an explicit
    ``NamedSharding``; with rules alone it is a bare ``PartitionSpec``
    (requires an ambient mesh at lowering, the legacy launcher path).
    """
    rules = current_rules()
    if rules is None:
        return x
    spec = P(*[rules.get(a, None) if a is not None else None
               for a in logical_axes])
    mesh = current_mesh()
    if mesh is not None:
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    return jax.lax.with_sharding_constraint(x, spec)
