"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches JAX device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any JAX
initialization, and smoke tests/benches must keep seeing 1 device.

Fleet serving (repro.fleet) shards its stream axis over a 1-D ``streams``
mesh built by :func:`make_fleet_mesh`; :func:`resolve_fleet_mesh` is the
engine-facing resolver turning the user-facing spec (``None`` / ``"auto"``
/ a device count / a ready Mesh) into a mesh whose size divides the fleet.

Every mesh built here has Auto axes: ``jax.make_mesh`` defaults to
Explicit axes, which ``with_sharding_constraint`` (``models.sharding``)
refuses.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple, axes: tuple):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(data: int = 1, model: int = 1):
    """Tiny mesh for CPU tests (uses however many host devices exist)."""
    return _auto_mesh((data, model), ("data", "model"))


def make_fleet_mesh(n_devices: Optional[int] = None):
    """1-D device mesh over the ``streams`` axis for fleet serving.

    ``n_devices=None`` uses every available device. An explicit count is
    validated against the host (multi-device CPU hosts come from
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``, set before JAX
    initializes — see benchmarks/fleet_scaling.py ``--devices``).
    """
    avail = len(jax.devices())
    n = avail if n_devices is None else int(n_devices)
    if n < 1 or n > avail:
        raise ValueError(
            f"make_fleet_mesh: asked for {n} devices, host has {avail} "
            f"(set XLA_FLAGS=--xla_force_host_platform_device_count "
            f"before JAX initializes to virtualize a CPU host)")
    return _auto_mesh((n,), ("streams",))


def fleet_shard_count(n_streams: int, n_devices: Optional[int] = None) -> int:
    """Largest device count <= available (or ``n_devices``) that divides
    the fleet evenly — the ``mesh="auto"`` sizing rule."""
    avail = len(jax.devices()) if n_devices is None else int(n_devices)
    d = max(min(avail, n_streams), 1)
    while n_streams % d:
        d -= 1
    return d


def resolve_fleet_mesh(spec, n_streams: int):
    """Resolve a fleet mesh spec into a Mesh, or None (single-device path).

    * ``None``   — no sharding (the default single-device dispatch);
    * ``"auto"`` — the largest even divisor of ``n_streams`` that fits the
      host's devices; resolves to None on a 1-device host, so the sharded
      and unsharded paths are picked transparently;
    * ``int``    — exactly that many devices (must divide ``n_streams``);
      ``1`` still builds a (size-1) mesh, which is the bitwise-parity twin
      of the unsharded path (tests/test_sharded_fleet.py);
    * a ``Mesh`` — used as-is; must carry a ``streams`` axis whose total
      size divides ``n_streams``.
    """
    if spec is None:
        return None
    if isinstance(spec, str):
        if spec != "auto":
            raise ValueError(f"fleet mesh spec must be None, 'auto', a "
                             f"device count or a Mesh; got {spec!r}")
        d = fleet_shard_count(n_streams)
        return None if d == 1 else make_fleet_mesh(d)
    if isinstance(spec, int):
        mesh = make_fleet_mesh(spec)
    else:
        mesh = spec
        if "streams" not in mesh.axis_names:
            raise ValueError(f"fleet mesh needs a 'streams' axis, got "
                             f"axes {mesh.axis_names}")
    n_dev = int(mesh.devices.size)
    if n_streams % n_dev:
        raise ValueError(
            f"n_streams={n_streams} is not divisible by the mesh's "
            f"{n_dev} devices; pick a fleet size that shards evenly "
            f"(or mesh='auto' to size the mesh to the fleet)")
    return mesh


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def batch_axes(mesh) -> tuple:
    """The mesh axes the global batch is sharded over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
