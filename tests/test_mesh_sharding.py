"""launch.mesh + models.sharding unit tier — the helpers the sharded
fleet path (fleet.step / fleet.engine) leans on.

Single-device by default: everything here must hold on a 1-device CPU
host (mesh construction, auto-sizing, divisibility validation, the
activation-rules context discipline and the constrain identity), because
that is what every other tier-1 environment sees.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import mesh as mesh_lib
from repro.models import sharding

jax.config.update("jax_platform_name", "cpu")


class TestFleetMesh:
    def test_make_fleet_mesh_default_uses_all_devices(self):
        m = mesh_lib.make_fleet_mesh()
        assert m.axis_names == ("streams",)
        assert m.devices.size == len(jax.devices())

    def test_make_fleet_mesh_validates_count(self):
        avail = len(jax.devices())
        with pytest.raises(ValueError, match="asked for"):
            mesh_lib.make_fleet_mesh(avail + 1)
        with pytest.raises(ValueError, match="asked for"):
            mesh_lib.make_fleet_mesh(0)

    def test_fleet_shard_count_divides(self):
        # Largest d <= min(avail, S) with S % d == 0, for any device count.
        assert mesh_lib.fleet_shard_count(256, n_devices=8) == 8
        assert mesh_lib.fleet_shard_count(12, n_devices=8) == 6
        assert mesh_lib.fleet_shard_count(7, n_devices=4) == 1
        assert mesh_lib.fleet_shard_count(2, n_devices=8) == 2
        assert mesh_lib.fleet_shard_count(1, n_devices=8) == 1

    def test_resolve_none_and_auto(self):
        assert mesh_lib.resolve_fleet_mesh(None, 16) is None
        m = mesh_lib.resolve_fleet_mesh("auto", 16)
        if len(jax.devices()) == 1:
            # 1-device hosts transparently keep the unsharded path.
            assert m is None
        else:
            assert 16 % m.devices.size == 0

    def test_resolve_int_and_mesh_passthrough(self):
        m = mesh_lib.resolve_fleet_mesh(1, 16)
        assert m is not None and m.devices.size == 1
        assert mesh_lib.resolve_fleet_mesh(m, 16) is m

    def test_resolve_rejects_bad_specs(self):
        with pytest.raises(ValueError, match="auto"):
            mesh_lib.resolve_fleet_mesh("all", 16)
        with pytest.raises(ValueError, match="streams"):
            mesh_lib.resolve_fleet_mesh(
                jax.make_mesh((1, 1), ("data", "model")), 16)

    def test_resolve_rejects_indivisible(self):
        # A size-1 mesh divides everything; the case needs >= 2 devices
        # (exercised for real on the multi-device CI leg).
        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 devices")
        with pytest.raises(ValueError, match="not divisible"):
            mesh_lib.resolve_fleet_mesh(2, 3)

    def test_axis_sizes_helper(self):
        m = jax.make_mesh((1,), ("streams",))
        assert mesh_lib.mesh_axis_sizes(m) == {"streams": 1}
        d = jax.make_mesh((1, 1), ("data", "model"))
        assert mesh_lib.mesh_axis_sizes(d) == {"data": 1, "model": 1}
        assert mesh_lib.batch_axes(d) == ("data",)


class TestActivationRules:
    def test_constrain_identity_without_rules(self):
        x = jnp.arange(8.0)
        assert sharding.current_rules() is None
        y = sharding.constrain(x, ("streams",))
        assert y is x                       # literally the identity

    def test_rules_install_and_restore(self):
        assert sharding.current_rules() is None
        with sharding.activation_rules({"streams": "streams"}):
            assert sharding.current_rules() == {"streams": "streams"}
            assert sharding.current_mesh() is None
        assert sharding.current_rules() is None

    def test_rules_nest_and_restore_on_error(self):
        outer = {"batch": "data"}
        inner = {"streams": "streams"}
        with sharding.activation_rules(outer):
            with sharding.activation_rules(inner):
                assert sharding.current_rules() is inner
            assert sharding.current_rules() is outer
            with pytest.raises(RuntimeError):
                with sharding.activation_rules(inner):
                    raise RuntimeError("boom")
            assert sharding.current_rules() is outer   # restored on error
        assert sharding.current_rules() is None

    def test_mesh_carried_and_restored(self):
        m = jax.make_mesh((1,), ("streams",))
        with sharding.activation_rules({"streams": "streams"}, mesh=m):
            assert sharding.current_mesh() is m
            with sharding.activation_rules({}, mesh=None):
                assert sharding.current_mesh() is None
            assert sharding.current_mesh() is m
        assert sharding.current_mesh() is None

    def test_constrain_values_unchanged_under_mesh(self):
        """With rules + mesh installed the constraint is semantically the
        identity on values (it only pins placement)."""
        m = mesh_lib.make_fleet_mesh(1)
        x = np.arange(12, dtype=np.float32).reshape(4, 3)

        def f(a):
            with sharding.activation_rules({"streams": "streams"}, mesh=m):
                return sharding.constrain(a, ("streams", None)) * 2.0

        np.testing.assert_array_equal(jax.jit(f)(x), x * 2.0)

    def test_constrain_spec_mapping(self):
        """The logical->mesh axis mapping lands in the traced constraint
        (XLA normalizes a 1-device sharding away post-compile, so check
        the jaxpr, not the output)."""
        m = mesh_lib.make_fleet_mesh(1)

        def f(a):
            with sharding.activation_rules({"streams": "streams"}, mesh=m):
                return sharding.constrain(a, (None, "streams"))

        jpr = str(jax.make_jaxpr(f)(np.zeros((2, 4), np.float32)))
        assert "sharding_constraint" in jpr and "streams" in jpr
        # Unknown logical names map to None (replicated), not an error.
        with sharding.activation_rules({"streams": "streams"}, mesh=m):
            out = sharding.constrain(jnp.zeros((2,)), ("unmapped",))
        assert out.shape == (2,)


if __name__ == "__main__":
    pytest.main([__file__, "-v", "-x"])
