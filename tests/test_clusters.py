"""Tests for `projection.build_clusters`, the per-object point compaction."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import projection

jax.config.update("jax_platform_name", "cpu")


def _per_slot_argsort(points, labels, max_obj, pts_per_obj):
    """The per-slot compaction: one stable argsort of all points a slot."""
    def one(obj_id):
        m = labels == obj_id
        order = jnp.argsort(~m)  # members first, stable
        idx = order[:pts_per_obj]
        v = m[idx]
        pts = jnp.where(v[:, None], points[idx], 0.0)
        return pts, v, jnp.sum(m)

    obj_ids = jnp.arange(1, max_obj + 1, dtype=jnp.int32)
    return jax.vmap(one)(obj_ids)


def _labels(rng, n, max_obj, case):
    if case == "no_members":
        return np.zeros(n, np.int32)
    if case == "slot_over_p":
        lab = rng.integers(0, max_obj + 1, n)
        lab[rng.random(n) < 0.4] = 3
        return lab
    if case == "all_members":
        return rng.integers(1, max_obj + 1, n)
    if case == "outside_slots":
        return rng.choice([-7, -1, 0, 1, 2, max_obj, max_obj + 1,
                           max_obj + 9], n)
    # Sparse members, as a sweep's few labeled objects.
    lab = rng.integers(1, max_obj + 1, n)
    lab[rng.random(n) < 0.8] = 0
    return lab


# (case, N, O, P): N = 1000 and 120,000 are not powers of two.
CASES = [
    ("no_members", 1000, 12, 16),
    ("slot_over_p", 1000, 12, 16),
    ("all_members", 1000, 12, 16),
    ("outside_slots", 1000, 12, 16),
    ("sparse", 777, 5, 64),
    ("sparse", 10, 6, 16),            # N < P
    ("sparse", 120_000, 12, 256),     # the KITTI sweep's shape
]


@pytest.mark.parametrize("path", ["shape_rule", "packed", "stable_pair"])
@pytest.mark.parametrize("case,n,max_obj,pts_per_obj", CASES)
def test_matches_per_slot_argsort(case, n, max_obj, pts_per_obj, path):
    rng = np.random.default_rng(n + max_obj)
    points = jnp.asarray(rng.normal(0.0, 10.0, (n, 3)).astype(np.float32))
    labels = jnp.asarray(_labels(rng, n, max_obj, case).astype(np.int32))
    if path == "shape_rule":
        got = projection.build_clusters(points, labels, max_obj, pts_per_obj)
    else:
        got = projection._build_clusters(points, labels, max_obj,
                                         pts_per_obj, path == "packed")
    want = _per_slot_argsort(points, labels, max_obj, pts_per_obj)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_fleet_lowers_to_one_sort_without_slot_axis():
    """Under the fleet vmap the step sorts each stream once, not each slot."""
    s, n, max_obj, pts_per_obj = 2, 1000, 12, 16
    step = jax.jit(jax.vmap(
        lambda p, l: projection.build_clusters(p, l, max_obj, pts_per_obj)))
    hlo = step.lower(jax.ShapeDtypeStruct((s, n, 3), jnp.float32),
                     jax.ShapeDtypeStruct((s, n), jnp.int32)).as_text()
    sorts = re.findall(r'"stablehlo\.sort".*?\}\) : \(([^)]*)\)', hlo, re.S)
    # One sort, of one operand: the stream's packed keys, with no slot axis.
    assert sorts == [f"tensor<{s}x{n}xi32>"], sorts
    # The packed key fits at the KITTI sweep's size, not past 31 bits.
    assert projection._packed_key_fits(120_000, 12)
    assert not projection._packed_key_fits(2 ** 28, 12)
