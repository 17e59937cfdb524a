"""Partition engine (parallel/partition.py): regex rule matching,
axis validation, rule-driven staging, topology helpers, and the
serving-side sharded-factor staging with its phantom mask."""

from __future__ import annotations

import numpy as np
import pytest

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from predictionio_tpu.obs import get_registry
from predictionio_tpu.parallel import partition
from predictionio_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    ComputeContext,
    assert_phantom_rows_zero,
)


@pytest.fixture(scope="module")
def ctx42():
    return ComputeContext.create(batch="pt-2d", mesh_shape=(4, 2))


@pytest.fixture(scope="module")
def ctx8():
    return ComputeContext.create(batch="pt-1d", mesh_shape=(8, 1))


class TestMatchPartitionRules:
    RULES = (
        (r"(^|/)(user|item)_factors$", P(MODEL_AXIS, None)),
        (r"(^|/)idx$", P(DATA_AXIS)),
        (r".*", P()),
    )

    def test_first_matching_rule_wins(self):
        rules = (
            (r"factors", P(MODEL_AXIS, None)),
            (r"item_factors", P(DATA_AXIS, None)),
        )
        spec = partition.match_partition_rule(rules, "item_factors")
        assert spec == P(MODEL_AXIS, None)

    def test_tree_paths_drive_matching(self):
        tree = {
            "user_factors": np.zeros((8, 4)),
            "slabs": [{"idx": np.zeros((8, 2), np.int32)}],
            "other": np.zeros((4, 4)),
        }
        specs = partition.match_partition_rules(self.RULES, tree)
        assert specs["user_factors"] == P(MODEL_AXIS, None)
        assert specs["slabs"][0]["idx"] == P(DATA_AXIS)
        assert specs["other"] == P()

    def test_scalar_leaves_never_partitioned(self):
        tree = {"user_factors": np.float32(3.0), "idx": np.zeros((1,))}
        specs = partition.match_partition_rules(self.RULES, tree)
        # both scalar-like: the factors rule is never consulted
        assert specs["user_factors"] == P()
        assert specs["idx"] == P()

    def test_unmatched_leaf_raises(self):
        rules = ((r"^only_this$", P()),)
        with pytest.raises(ValueError, match="no partition rule"):
            partition.match_partition_rules(
                rules, {"something_else": np.zeros((4, 4))}
            )

    def test_leaf_names(self):
        tree = {"a": [np.zeros(2), {"b": np.zeros(2)}]}
        names = partition.tree_leaf_names(tree)
        assert names == ["a/0", "a/1/b"]


class TestValidateRules:
    def test_bad_axis_raises_with_rule_named(self, ctx42):
        rules = ((r"x", P("modle")),)  # typo'd axis
        with pytest.raises(ValueError, match="modle"):
            partition.validate_rules(rules, ctx42.mesh)

    def test_bad_axis_inside_tuple_entry(self, ctx42):
        rules = ((r"x", P((DATA_AXIS, "replica"), None)),)
        with pytest.raises(ValueError, match="replica"):
            partition.validate_rules(rules, ctx42.mesh)

    def test_known_axes_pass(self, ctx42):
        partition.validate_rules(partition.ALS_SHARDED_RULES, ctx42.mesh)
        partition.validate_rules(
            partition.ALS_REPLICATED_RULES, ctx42.mesh
        )

    def test_shard_pytree_validates_by_default(self, ctx42):
        with pytest.raises(ValueError, match="ghost"):
            partition.shard_pytree(
                ctx42, ((r".*", P("ghost")),), {"x": np.zeros((8, 2))}
            )


class TestShardPytree:
    def test_als_sharded_placements(self, ctx42):
        tree = {
            "user_factors": np.zeros((16, 4), np.float32),
            "slabs": [
                {
                    "idx": np.zeros((8, 4), np.int32),
                    "weights": np.zeros((8, 4), np.float32),
                    "valid": np.zeros((8, 4), np.float32),
                }
            ],
            "heavy": {"owner": np.zeros(8, np.int32)},
            "inv_perm": np.arange(16, dtype=np.int32),
        }
        placed = partition.shard_pytree(
            ctx42, partition.ALS_SHARDED_RULES, tree
        )
        mesh = ctx42.mesh
        assert placed["user_factors"].sharding == NamedSharding(
            mesh, P(MODEL_AXIS, None)
        )
        assert placed["slabs"][0]["idx"].sharding == NamedSharding(
            mesh, P((DATA_AXIS, MODEL_AXIS), None)
        )
        assert placed["heavy"]["owner"].sharding == NamedSharding(
            mesh, P((DATA_AXIS, MODEL_AXIS))
        )
        assert placed["inv_perm"].sharding == NamedSharding(
            mesh, P(MODEL_AXIS)
        )

    def test_replicated_placements(self, ctx8):
        placed = partition.shard_pytree(
            ctx8,
            partition.ALS_REPLICATED_RULES,
            {
                "user_factors": np.zeros((16, 4), np.float32),
                "idx": np.zeros((8, 4), np.int32),
            },
        )
        assert placed["user_factors"].sharding.spec == P()
        assert placed["idx"].sharding.spec == P(DATA_AXIS)


class TestTopology:
    def test_default_even_gets_model_axis(self):
        assert partition.topology_mesh_shape(8) == (4, 2)
        assert partition.topology_mesh_shape(2) == (1, 2)

    def test_one_device_degenerates(self):
        assert partition.topology_mesh_shape(1) == (1, 1)

    def test_odd_count_pure_data(self):
        assert partition.topology_mesh_shape(3) == (3, 1)

    def test_explicit_model_parallelism(self):
        assert partition.topology_mesh_shape(8, 4) == (2, 4)

    def test_non_dividing_model_axis_rejected(self):
        with pytest.raises(ValueError, match="does not divide"):
            partition.topology_mesh_shape(8, 3)

    def test_mesh_from_topology_counts(self):
        ctx = partition.mesh_from_topology(4, batch="pt-topo")
        assert ctx.n_devices == 4
        assert ctx.model_parallelism == 2
        with pytest.raises(ValueError, match="have"):
            partition.mesh_from_topology(99)


class TestStageFactorMatrix:
    def test_pads_and_masks(self, ctx42):
        arr = np.random.default_rng(0).normal(size=(9, 4)).astype(
            np.float32
        )
        staged, mask = partition.stage_factor_matrix(ctx42, arr, n_real=9)
        assert staged.shape == (10, 4)  # padded to model multiple (2)
        assert staged.sharding.spec == P(MODEL_AXIS, None)
        assert mask is not None and mask.shape == (10,)
        assert np.asarray(mask).sum() == 1
        np.testing.assert_allclose(np.asarray(staged)[:9], arr)
        np.testing.assert_allclose(np.asarray(staged)[9:], 0.0)

    def test_unpadded_has_no_mask(self, ctx42):
        staged, mask = partition.stage_factor_matrix(
            ctx42, np.zeros((8, 4), np.float32)
        )
        assert staged.shape == (8, 4)
        assert mask is None

    def test_resident_sharded_array_passes_through(self, ctx42):
        arr = jax.device_put(
            np.zeros((8, 4), np.float32),
            NamedSharding(ctx42.mesh, P(MODEL_AXIS, None)),
        )
        staged, mask = partition.stage_factor_matrix(ctx42, arr, n_real=6)
        assert staged is arr  # no host round-trip, no copy
        assert mask is not None and np.asarray(mask).sum() == 2

    def test_resident_non_multiple_rejected(self, ctx42):
        arr = jax.device_put(np.zeros((9, 4), np.float32))
        with pytest.raises(ValueError, match="not a multiple"):
            partition.stage_factor_matrix(ctx42, arr)


class TestShardRowsPadding:
    def test_smaller_than_device_count_pads_and_shards(self, ctx8):
        """3 rows over 8 devices: pad-and-shard (one row per device),
        never a silent replicated fallback — with the padding counted
        in pio_mesh_pad_rows_total."""
        counter = get_registry().counter(
            "pio_mesh_pad_rows_total",
            "Phantom rows added when padding arrays to a mesh-axis "
            "multiple (shard_rows / sharded factor staging)",
        )
        before = counter.value
        arr = np.arange(6, dtype=np.float32).reshape(3, 2)
        out = ctx8.shard_rows(arr)
        assert out.shape == (8, 2)
        shard_rows = {s.data.shape[0] for s in out.addressable_shards}
        assert shard_rows == {1}  # genuinely sharded, one row each
        np.testing.assert_allclose(np.asarray(out)[:3], arr)
        np.testing.assert_allclose(np.asarray(out)[3:], 0.0)
        assert counter.value == before + 5

    def test_multiple_rows_unpadded_uncounted(self, ctx8):
        counter = get_registry().counter(
            "pio_mesh_pad_rows_total",
            "Phantom rows added when padding arrays to a mesh-axis "
            "multiple (shard_rows / sharded factor staging)",
        )
        before = counter.value
        out = ctx8.shard_rows(np.zeros((16, 2), np.float32))
        assert out.shape == (16, 2)
        assert counter.value == before


class TestPhantomInvariant:
    def test_zero_tail_passes(self):
        arr = np.zeros((6, 3), np.float32)
        arr[:4] = 1.0
        assert_phantom_rows_zero(arr, 4)

    def test_nonzero_phantom_raises(self):
        arr = np.zeros((6, 3), np.float32)
        arr[5, 1] = 1e-8  # any nonzero, however small
        with pytest.raises(AssertionError, match="phantom-row"):
            assert_phantom_rows_zero(arr, 4, "item factors")


class TestForceHostDevices:
    """utils/hostdevices.py — the one shared pre-jax-import pinning
    contract (conftest, dryrun, multichip workers, child processes)."""

    def test_sets_when_absent(self, monkeypatch):
        from predictionio_tpu.utils.hostdevices import (
            force_host_platform_device_count,
        )

        monkeypatch.setenv("XLA_FLAGS", "--xla_foo=1")
        force_host_platform_device_count(4)
        assert (
            "--xla_force_host_platform_device_count=4"
            in __import__("os").environ["XLA_FLAGS"]
        )
        assert "--xla_foo=1" in __import__("os").environ["XLA_FLAGS"]

    def test_minimum_mode_never_shrinks(self, monkeypatch):
        import os

        from predictionio_tpu.utils.hostdevices import (
            force_host_platform_device_count,
        )

        monkeypatch.setenv(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8"
        )
        force_host_platform_device_count(2)
        assert "count=8" in os.environ["XLA_FLAGS"]
        force_host_platform_device_count(16)
        assert "count=16" in os.environ["XLA_FLAGS"]

    def test_exact_mode_rewrites(self, monkeypatch):
        import os

        from predictionio_tpu.utils.hostdevices import (
            force_host_platform_device_count,
        )

        monkeypatch.setenv(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8"
        )
        force_host_platform_device_count(2, exact=True)
        assert "count=2" in os.environ["XLA_FLAGS"]
        with pytest.raises(ValueError):
            force_host_platform_device_count(0)
