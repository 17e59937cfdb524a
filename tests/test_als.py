"""ALS kernel tests: packing correctness, normal-equation agreement with a
dense numpy reference, reconstruction quality, multi-device equivalence."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops.als import (
    ALSFactors,
    build_padded_csr,
    train_als,
)
from predictionio_tpu.parallel.mesh import ComputeContext


@pytest.fixture(scope="module")
def ctx8():
    return ComputeContext.create(batch="als-test")


@pytest.fixture(scope="module")
def ctx1():
    import jax

    return ComputeContext.create(
        batch="als-1dev", devices=jax.devices()[:1]
    )


class TestPacking:
    def test_blocks_cover_all_nnz(self):
        rng = np.random.default_rng(0)
        n_rows, nnz = 17, 300
        rows = rng.integers(0, n_rows, nnz).astype(np.int32)
        cols = rng.integers(0, 50, nnz).astype(np.int32)
        vals = rng.uniform(0.5, 2.0, nnz).astype(np.float32)
        csr = build_padded_csr(rows, cols, vals, n_rows, block_len=8)
        # every nnz appears exactly once with its weight
        total = csr.weights.sum()
        np.testing.assert_allclose(total, vals.sum(), rtol=1e-5)
        # per-row weight sums match
        for u in range(n_rows):
            expected = vals[rows == u].sum()
            got = csr.weights[csr.owner == u].sum()
            # owner 0 also holds padding blocks with zero weight
            np.testing.assert_allclose(got, expected, rtol=1e-5)

    def test_heavy_row_spans_blocks(self):
        rows = np.zeros(100, np.int32)
        cols = np.arange(100).astype(np.int32)
        vals = np.ones(100, np.float32)
        csr = build_padded_csr(rows, cols, vals, 1, block_len=16)
        assert (csr.owner == 0).all()
        assert csr.n_blocks == 7  # ceil(100/16)
        assert csr.weights.sum() == 100

    def test_padding_multiples(self):
        rows = np.asarray([0, 1, 2], np.int32)
        cols = np.asarray([0, 1, 2], np.int32)
        vals = np.ones(3, np.float32)
        csr = build_padded_csr(
            rows, cols, vals, 3, block_len=4, row_multiple=8,
            block_multiple=16,
        )
        assert csr.n_rows_padded == 8
        assert csr.idx.shape[0] == 16


def _dense_implicit_reference(r, x_init, n_iters, rank, lam, alpha):
    """Textbook dense implicit ALS for cross-checking."""
    n_u, n_i = r.shape
    rng = np.random.default_rng(13)
    y = x_init.copy()
    x = np.zeros((n_u, rank), np.float64)

    def solve_side(r_mat, y_):
        yty = y_.T @ y_
        out = np.zeros((r_mat.shape[0], rank))
        for u in range(r_mat.shape[0]):
            cu = alpha * r_mat[u]
            a = yty + (y_.T * cu) @ y_ + lam * np.eye(rank)
            b = y_.T @ ((1 + cu) * (r_mat[u] > 0))
            out[u] = np.linalg.solve(a, b)
        return out

    for _ in range(n_iters):
        x = solve_side(r, y)
        y = solve_side(r.T, x)
    return x, y


class TestSlabSplitting:
    """max_slab_slots caps per-slab size (HBM bound on the factor-gather
    temp at MovieLens-20M scale) without changing any numerics."""

    def _data(self):
        rng = np.random.default_rng(5)
        nnz = 2000
        rows = rng.integers(0, 64, nnz).astype(np.int32)
        cols = rng.integers(0, 40, nnz).astype(np.int32)
        # a few heavy rows
        rows[:600] = rng.integers(0, 3, 600)
        vals = rng.uniform(0.5, 2.0, nnz).astype(np.float32)
        return rows, cols, vals

    def test_split_caps_slab_slots(self):
        from predictionio_tpu.ops.als import build_bucketed

        rows, cols, vals = self._data()
        cap = 64
        packed = build_bucketed(
            rows, cols, vals, 64, block_len=8, row_multiple=2,
            s_max=2, max_slab_slots=cap,
        )
        for s in packed.slabs + packed.heavy:
            # a slab may exceed the cap only when a single
            # row_multiple-sized group already does
            assert (
                s.idx.size <= cap
                or s.idx.shape[0] == 2
            ), s.idx.shape
        assert len(packed.slabs) > 1  # regular bucket was split
        assert len(packed.heavy) > 1  # heavy sub-rows were split
        # every nnz still packed exactly once
        total = sum(s.weights.sum() for s in packed.slabs)
        total += sum(h.weights.sum() for h in packed.heavy)
        np.testing.assert_allclose(total, vals.sum(), rtol=1e-5)

    def test_split_and_unsplit_factors_identical(self, ctx8, ctx1):
        """Splitting is pure layout: trained factors must be bit-stable
        vs the unsplit packing (same stats, same solves, same order)."""
        from predictionio_tpu.ops.als import (
            _device_slabs,
            build_bucketed,
            make_solve_side,
        )

        rows, cols, vals = self._data()
        y = np.asarray(
            np.random.default_rng(0).normal(size=(40, 4)), np.float32
        )

        def solve_with(cap):
            packed = build_bucketed(
                rows, cols, vals, 64, block_len=8, row_multiple=2,
                s_max=2, max_slab_slots=cap,
            )
            slabs, heavy = _device_slabs(ctx1, packed)
            f = make_solve_side(ctx1, packed, True, 1.0)
            return np.asarray(f(jnp.asarray(y), slabs, heavy, 0.1))

        split, unsplit = solve_with(512), solve_with(1 << 30)
        np.testing.assert_allclose(split, unsplit, rtol=1e-6, atol=1e-7)

    def test_sharded_path_with_split_slabs(self, ctx8, ctx1):
        """plan_shards + sharded training still agree with the
        single-device result when slabs are split."""
        rows, cols, vals = self._data()
        kwargs = dict(
            n_users=64, n_items=40, rank=4, iterations=2, reg=0.1,
            block_len=8, s_max=2, max_slab_slots=512,
        )
        fs = train_als(
            ctx8, rows, cols, vals, factor_sharding="sharded", **kwargs
        )
        f1 = train_als(ctx1, rows, cols, vals, **kwargs)
        np.testing.assert_allclose(
            fs.user_factors, f1.user_factors, rtol=1e-4, atol=1e-5
        )


class TestNativePackParity:
    """native/alspack.cc fill vs the numpy fallback — identical output
    for every geometry (heavy rows, padding, slot-cap splits)."""

    def test_native_and_numpy_fill_agree(self, monkeypatch):
        from predictionio_tpu.ops import als

        if als._load_alspack() is None:
            pytest.skip("native alspack not built (no toolchain)")
        rng = np.random.default_rng(9)
        for _ in range(10):
            n_rows = int(rng.integers(1, 150))
            nnz = int(rng.integers(0, 2500))
            rows = rng.integers(0, n_rows, nnz).astype(np.int32)
            cols = rng.integers(0, 80, nnz).astype(np.int32)
            vals = rng.uniform(0.1, 5.0, nnz).astype(np.float32)
            kw = dict(
                block_len=4, row_multiple=int(rng.choice([1, 2, 8])),
                s_max=2, max_slab_slots=int(rng.choice([64, 2 << 20])),
            )
            pn = als.build_bucketed(rows, cols, vals, n_rows, **kw)
            monkeypatch.setattr(als, "_ALSPACK_LIB", None)
            monkeypatch.setattr(als, "_ALSPACK_TRIED", True)
            pf = als.build_bucketed(rows, cols, vals, n_rows, **kw)
            monkeypatch.undo()
            for a, b in zip(pn.slabs + pn.heavy, pf.slabs + pf.heavy):
                np.testing.assert_array_equal(a.idx, b.idx)
                np.testing.assert_array_equal(a.weights, b.weights)
                np.testing.assert_array_equal(a.valid, b.valid)
            np.testing.assert_array_equal(pn.inv_perm, pf.inv_perm)


class TestComputeDtype:
    """bf16 gather/Gramian mode: reduced-precision stats, f32 accum +
    solve — reconstructions must stay close to the f32 run."""

    def test_bf16_factors_close_to_f32(self, ctx1):
        rng = np.random.default_rng(6)
        n_users, n_items, nnz = 40, 30, 600
        rows = rng.integers(0, n_users, nnz).astype(np.int32)
        cols = rng.integers(0, n_items, nnz).astype(np.int32)
        vals = rng.uniform(0.5, 4.0, nnz).astype(np.float32)
        kwargs = dict(
            n_users=n_users, n_items=n_items, rank=4, iterations=3,
            reg=0.1, block_len=8,
        )
        f32 = train_als(ctx1, rows, cols, vals, **kwargs)
        bf16 = train_als(
            ctx1, rows, cols, vals, compute_dtype="bfloat16", **kwargs
        )
        assert np.isfinite(bf16.user_factors).all()
        # bf16 mantissa is 8 bits: expect agreement to ~1e-2 relative
        err = np.abs(bf16.user_factors - f32.user_factors)
        scale = np.abs(f32.user_factors).max()
        assert err.max() / max(scale, 1e-6) < 0.05

    def test_kmajor_gather_layout_identical(self, ctx1, monkeypatch):
        """The kmajor gather formulation (unpadded [k, R, W] temp) must
        produce the same factors as the default layout."""
        rng = np.random.default_rng(8)
        rows = rng.integers(0, 40, 600).astype(np.int32)
        cols = rng.integers(0, 30, 600).astype(np.int32)
        vals = rng.uniform(0.5, 4.0, 600).astype(np.float32)
        kwargs = dict(
            n_users=40, n_items=30, rank=4, iterations=3, reg=0.1,
            block_len=8,
        )
        base = train_als(ctx1, rows, cols, vals, **kwargs)
        monkeypatch.setenv("PIO_ALS_GATHER_LAYOUT", "kmajor")
        km = train_als(ctx1, rows, cols, vals, **kwargs)
        np.testing.assert_allclose(
            km.user_factors, base.user_factors, rtol=1e-4, atol=1e-6
        )

    def test_env_knob_resolves(self, monkeypatch):
        from predictionio_tpu.ops.als import _resolve_compute

        monkeypatch.delenv("PIO_ALS_COMPUTE_DTYPE", raising=False)
        # default is "auto": f32 on the CPU backend the tests pin
        # (bf16 on TPU — see ops.als._resolve_compute)
        assert _resolve_compute(None) is None
        assert _resolve_compute("auto") is None
        assert _resolve_compute("float32") is None
        assert _resolve_compute("bfloat16") == jnp.bfloat16
        monkeypatch.setenv("PIO_ALS_COMPUTE_DTYPE", "bfloat16")
        assert _resolve_compute(None) == jnp.bfloat16
        assert _resolve_compute("float32") is None


class TestSolveCorrectness:
    def test_matches_dense_reference(self, ctx8):
        """One deterministic seed: our mesh solve must match the dense
        numpy implicit-ALS reference iteration-for-iteration."""
        rng = np.random.default_rng(7)
        n_u, n_i, rank = 12, 9, 4
        r = np.zeros((n_u, n_i), np.float32)
        nnz_mask = rng.uniform(size=(n_u, n_i)) < 0.4
        r[nnz_mask] = rng.integers(1, 5, nnz_mask.sum())
        rows, cols = np.nonzero(r)
        vals = r[rows, cols]

        factors = train_als(
            ctx8,
            rows.astype(np.int32),
            cols.astype(np.int32),
            vals.astype(np.float32),
            n_users=n_u,
            n_items=n_i,
            rank=rank,
            iterations=3,
            reg=0.1,
            alpha=2.0,
            implicit=True,
            block_len=4,
            row_chunk=2,
        )
        # replicate the same init the device code uses (logical size)
        import jax

        key = jax.random.PRNGKey(13)
        y0 = np.asarray(
            jax.random.normal(key, (n_i, rank), np.float32)
            / np.sqrt(rank)
        ).astype(np.float64)
        x_ref, y_ref = _dense_implicit_reference(r, y0, 3, rank, 0.1, 2.0)
        np.testing.assert_allclose(
            factors.user_factors, x_ref, rtol=2e-3, atol=2e-4
        )
        np.testing.assert_allclose(
            factors.item_factors, y_ref, rtol=2e-3, atol=2e-4
        )

    def test_reconstruction_quality_implicit(self, ctx8):
        """Low-rank planted structure: observed entries should score far
        above unobserved ones."""
        rng = np.random.default_rng(3)
        n_u, n_i, rank = 40, 30, 8
        # two user groups × two item groups
        r = np.zeros((n_u, n_i), np.float32)
        r[:20, :15] = rng.integers(1, 4, (20, 15))
        r[20:, 15:] = rng.integers(1, 4, (20, 15))
        rows, cols = np.nonzero(r)
        factors = train_als(
            ctx8,
            rows.astype(np.int32),
            cols.astype(np.int32),
            r[rows, cols],
            n_users=n_u,
            n_items=n_i,
            rank=rank,
            iterations=8,
            reg=0.05,
            alpha=4.0,
            block_len=8,
            row_chunk=4,
        )
        scores = factors.user_factors @ factors.item_factors.T
        in_block = scores[:20, :15].mean()
        out_block = scores[:20, 15:].mean()
        assert in_block > 0.7
        assert in_block > out_block + 0.5

    def test_explicit_mode_fits_ratings(self, ctx8):
        rng = np.random.default_rng(5)
        n_u, n_i, rank = 30, 20, 6
        true_u = rng.normal(size=(n_u, rank))
        true_i = rng.normal(size=(n_i, rank))
        full = true_u @ true_i.T
        mask = rng.uniform(size=full.shape) < 0.6
        rows, cols = np.nonzero(mask)
        vals = full[rows, cols].astype(np.float32)
        factors = train_als(
            ctx8,
            rows.astype(np.int32),
            cols.astype(np.int32),
            vals,
            n_users=n_u,
            n_items=n_i,
            rank=rank,
            iterations=12,
            reg=0.05,
            implicit=False,
            block_len=8,
            row_chunk=4,
        )
        pred = factors.user_factors @ factors.item_factors.T
        rmse = np.sqrt(((pred[mask] - full[mask]) ** 2).mean())
        assert rmse < 0.15 * np.abs(full[mask]).std() + 0.1

    def test_single_vs_multi_device_identical(self, ctx8, ctx1):
        rng = np.random.default_rng(11)
        nnz = 200
        rows = rng.integers(0, 16, nnz).astype(np.int32)
        cols = rng.integers(0, 12, nnz).astype(np.int32)
        vals = rng.integers(1, 5, nnz).astype(np.float32)
        kwargs = dict(
            n_users=16, n_items=12, rank=4, iterations=2, reg=0.1,
            alpha=1.0, block_len=4, row_chunk=2,
        )
        f8 = train_als(ctx8, rows, cols, vals, **kwargs)
        f1 = train_als(ctx1, rows, cols, vals, **kwargs)
        np.testing.assert_allclose(
            f8.user_factors, f1.user_factors, rtol=1e-4, atol=1e-5
        )

    def test_cold_entities_get_zero_factors(self, ctx8):
        # user 3 and item 4 never interact
        rows = np.asarray([0, 1, 2], np.int32)
        cols = np.asarray([0, 1, 2], np.int32)
        vals = np.ones(3, np.float32)
        factors = train_als(
            ctx8, rows, cols, vals, n_users=4, n_items=5, rank=4,
            iterations=2, block_len=4, row_chunk=1,
        )
        assert isinstance(factors, ALSFactors)
        np.testing.assert_allclose(factors.user_factors[3], 0.0, atol=1e-6)
        np.testing.assert_allclose(factors.item_factors[4], 0.0, atol=1e-6)


@pytest.fixture(scope="module")
def ctx42():
    """2D data×model mesh — the factor-sharded training configuration."""
    return ComputeContext.create(batch="als-2d", mesh_shape=(4, 2))


class TestShardedFactors:
    """Model-axis factor sharding (VERDICT r1 #2): the 2D mesh must do
    real work in training and agree with the replicated 1-device run."""

    def _data(self, heavy=False):
        rng = np.random.default_rng(21)
        nnz = 600
        rows = rng.integers(0, 24, nnz).astype(np.int32)
        cols = rng.integers(0, 18, nnz).astype(np.int32)
        vals = rng.integers(1, 5, nnz).astype(np.float32)
        if heavy:
            # rows 0/1 and item 0 get degree ≫ s_max·block_len so the
            # heavy (sub-row split) path engages in both directions
            hr = np.concatenate([
                np.zeros(60, np.int32), np.ones(60, np.int32)])
            hc = np.concatenate([
                np.arange(60, dtype=np.int32) % 18,
                np.zeros(60, np.int32)])
            rows = np.concatenate([rows, hr])
            cols = np.concatenate([cols, hc])
            vals = np.concatenate([vals, np.ones(120, np.float32)])
        # dedupe duplicate (row, col) pairs: keep first occurrence
        _, keep = np.unique(
            rows.astype(np.int64) * 1000 + cols, return_index=True
        )
        return rows[keep], cols[keep], vals[keep]

    def test_2d_mesh_matches_1device(self, ctx42, ctx1):
        rows, cols, vals = self._data()
        kwargs = dict(
            n_users=24, n_items=18, rank=4, iterations=3, reg=0.1,
            alpha=2.0, block_len=4,
        )
        f2d = train_als(ctx42, rows, cols, vals, **kwargs)
        f1 = train_als(ctx1, rows, cols, vals, **kwargs)
        np.testing.assert_allclose(
            f2d.user_factors, f1.user_factors, rtol=1e-4, atol=1e-5
        )
        np.testing.assert_allclose(
            f2d.item_factors, f1.item_factors, rtol=1e-4, atol=1e-5
        )

    def test_sharded_heavy_rows_match(self, ctx42, ctx1):
        rows, cols, vals = self._data(heavy=True)
        kwargs = dict(
            n_users=24, n_items=18, rank=4, iterations=3, reg=0.1,
            alpha=1.0, block_len=4, s_max=2,
        )
        f2d = train_als(ctx42, rows, cols, vals, **kwargs)
        f1 = train_als(ctx1, rows, cols, vals, **kwargs)
        np.testing.assert_allclose(
            f2d.user_factors, f1.user_factors, rtol=1e-4, atol=1e-5
        )
        np.testing.assert_allclose(
            f2d.item_factors, f1.item_factors, rtol=1e-4, atol=1e-5
        )

    def test_sharded_explicit_mode(self, ctx42, ctx1):
        rows, cols, vals = self._data()
        kwargs = dict(
            n_users=24, n_items=18, rank=4, iterations=3, reg=0.1,
            implicit=False, block_len=4,
        )
        f2d = train_als(ctx42, rows, cols, vals, **kwargs)
        f1 = train_als(ctx1, rows, cols, vals, **kwargs)
        np.testing.assert_allclose(
            f2d.user_factors, f1.user_factors, rtol=1e-4, atol=1e-5
        )

    def test_forced_sharded_on_data_mesh(self, ctx8, ctx1):
        """factor_sharding="sharded" also works on a pure data mesh
        (n_shards = n_devices, model axis of size 1)."""
        rows, cols, vals = self._data()
        kwargs = dict(
            n_users=24, n_items=18, rank=4, iterations=2, reg=0.1,
            block_len=4,
        )
        fs = train_als(
            ctx8, rows, cols, vals, factor_sharding="sharded", **kwargs
        )
        f1 = train_als(ctx1, rows, cols, vals, **kwargs)
        np.testing.assert_allclose(
            fs.user_factors, f1.user_factors, rtol=1e-4, atol=1e-5
        )

    def test_factors_actually_sharded_on_device(self, ctx42):
        """The in-loop factor arrays must be split over MODEL_AXIS —
        each device holds 1/model_parallelism of the rows (not a
        replicated copy constrained at the end)."""
        from predictionio_tpu.ops.als import check_factor_sharding

        rows, cols, vals = self._data()
        check_factor_sharding(
            ctx42, rows, cols, vals, 24, 18, rank=4, block_len=4
        )

    def test_plan_shards_covers_all_nnz(self):
        from predictionio_tpu.ops.als import build_bucketed, plan_shards

        rows, cols, vals = self._data(heavy=True)
        packed = build_bucketed(
            rows, cols, vals, 24, block_len=4, row_multiple=8, s_max=2
        )
        plan = plan_shards(packed, 8)
        total = sum(s.weights.sum() for s in packed.slabs)
        if plan.heavy is not None:
            total += plan.heavy.weights.sum()
        np.testing.assert_allclose(total, vals.sum(), rtol=1e-5)
        # inv_perm_dm is a valid permutation into the device-major layout
        assert plan.inv_perm_dm.max() < 8 * plan.c_local
        assert len(np.unique(plan.inv_perm_dm)) == packed.n_rows_padded


class TestReviewRegressions:
    def test_explicit_zero_rating_counts(self, ctx8):
        """A real 0-valued rating must contribute to the normal equations
        (validity mask, not weight!=0)."""
        # user 0 rates item 0 as 0.0 and item 1 as 4.0
        rows = np.asarray([0, 0], np.int32)
        cols = np.asarray([0, 1], np.int32)
        vals = np.asarray([0.0, 4.0], np.float32)
        f = train_als(
            ctx8, rows, cols, vals, n_users=1, n_items=2, rank=2,
            iterations=4, reg=0.1, implicit=False, block_len=2, row_chunk=1,
        )
        pred = f.user_factors @ f.item_factors.T
        # the observed 0 should be fit near 0, not treated as unobserved
        assert abs(pred[0, 0]) < 1.0
        assert pred[0, 1] > 2.0

    def test_empty_batch_predict(self, ctx8, memory_storage):
        from predictionio_tpu.models.recommendation import (
            ALSAlgorithm,
            ALSParams,
            ALSRecModel,
        )
        from predictionio_tpu.utils.bimap import BiMap

        model = ALSRecModel(
            user_factors=np.ones((2, 4), np.float32),
            item_factors=np.ones((3, 4), np.float32),
            user_map=BiMap(["u0", "u1"]),
            item_map=BiMap(["i0", "i1", "i2"]),
        )
        assert ALSAlgorithm(ALSParams()).batch_predict(model, []) == []


class TestCheckpointResume:
    def _data(self):
        rng = np.random.default_rng(2)
        nnz = 150
        return (
            rng.integers(0, 12, nnz).astype(np.int32),
            rng.integers(0, 10, nnz).astype(np.int32),
            rng.integers(1, 5, nnz).astype(np.float32),
        )

    def test_resume_matches_uninterrupted(self, ctx8, tmp_path):
        rows, cols, vals = self._data()
        kwargs = dict(
            n_users=12, n_items=10, rank=4, iterations=6, reg=0.1,
            block_len=4, row_chunk=2,
        )
        full = train_als(ctx8, rows, cols, vals, **kwargs)
        # run that checkpoints every 2 iterations, "crashes" after 4
        train_als(
            ctx8, rows, cols, vals,
            **{**kwargs, "iterations": 4},
            checkpoint_dir=str(tmp_path), checkpoint_every=2,
        )
        ck = dict(np.load(tmp_path / "als_checkpoint.npz"))
        assert int(ck["iteration"]) == 2  # intermediate ckpt exists
        # resume from the iteration-2 state and finish to 6: must match
        # the uninterrupted run exactly (same alternating sequence)
        resumed = train_als(
            ctx8, rows, cols, vals, **kwargs,
            checkpoint_dir=str(tmp_path), checkpoint_every=2, resume=True,
        )
        np.testing.assert_allclose(
            resumed.user_factors, full.user_factors, rtol=1e-4, atol=1e-5
        )
        np.testing.assert_allclose(
            resumed.item_factors, full.item_factors, rtol=1e-4, atol=1e-5
        )

    def test_timer_records_steps(self, ctx8, tmp_path):
        from predictionio_tpu.utils.profiling import StepTimer

        rows, cols, vals = self._data()
        timer = StepTimer()
        train_als(
            ctx8, rows, cols, vals, n_users=12, n_items=10, rank=4,
            iterations=3, block_len=4, row_chunk=2, timer=timer,
        )
        s = timer.summary()
        assert s["als/user_solve"]["count"] == 3
        assert s["als/item_solve"]["count"] == 3
        assert s["als/user_solve"]["mean_s"] > 0
        import json

        json.loads(timer.to_json())  # serializable


class TestResumeEdgeCases:
    def test_zero_iterations(self, ctx8):
        rows = np.asarray([0, 1], np.int32)
        cols = np.asarray([0, 1], np.int32)
        vals = np.ones(2, np.float32)
        f = train_als(
            ctx8, rows, cols, vals, n_users=2, n_items=2, rank=2,
            iterations=0, block_len=2, row_chunk=1,
        )
        assert f.user_factors.shape == (2, 2)
        assert np.isfinite(f.user_factors).all()

    def test_resume_from_unaligned_iteration_still_checkpoints(
        self, ctx8, tmp_path
    ):
        """Chunk boundaries align to absolute multiples of
        checkpoint_every even when resuming from a checkpoint written
        on a different schedule (e.g. iteration 3 with every=2)."""
        rows = np.asarray([0, 1, 0], np.int32)
        cols = np.asarray([0, 1, 1], np.int32)
        vals = np.ones(3, np.float32)
        from predictionio_tpu.ops.als import _write_checkpoint

        _write_checkpoint(
            str(tmp_path / "als_checkpoint.npz"),
            iteration=3,
            user_factors=np.zeros((2, 2), np.float32),
            item_factors=np.zeros((2, 2), np.float32),
        )
        train_als(
            ctx8, rows, cols, vals, n_users=2, n_items=2, rank=2,
            iterations=6, block_len=2, row_chunk=1,
            checkpoint_dir=str(tmp_path), checkpoint_every=2,
            resume=True,
        )
        ck = dict(np.load(tmp_path / "als_checkpoint.npz"))
        assert int(ck["iteration"]) == 4  # wrote at the next multiple

    def test_resume_at_full_iteration_count_uses_checkpoint(
        self, ctx8, tmp_path
    ):
        rows = np.asarray([0, 1, 0], np.int32)
        cols = np.asarray([0, 1, 1], np.int32)
        vals = np.ones(3, np.float32)
        kwargs = dict(
            n_users=2, n_items=2, rank=2, block_len=2, row_chunk=1,
            checkpoint_dir=str(tmp_path),
        )
        # externally-produced checkpoint at the requested count
        from predictionio_tpu.ops.als import _write_checkpoint

        _write_checkpoint(
            str(tmp_path / "als_checkpoint.npz"),
            iteration=4,
            user_factors=np.full((2, 2), 7.0, np.float32),
            item_factors=np.full((2, 2), 8.0, np.float32),
        )
        f = train_als(
            ctx8, rows, cols, vals, iterations=4, resume=True, **kwargs
        )
        np.testing.assert_allclose(f.user_factors, 7.0)
        np.testing.assert_allclose(f.item_factors, 8.0)


class TestGatherLayoutDefault:
    def test_auto_resolves_by_backend(self, monkeypatch):
        from predictionio_tpu.ops.als import _resolve_gather_layout

        monkeypatch.delenv("PIO_ALS_GATHER_LAYOUT", raising=False)
        # tests pin the cpu backend -> auto means kminor here
        assert _resolve_gather_layout() == "kminor"
        monkeypatch.setenv("PIO_ALS_GATHER_LAYOUT", "auto")
        assert _resolve_gather_layout() == "kminor"
        monkeypatch.setenv("PIO_ALS_GATHER_LAYOUT", "kmajor")
        assert _resolve_gather_layout() == "kmajor"
        monkeypatch.setenv("PIO_ALS_GATHER_LAYOUT", "bogus")
        import pytest as _pytest

        with _pytest.raises(ValueError, match="bogus"):
            _resolve_gather_layout()


class TestShardPlanEdges:
    """plan_shards / stage_sharded edge cases (previously untested):
    non-divisible row counts, a mesh axis of size 1 degrading to the
    unsharded layout, and empty slab groups."""

    def _packed(self, row_multiple=8, nnz=200, n_rows=24):
        from predictionio_tpu.ops.als import build_bucketed

        rng = np.random.default_rng(3)
        rows = rng.integers(0, n_rows, nnz).astype(np.int32)
        cols = rng.integers(0, 16, nnz).astype(np.int32)
        vals = np.ones(nnz, np.float32)
        return build_bucketed(
            rows, cols, vals, n_rows, block_len=4,
            row_multiple=row_multiple,
        )

    def test_rows_not_divisible_by_shards_raises(self):
        from predictionio_tpu.ops.als import plan_shards

        packed = self._packed(row_multiple=3)
        with pytest.raises(ValueError, match="not divisible"):
            plan_shards(packed, 8)

    def test_one_shard_degrades_to_unsharded_layout(self):
        """n_shards=1 must reproduce the plain Bucketed layout: the
        device-major permutation IS inv_perm and one device owns every
        stats row."""
        from predictionio_tpu.ops.als import plan_shards

        packed = self._packed(row_multiple=1)
        plan = plan_shards(packed, 1)
        assert plan.n_shards == 1
        assert plan.c_local == packed.n_stat_rows
        np.testing.assert_array_equal(
            np.sort(plan.inv_perm_dm), np.sort(packed.inv_perm)
        )

    def test_empty_heavy_group_stages_clean(self, ctx8):
        """No heavy rows: the staged side carries an empty heavy tuple
        and the sharded train step still runs."""
        from predictionio_tpu.ops.als import plan_shards, stage_sharded

        packed = self._packed(row_multiple=8)
        assert packed.heavy == []
        plan = plan_shards(packed, 8)
        assert plan.heavy is None and plan.n_heavy_slots_local == 0
        side = stage_sharded(ctx8, packed, plan)
        assert side.heavy == ()
        assert side.inv.shape == (packed.n_rows_padded,)

    def test_empty_interactions_stage_and_train(self, ctx8):
        """Zero nnz: every slab row is padding, the sharded epoch still
        executes and every factor row is an exact-zero phantom-like
        solve (nothing observed anywhere)."""
        f = train_als(
            ctx8,
            np.zeros(0, np.int32),
            np.zeros(0, np.int32),
            np.zeros(0, np.float32),
            n_users=4, n_items=4, rank=2, iterations=1, block_len=4,
            factor_sharding="sharded",
        )
        np.testing.assert_allclose(f.user_factors, 0.0)
        assert f.user_factors.shape == (4, 2)


class TestPhantomRowRegression:
    """The phantom-row invariant end to end: padded factor rows solve
    to exact zeros, and even a CORRUPT nonzero phantom cannot leak
    into serving top-k (the staged mask excludes it)."""

    def test_sharded_train_phantoms_exactly_zero(self, ctx42):
        rng = np.random.default_rng(11)
        nnz = 300
        rows = rng.integers(0, 21, nnz).astype(np.int32)  # 21 -> pad 24
        cols = rng.integers(0, 13, nnz).astype(np.int32)  # 13 -> pad 16
        vals = np.ones(nnz, np.float32)
        f = train_als(
            ctx42, rows, cols, vals, n_users=21, n_items=13, rank=4,
            iterations=2, block_len=4, factor_sharding="sharded",
            return_layout="device",
        )
        uf = np.asarray(f.user_factors)
        itf = np.asarray(f.item_factors)
        assert uf.shape[0] == 24 and itf.shape[0] == 16
        # EXACT zeros, not allclose: the padded normal equations have
        # b = 0, so any nonzero is corrupt state, not roundoff
        assert not uf[21:].any()
        assert not itf[13:].any()

    def test_nonzero_phantom_is_caught_centrally(self, ctx42, monkeypatch):
        """If a solver bug ever leaves a phantom nonzero, train_als
        refuses to return factors rather than let it reach top-k."""
        from predictionio_tpu.ops import als as als_mod

        real_solve = als_mod._solve

        def corrupt_solve(a, b, cnt, yty, lam, implicit, k, dtype):
            return real_solve(a, b, cnt, yty, lam, implicit, k, dtype) + 0.5

        monkeypatch.setattr(als_mod, "_solve", corrupt_solve)
        rows = np.asarray([0, 1, 2], np.int32)
        cols = np.asarray([0, 1, 2], np.int32)
        vals = np.ones(3, np.float32)
        with pytest.raises(AssertionError, match="phantom-row"):
            train_als(
                ctx42, rows, cols, vals, n_users=3, n_items=3, rank=2,
                iterations=1, block_len=4,
            )

    def test_corrupt_phantom_never_reaches_topk(self, ctx42):
        """Serving-side belt to the trainer-side suspenders: a staged
        catalog whose phantom row is (artificially) nonzero is still
        masked out of every ranking."""
        from predictionio_tpu.models.recommendation import (
            ALSAlgorithm,
            ALSRecModel,
        )
        from predictionio_tpu.utils.bimap import BiMap

        n_items = 3  # pads to 4 on the model axis
        item_f = np.zeros((n_items, 2), np.float32)
        item_f[:] = [[0.1, 0.0], [0.2, 0.0], [0.3, 0.0]]
        user_f = np.asarray([[-1.0, 0.0]], np.float32)  # all scores < 0
        algo = ALSAlgorithm()
        model = algo.stage_model(
            ctx42,
            ALSRecModel(
                user_factors=user_f,
                item_factors=item_f,
                user_map=BiMap(["u0"]),
                item_map=BiMap([f"i{i}" for i in range(n_items)]),
            ),
        )
        # corrupt the padded row AFTER staging: phantom gets factors
        # that would out-score every real item (dot = 0 > negatives)
        corrupt = np.array(model.item_factors)  # writable host copy
        assert corrupt.shape[0] == 4
        corrupt[3] = [0.0, 5.0]
        model = dataclasses.replace(
            model,
            item_factors=jax.device_put(
                corrupt, model.item_factors.sharding
            ),
        )
        qs = [{"user": "u0", "num": 3}]
        out = algo.batch_predict_collect(
            model, algo.batch_predict_launch(model, qs), qs
        )
        items = [s["item"] for s in out[0]["itemScores"]]
        assert len(items) == 3 and set(items) == {"i0", "i1", "i2"}

    def test_without_mask_the_phantom_would_leak(self, ctx42):
        """The scenario the mask exists for: same corrupt catalog with
        the mask stripped ranks the phantom first — proving the
        regression test above actually bites."""
        from predictionio_tpu.ops import similarity

        item_f = np.asarray(
            [[0.1, 0.0], [0.2, 0.0], [0.3, 0.0], [0.0, 5.0]], np.float32
        )
        user_f = np.asarray([[-1.0, 0.0], [0.0, 1.0]], np.float32)
        scores, idx = similarity.gather_top_k_dot(
            user_f, np.asarray([0], np.int32), item_f, 3
        )
        assert int(np.asarray(idx)[0, 0]) == 3  # phantom wins unmasked
        scores_m, idx_m = similarity.gather_top_k_dot(
            user_f, np.asarray([0], np.int32), item_f, 3,
            mask=jnp.asarray([False, False, False, True]),
        )
        assert 3 not in np.asarray(idx_m)[0].tolist()


class TestDeviceLayoutServing:
    def test_unbroken_sharded_train_to_serve(self, ctx42):
        """train_als(return_layout='device') feeds serving with zero
        host gathers: the staged model keeps the training arrays (same
        objects), predictions match the host-layout pipeline."""
        from predictionio_tpu.models.recommendation import (
            ALSAlgorithm,
            ALSRecModel,
        )
        from predictionio_tpu.utils.bimap import BiMap

        rng = np.random.default_rng(9)
        nnz, n_u, n_i = 400, 30, 20
        rows = rng.integers(0, n_u, nnz).astype(np.int32)
        cols = rng.integers(0, n_i, nnz).astype(np.int32)
        vals = np.ones(nnz, np.float32)
        kwargs = dict(
            n_users=n_u, n_items=n_i, rank=4, iterations=2, block_len=4
        )
        f_dev = train_als(
            ctx42, rows, cols, vals, return_layout="device", **kwargs
        )
        assert isinstance(f_dev.user_factors, jax.Array)
        assert f_dev.n_users == n_u and f_dev.n_items == n_i
        umap = BiMap([f"u{i}" for i in range(n_u)])
        imap = BiMap([f"i{i}" for i in range(n_i)])
        algo = ALSAlgorithm()
        staged = algo.stage_model(
            ctx42,
            ALSRecModel(
                user_factors=f_dev.user_factors,
                item_factors=f_dev.item_factors,
                user_map=umap,
                item_map=imap,
            ),
        )
        # the training arrays ARE the serving arrays — no host gather
        assert staged.user_factors is f_dev.user_factors
        assert staged.item_factors is f_dev.item_factors
        assert staged.item_phantom_mask is not None

        f_host = train_als(ctx42, rows, cols, vals, **kwargs)
        host_model = algo.stage_model(
            ctx42,
            ALSRecModel(
                user_factors=f_host.user_factors,
                item_factors=f_host.item_factors,
                user_map=umap,
                item_map=imap,
            ),
        )
        qs = [{"user": f"u{i}", "num": 5} for i in (0, 7, 19)]
        dev_out = algo.batch_predict_collect(
            staged, algo.batch_predict_launch(staged, qs), qs
        )
        host_out = algo.batch_predict_collect(
            host_model, algo.batch_predict_launch(host_model, qs), qs
        )
        assert [
            [s["item"] for s in o["itemScores"]] for o in dev_out
        ] == [[s["item"] for s in o["itemScores"]] for o in host_out]


class TestReviewRegressionsPR14:
    def test_data_parallel_padded_factors_still_masked(self, ctx8):
        """Device-layout factors are padded on data-parallel meshes
        too (row_multiple = data_parallelism); the phantom mask must
        key on 'rows > real', never on the mesh having a model axis —
        unmasked, a zero phantom out-scores all-negative real items
        and serving would decode a ghost index."""
        from predictionio_tpu.models.recommendation import (
            ALSAlgorithm,
            ALSRecModel,
        )
        from predictionio_tpu.utils.bimap import BiMap

        n_u, n_i = 9, 13  # both pad to multiples of 8 on the 8x1 mesh
        rng = np.random.default_rng(2)
        rows = rng.integers(0, n_u, 200).astype(np.int32)
        cols = rng.integers(0, n_i, 200).astype(np.int32)
        f = train_als(
            ctx8, rows, cols, np.ones(200, np.float32),
            n_users=n_u, n_items=n_i, rank=4, iterations=2, block_len=4,
            return_layout="device",
        )
        assert f.item_factors.shape[0] == 16  # padded
        algo = ALSAlgorithm()
        staged = algo.stage_model(
            ctx8,
            ALSRecModel(
                user_factors=f.user_factors,
                item_factors=f.item_factors,
                user_map=BiMap([f"u{i}" for i in range(n_u)]),
                item_map=BiMap([f"i{i}" for i in range(n_i)]),
            ),
        )
        assert staged.item_phantom_mask is not None
        assert np.asarray(staged.item_phantom_mask).sum() == 3
        qs = [{"user": "u0", "num": 13}]
        out = algo.batch_predict_collect(
            staged, algo.batch_predict_launch(staged, qs), qs
        )
        items = {s["item"] for s in out[0]["itemScores"]}
        assert len(out[0]["itemScores"]) == 13
        assert items == {f"i{i}" for i in range(n_i)}  # no ghosts

    def test_resume_complete_honors_device_layout(self, ctx8, tmp_path):
        """A resume that lands at the full iteration count must still
        return the documented device layout (padded, device-resident),
        not silently fall back to host numpy."""
        rows = np.asarray([0, 1, 2], np.int32)
        cols = np.asarray([0, 1, 2], np.int32)
        vals = np.ones(3, np.float32)
        kwargs = dict(
            n_users=3, n_items=3, rank=2, block_len=4,
            checkpoint_dir=str(tmp_path), checkpoint_every=2,
        )
        train_als(ctx8, rows, cols, vals, iterations=4, **kwargs)
        f = train_als(
            ctx8, rows, cols, vals, iterations=2, resume=True,
            return_layout="device", **kwargs,
        )
        assert isinstance(f.user_factors, jax.Array)
        assert isinstance(f.item_factors, jax.Array)
        assert f.user_factors.shape[0] == 8  # padded to the mesh
        assert f.n_users == 3
