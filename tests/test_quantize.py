"""Quantized factor tables: round-trip accuracy, the dequantizing
Pallas kernel (interpret mode on CPU), recall@k agreement with the f32
path, model-level helpers, and the ``ops/similarity`` dispatcher
threshold the 512 MB crossover is built on."""

import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from predictionio_tpu.ops import quantize, similarity
from predictionio_tpu.ops.pallas_topk import fused_top_k_dot
from predictionio_tpu.ops.similarity import (
    _PALLAS_MIN_INTERMEDIATE_BYTES,
    _top_k_dot_xla,
    _use_pallas,
)


def _tables(n=400, k=24, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, k)).astype(np.float32)


class TestQuantizeFactors:
    def test_int8_round_trip_error_bounded(self):
        x = _tables()
        qf = quantize.quantize_factors(x, "int8")
        assert qf.data.dtype == jnp.int8
        assert qf.scale.shape == (x.shape[0],)
        err = np.abs(np.asarray(quantize.dequantize(qf)) - x)
        # per-row error ≤ half a quant step of that row's absmax scale
        step = np.abs(x).max(axis=1, keepdims=True) / 127.0
        assert (err <= 0.5 * step + 1e-6).all()

    def test_bf16_is_plain_cast(self):
        x = _tables()
        qf = quantize.quantize_factors(x, "bf16")
        assert qf.data.dtype == jnp.bfloat16
        assert qf.scale is None
        np.testing.assert_allclose(
            np.asarray(quantize.dequantize(qf)), x, rtol=1e-2
        )

    def test_zero_rows_stay_zero(self):
        x = _tables()
        x[7] = 0.0
        qf = quantize.quantize_factors(x, "int8")
        assert float(jnp.abs(quantize.dequantize(qf)[7]).max()) == 0.0
        assert np.isfinite(np.asarray(qf.scale)).all()

    def test_nbytes_quarter_of_f32(self):
        x = _tables(512, 128)
        qf = quantize.quantize_factors(x, "int8")
        # int8 data + f32 scale: ~0.26× of the f32 table
        assert qf.nbytes < x.nbytes * 0.3

    def test_unknown_mode_rejected(self):
        with pytest.raises(ValueError):
            quantize.quantize_factors(_tables(), "fp4")

    def test_gather_rows_dequantizes(self):
        x = _tables()
        qf = quantize.quantize_factors(x, "int8")
        rows = quantize.gather_rows(qf, np.array([3, 11], np.int32))
        step = np.abs(x[[3, 11]]).max(axis=1, keepdims=True) / 127.0
        assert (
            np.abs(np.asarray(rows) - x[[3, 11]]) <= 0.5 * step + 1e-6
        ).all()


class TestQuantizedTopK:
    def test_xla_path_recall(self):
        x = _tables(600, 32)
        q = _tables(16, 32, seed=1)
        qf = quantize.quantize_factors(x, "int8")
        _, i_ref = _top_k_dot_xla(jnp.asarray(q), jnp.asarray(x), 10)
        _, i_q = quantize.top_k_dot_quantized(jnp.asarray(q), qf, 10)
        assert quantize.recall_at_k(i_ref, i_q) >= 0.9

    def test_pallas_interpret_matches_quant_xla(self):
        # same quantized table through both paths: identical ranking
        x = _tables(700, 16, seed=2)
        q = jnp.asarray(_tables(6, 16, seed=3))
        qf = quantize.quantize_factors(x, "int8")
        ps, pi = fused_top_k_dot(
            q, qf.data, 9, block=256, interpret=True, scale=qf.scale
        )
        xs, xi = quantize._top_k_dot_quant_xla(
            q, qf.data, qf.scale, 9
        )
        np.testing.assert_allclose(
            np.asarray(ps), np.asarray(xs), rtol=1e-5, atol=1e-5
        )
        assert (np.asarray(pi) == np.asarray(xi)).mean() > 0.95

    def test_pallas_interpret_bf16_no_scale(self):
        x = _tables(512, 16, seed=4)
        q = jnp.asarray(_tables(4, 16, seed=5))
        qf = quantize.quantize_factors(x, "bf16")
        ps, pi = fused_top_k_dot(
            q, qf.data, 7, block=256, interpret=True
        )
        _, i_ref = _top_k_dot_xla(q, jnp.asarray(x), 7)
        assert quantize.recall_at_k(i_ref, pi) >= 0.9

    def test_mask_and_scale_compose(self):
        x = _tables(300, 8, seed=6)
        q = jnp.asarray(_tables(5, 8, seed=7))
        qf = quantize.quantize_factors(x, "int8")
        mask = np.zeros((5, 300), bool)
        mask[:, :250] = True
        _, pi = fused_top_k_dot(
            q, qf.data, 5, mask=jnp.asarray(mask), block=128,
            interpret=True, scale=qf.scale,
        )
        assert (np.asarray(pi) >= 250).all()

    def test_kernel_side_routes_quantized_through_interpreter(
        self, monkeypatch
    ):
        monkeypatch.setattr(similarity, "_use_pallas", lambda *a, **k: True)
        x = _tables(300, 8, seed=8)
        qf = quantize.quantize_factors(x, "int8")
        q = jnp.asarray(_tables(3, 8, seed=9))
        _, i_ref = _top_k_dot_xla(q, jnp.asarray(x), 5)
        _, i_q = similarity.top_k_dot(q, qf, 5)
        assert quantize.recall_at_k(i_ref, i_q) >= 0.8

    def test_recall_at_k_helper(self):
        a = np.array([[1, 2, 3], [4, 5, 6]])
        assert quantize.recall_at_k(a, a) == 1.0
        b = np.array([[1, 2, 9], [4, 5, 6]])
        assert quantize.recall_at_k(a, b) == pytest.approx(5 / 6)
        with pytest.raises(ValueError):
            quantize.recall_at_k(a, b[:1])


class TestSimilarityAcceptsQuantized:
    def test_gather_top_k_dot_both_sides_quantized(self):
        users, items = _tables(50, 16, seed=10), _tables(400, 16, 11)
        qu = quantize.quantize_factors(users, "int8")
        qi = quantize.quantize_factors(items, "int8")
        idx = np.arange(8, dtype=np.int32)
        _, i_ref = similarity.gather_top_k_dot(
            users, idx, items, 10
        )
        _, i_q = similarity.gather_top_k_dot(qu, idx, qi, 10)
        assert quantize.recall_at_k(i_ref, i_q) >= 0.85

    def test_gather_respects_item_mask(self):
        users, items = _tables(20, 8, seed=12), _tables(300, 8, 13)
        qu = quantize.quantize_factors(users, "int8")
        qi = quantize.quantize_factors(items, "int8")
        mask = np.zeros(300, bool)
        mask[:200] = True
        _, pi = similarity.gather_top_k_dot(
            qu, np.arange(4, dtype=np.int32), qi, 5,
            mask=jnp.asarray(mask),
        )
        assert (np.asarray(pi) >= 200).all()

    def test_cosine_scale_cancels(self):
        items = _tables(500, 24, seed=14)
        q = jnp.asarray(_tables(8, 24, seed=15))
        qi = quantize.quantize_factors(items, "int8")
        _, i_ref = similarity.top_k_cosine(q, jnp.asarray(items), 10)
        _, i_q = similarity.top_k_cosine(q, qi, 10)
        assert quantize.recall_at_k(i_ref, i_q) >= 0.9

    def test_similar_step_on_quantized_tables(self):
        """The rules step's SIMILAR branch (the similar-product query:
        summed cosine to a set of items) over an int8 table, widened
        inside the step, ranks as it does over the f32 table."""
        items = _tables(400, 16, seed=16)
        qi = quantize.quantize_factors(items, "int8")
        catalog = similarity.CatalogRules(
            jnp.full((1, 400), -1, jnp.int32), jnp.zeros(400, bool),
            similarity.inverse_norms(jnp.asarray(items)), None,
        )
        basket = np.array([3, 7, 12], np.int32)
        rules = similarity.QueryRules.blank(1, 1, mode=similarity.SIMILAR)
        rules.recent[0, :3] = basket
        rules = dataclasses.replace(
            rules, lists=similarity.pack_lists([basket])
        )
        _, i_ref = similarity.rules_top_k(None, items, 10, catalog, rules)
        _, i_q = similarity.rules_top_k(None, qi, 10, catalog, rules)
        assert not set(basket.tolist()) & set(np.asarray(i_q)[0].tolist())
        assert quantize.recall_at_k(i_ref, i_q) >= 0.9

    @pytest.mark.parametrize("mode", quantize.MODES)
    def test_known_row_of_the_rules_step_on_quantized_tables(self, mode):
        """The rules step's KNOWN branch (the e-commerce query) gathers
        its user's row from a quantized user table as f32 and ranks a
        quantized item table, widened inside the step, as the f32 one."""
        users, items = _tables(30, 16, seed=17), _tables(400, 16, seed=18)
        catalog = similarity.CatalogRules(
            jnp.full((1, 400), -1, jnp.int32), jnp.zeros(400, bool),
            similarity.inverse_norms(jnp.asarray(items)),
            jnp.zeros(400, jnp.float32),
        )
        rules = similarity.QueryRules.blank(2, 1, mode=similarity.KNOWN)
        rules.idx[:] = [4, 9]
        rules = dataclasses.replace(
            rules, lists=similarity.pack_lists([np.array([1], np.int32)] * 2)
        )
        s_ref, i_ref = similarity.rules_top_k(users, items, 10, catalog, rules)
        s_q, i_q = similarity.rules_top_k(
            quantize.quantize_factors(users, mode),
            quantize.quantize_factors(items, mode), 10, catalog, rules,
        )
        assert quantize.recall_at_k(i_ref, i_q) >= 0.8
        np.testing.assert_allclose(s_q[:, 0], s_ref[:, 0], rtol=0.05)


class TestModelHelpers:
    def _model(self):
        from predictionio_tpu.models.recommendation import (
            ALSRecModel,
            BiMap,
        )

        return ALSRecModel(
            user_factors=_tables(40, 16, seed=17),
            item_factors=_tables(160, 16, seed=18),
            user_map=BiMap([str(i) for i in range(40)]),
            item_map=BiMap([str(i) for i in range(160)]),
        )

    def test_quantize_model_factors(self):
        m = self._model()
        qm = quantize.quantize_model_factors(m, "int8")
        assert isinstance(qm.user_factors, quantize.QuantizedFactors)
        assert isinstance(qm.item_factors, quantize.QuantizedFactors)
        assert qm.user_map is m.user_map
        assert quantize.model_resident_bytes(
            qm
        ) < quantize.model_resident_bytes(m) / 3

    def test_idempotent_and_passthrough(self):
        m = self._model()
        qm = quantize.quantize_model_factors(m, "int8")
        again = quantize.quantize_model_factors(qm, "int8")
        assert again.item_factors is qm.item_factors
        assert quantize.quantize_model_factors(m, "") is m
        sentinel = object()
        assert quantize.quantize_model_factors(sentinel, "int8") is (
            sentinel
        )

    def test_quantized_model_serves(self):
        m = self._model()
        qm = quantize.quantize_model_factors(m, "int8")
        idx = np.arange(6, dtype=np.int32)
        _, i_ref = similarity.gather_top_k_dot(
            m.user_factors, idx, m.item_factors, 8
        )
        _, i_q = similarity.gather_top_k_dot(
            qm.user_factors, idx, qm.item_factors, 8
        )
        assert quantize.recall_at_k(i_ref, i_q) >= 0.8

    def test_pytree_registration(self):
        qf = quantize.quantize_factors(_tables(32, 8, seed=19), "int8")
        leaves, treedef = jax.tree_util.tree_flatten(qf)
        assert len(leaves) == 2
        back = jax.tree_util.tree_unflatten(treedef, leaves)
        assert back.mode == "int8"


class TestDispatcherThreshold:
    """The 512 MB [B, I] intermediate crossover that picks Pallas over
    XLA on TPU — previously documented but never CPU-tested."""

    def test_below_threshold_stays_xla(self, monkeypatch):
        monkeypatch.setattr(
            jax, "default_backend", lambda: "tpu"
        )
        b = 256
        n = _PALLAS_MIN_INTERMEDIATE_BYTES // (b * 4) - 1
        assert not _use_pallas(b, n)

    def test_at_threshold_picks_pallas_on_tpu(self, monkeypatch):
        monkeypatch.setattr(
            jax, "default_backend", lambda: "tpu"
        )
        b = 256
        n = _PALLAS_MIN_INTERMEDIATE_BYTES // (b * 4)
        assert _use_pallas(b, n)

    def test_threshold_irrelevant_off_tpu(self):
        assert jax.default_backend() == "cpu"
        assert not _use_pallas(4096, 10_000_000)

    def test_no_environment_variable_steers_the_choice(self, monkeypatch):
        """The platform and the shape choose; the variable that once
        overrode them is read by nothing."""
        monkeypatch.setenv("PIO_PALLAS_TOPK", "0")
        monkeypatch.setattr(
            jax, "default_backend", lambda: "tpu"
        )
        assert _use_pallas(4096, 10_000_000)
        monkeypatch.setenv("PIO_PALLAS_TOPK", "1")
        assert not _use_pallas(1, 1)


class TestNestedResidentBytes:
    def test_recurses_into_nested_dataclasses(self):
        """Template models wrap their arrays (NaiveBayesModel.nb,
        ALSRecModel.factors) — the pool must charge those bytes, not
        count the wrapper as 0."""
        import dataclasses

        import numpy as np

        @dataclasses.dataclass
        class Inner:
            theta: np.ndarray

        @dataclasses.dataclass
        class Outer:
            nb: Inner
            label: int

        arr = np.zeros((8, 4), np.float32)
        assert quantize.model_resident_bytes(
            Outer(nb=Inner(theta=arr), label=3)
        ) == arr.nbytes

    def test_recursion_is_depth_bounded(self):
        import dataclasses

        import numpy as np

        @dataclasses.dataclass
        class Node:
            child: object
            leaf: np.ndarray

        arr = np.zeros(4, np.float32)
        deep = Node(child=None, leaf=arr)
        for _ in range(10):
            deep = Node(child=deep, leaf=arr)
        # levels past the bound are simply not charged — no blowup
        counted = quantize.model_resident_bytes(deep)
        assert arr.nbytes <= counted <= 11 * arr.nbytes
