"""A predict launch is one call into the runtime: the jitted program takes
the batch's host operands itself (`ops/similarity.gather_top_k_dot`,
`rules_top_k`, `ops/quantize.gather_top_k_dot_quantized`). Counted here by
doubles of the test's own beside `pio_device_launch_calls_total`, held
equal to the two-program composition it replaced, and compiled once."""

from __future__ import annotations

import contextvars
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.models.recommendation import (
    ALSAlgorithm,
    ALSParams,
    ALSRecModel,
)
from predictionio_tpu.obs import tracing
from predictionio_tpu.obs.device import CompileWatch
from predictionio_tpu.obs.registry import MetricRegistry
from predictionio_tpu.ops import cooccurrence as CO
from predictionio_tpu.ops import naive_bayes as NB
from predictionio_tpu.ops import quantize as Q
from predictionio_tpu.ops import similarity as S
from predictionio_tpu.utils.bimap import BiMap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_USERS, N_ITEMS, RANK, NUM = 90, 1024, 8, 8
#: "rules_int8": the rules step over a pool's quantized tables, which it
#: widens inside its one program
PATHS = ("f32", "int8", "bf16", "rules", "rules_int8")
_JITTED = type(S._top_k_dot_xla)


def _tables(path: str, seed: int = 0, n_items: int = N_ITEMS):
    """Staged user and item tables of the path's kind."""
    rng = np.random.default_rng(seed)
    users = rng.normal(size=(N_USERS, RANK)).astype(np.float32)
    items = rng.normal(size=(n_items, RANK)).astype(np.float32)
    kind = path.removeprefix("rules_")
    if kind in Q.MODES:
        return tuple(
            Q.stage_quantized(Q.quantize_factors(x, kind)) for x in (users, items)
        )
    return S.stage_factors(users), S.stage_factors(items)


def _catalog_rules(seed: int = 0) -> S.CatalogRules:
    rng = np.random.default_rng([seed, 7])
    return S.CatalogRules(
        jnp.asarray(rng.integers(-1, 5, (1, N_ITEMS)).astype(np.int32)),
        jnp.asarray(rng.random(N_ITEMS) < 0.05),
        jnp.asarray(np.ones(N_ITEMS, np.float32)),
        jnp.asarray(rng.permutation(N_ITEMS).astype(np.float32)),
    )


def _query_rules(batch: int, seed: int) -> S.QueryRules:
    """One batch's rules as `predict.prep` leaves them: two host arrays."""
    rng = np.random.default_rng([seed, 11])
    rules = S.QueryRules.blank(batch, 1)
    rules.idx[:] = rng.integers(0, N_USERS, batch)
    rules.mode[:] = rng.integers(0, 3, batch)
    rules.recent[:, :3] = rng.integers(0, N_ITEMS, (batch, 3))
    return dataclasses.replace(rules, lists=S.pack_lists([
        rng.choice(N_ITEMS, 20, replace=False).astype(np.int32)
        for _ in range(batch)
    ]))


def _launch(path: str):
    """``launch(seed)``: one launch of the path over a fresh numpy ``idx``
    (and fresh rules) of one shape, as `predict.prep` hands them over."""
    users, items = _tables(path)
    rules = path.startswith("rules")
    catalog = _catalog_rules() if rules else None

    def launch(seed: int, batch: int = 4):
        if rules:
            return S.rules_top_k(
                users, items, NUM, catalog, _query_rules(batch, seed)
            )
        idx = np.random.default_rng(seed).integers(0, N_USERS, batch).astype(np.int32)
        return S.gather_top_k_dot(users, idx, items, NUM)

    return launch


def _bound(registry, fn, *args):
    """``fn(*args)`` on a context bound to ``registry``, as a batcher's
    thread is; the binding ends with the call."""
    def run():
        tracing.StageSink(registry).bind()
        return fn(*args)

    return contextvars.copy_context().run(run)


def _launch_calls(registry) -> float:
    return registry.counter("pio_device_launch_calls_total").value


# -- (a) one hand-over a launch, counted twice --------------------------------


@pytest.mark.parametrize("path", PATHS)
def test_a_launch_is_one_hand_over_to_the_runtime(path, monkeypatch):
    launch = _launch(path)
    jax.block_until_ready(launch(0))  # compiled: the doubles see no tracing
    handed_over = []

    def program(name, real):
        def double(*args, **kwargs):
            handed_over.append(name)
            return real(*args, **kwargs)
        return double

    def upload(name, real):
        def double(x, *args, **kwargs):
            if not isinstance(x, jax.Array):  # host data: a transfer of its own
                handed_over.append(name)
            return real(x, *args, **kwargs)
        return double

    for module in (S, Q):
        for name, value in list(vars(module).items()):
            if isinstance(value, _JITTED):
                monkeypatch.setattr(module, name, program(name, value))
    monkeypatch.setattr(jnp, "asarray", upload("jnp.asarray", jnp.asarray))
    monkeypatch.setattr(jnp, "array", upload("jnp.array", jnp.array))
    monkeypatch.setattr(jax, "device_put", upload("jax.device_put", jax.device_put))

    registry = MetricRegistry()
    for batches in (1, 2, 3):
        jax.block_until_ready(_bound(registry, launch, batches))
        assert len(handed_over) == batches, handed_over
        assert _launch_calls(registry) == batches
    wanted = {
        "f32": "_gather_top_k_dot_xla", "rules": "_rules_top_k",
        "rules_int8": "_rules_top_k",
    }.get(path, "_top_k_dot_quant_xla")
    assert set(handed_over) == {wanted}


def test_the_benchmark_s_counter_share_reads_the_counter_over_the_batches():
    """What a `benchmark` PR needs to report `*.launch_calls_share` is a
    reader's file of these three keys (PERF.md section 7): the accepted
    `counter_share` reads 100 where every batch was one call, nothing where
    no batch was dispatched, and 0 from a program with no such counter."""
    import sys

    sys.path[:0] = [os.path.join(ROOT, "benchmarks", "chip")]
    import layer_metrics

    spec = {
        "reader": "counter_share", "over": "pio_batches_total",
        "families": ["pio_device_launch_calls_total"],
    }
    registry = MetricRegistry()
    batches = registry.counter("pio_batches_total", "", ("batcher",))
    launch = _launch("int8")
    before = registry.to_dict()

    def read(after, start=before):
        run = {"before": start, "after": after, "traffic": {}}
        return layer_metrics.counter_share(run, spec)

    assert read(registry.to_dict()) is None
    for batcher in ("a", "b", "a"):
        _bound(registry, launch, 1)
        batches.labels(batcher).inc()
    assert read(registry.to_dict()) == pytest.approx(100.0)
    older = {"pio_batches_total": registry.to_dict()["pio_batches_total"]}
    assert read(older, start={}) == 0.0


def test_a_launch_outside_a_server_counts_on_the_process_registry():
    from predictionio_tpu.obs import get_registry

    before = _launch_calls(get_registry())
    contextvars.copy_context().run(_launch("f32"), 0)
    assert _launch_calls(get_registry()) == before + 1


def test_query_rules_are_two_host_operands_filled_in_place():
    """Each host operand of a call costs the launching thread as much as a
    call of its own did (PERF.md section 6, PR 29), so a batch's rules
    travel as two arrays, and prep writes through views: no copy."""
    rules = S.QueryRules.blank(4, 2)
    views = (rules.idx, rules.mode, rules.allow, rules.recent, rules.categories)
    assert all(np.shares_memory(v, rules.per_query) for v in views)
    assert sum(v.size for v in views) == rules.per_query.size
    assert rules.mode.tolist() == [S.POPULAR] * 4
    assert (rules.recent == -1).all() and (rules.categories == S.NO_CATEGORY).all()
    rules.mode[1], rules.allow[2] = S.SIMILAR, True
    rules = dataclasses.replace(
        rules, lists=S.pack_lists([np.array([7, 3], np.int32)] * 4)
    )
    leaves = jax.tree_util.tree_leaves(rules)
    assert [type(x) for x in leaves] == [np.ndarray, np.ndarray]
    assert [x.dtype for x in leaves] == [np.int32, np.int32]
    assert rules.per_query[:, 1:3].tolist() == [[2, 0], [1, 0], [2, 1], [2, 0]]
    assert np.shares_memory(rules.list_cols, rules.lists)
    assert rules.list_cols[:9].tolist() == [3] * 4 + [7] * 4 + [S.NO_ITEM]


# -- (b) the one program equals the two it replaced ----------------------------


@pytest.mark.parametrize("batch", [1, 2, 64])
@pytest.mark.parametrize("masked", [False, True], ids=["unmasked", "masked"])
@pytest.mark.parametrize("users_kind", ["quantized", "float"])
@pytest.mark.parametrize("mode", Q.MODES)
def test_one_quantized_program_equals_gather_then_top_k(mode, users_kind, masked, batch):
    users, items = _tables(mode, seed=3)
    if users_kind == "float":
        users = Q.dequantize(users)
    rng = np.random.default_rng([batch, masked])
    # as `predict.prep` makes it: an unknown user (-1) clipped to row 0,
    # the batch padded to its bucket with row 0
    asked = rng.integers(-1, N_USERS, batch)
    asked[0] = -1
    idx = np.clip(asked, 0, None).astype(np.int32)
    mask = jnp.asarray(rng.random(N_ITEMS) < 0.3) if masked else None
    got = S.gather_top_k_dot(users, idx, items, NUM, mask)
    want = Q.top_k_dot_quantized(Q.gather_rows(users, idx), items, NUM, mask)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == (batch, NUM)
        assert np.array_equal(np.asarray(g), np.asarray(w))


# -- (c) one compilation a shape, the warm-up's included ----------------------


def _algorithm_and_model(path: str, n_items: int):
    users, items = _tables(path, seed=5, n_items=n_items)
    return ALSAlgorithm(ALSParams(rank=RANK)), ALSRecModel(
        user_factors=users, item_factors=items,
        user_map=BiMap([f"u{i}" for i in range(N_USERS)]),
        item_map=BiMap([f"i{i}" for i in range(n_items)]),
    )


def _compiles(registry) -> float:
    family = registry.to_dict().get("pio_xla_compiles_total") or {}
    return sum(s["value"] for s in family.get("samples", ()))


@pytest.mark.parametrize("path", PATHS)
def test_fresh_operands_of_one_shape_compile_once(path):
    launch = _launch(path)
    registry = MetricRegistry()
    with CompileWatch(registry):
        jax.block_until_ready(launch(10, batch=16))
        first = _compiles(registry)
        jax.block_until_ready(launch(11, batch=16))
        jax.block_until_ready(launch(12, batch=16))
        assert _compiles(registry) == first


@pytest.mark.parametrize("path", ("f32", "int8", "bf16"))
def test_warm_up_compiles_what_serving_calls(path):
    """`EngineServer._precompile`'s call (a neutral query a bucket through
    `batch_predict`) leaves nothing for the served batch to compile or
    trace: the jitted program's own cache holds one signature for both."""
    # a catalog size no other test compiles for: the warm-up compiles here
    algorithm, model = _algorithm_and_model(path, n_items=777)
    program = S._gather_top_k_dot_xla if path == "f32" else Q._top_k_dot_quant_xla
    registry = MetricRegistry()
    with CompileWatch(registry):
        warmup_query = getattr(algorithm, "warmup_query", lambda: {})()
        for bucket in (1, 2, 4):
            algorithm.batch_predict(model, [warmup_query] * bucket)
        compiled, signatures = _compiles(registry), program._cache_size()
        assert compiled >= 3
        for n in (1, 2, 3, 4):
            queries = [{"user": f"u{7 * n + i}"} for i in range(n)]
            answers = algorithm.batch_predict(model, queries)
            assert all(len(a["itemScores"]) == 10 for a in answers)
        assert _compiles(registry) == compiled
        assert program._cache_size() == signatures


# -- (d) the names the benchmark's trace reader looks for ---------------------


def _configuration_files():
    folder = os.path.join(ROOT, "benchmarks", "chip", "configs")
    return sorted(f for f in os.listdir(folder) if f.endswith(".json"))


@pytest.mark.parametrize("config", _configuration_files())
def test_jit_names_of_a_configuration_are_jitted_programs(config):
    with open(os.path.join(ROOT, "benchmarks", "chip", "configs", config)) as f:
        names = json.load(f)["jit_names"]
    assert names
    for name in names:
        found = [getattr(m, name) for m in (S, Q, NB, CO) if hasattr(m, name)]
        assert len(found) == 1, (
            f"{name} is in one of ops/similarity.py, ops/quantize.py, "
            "ops/naive_bayes.py, ops/cooccurrence.py"
        )
        assert isinstance(found[0], _JITTED)
        assert found[0].__name__ == name  # the trace's module is jit_<name>
