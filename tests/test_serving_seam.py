"""The one seam every algorithm serves through: ``EngineServer`` wires
``Algorithm.batch_predict_launch`` / ``batch_predict_collect`` into the
micro-batcher for all of them, and the defaults of the pair carry an
algorithm that only has ``batch_predict``."""

import jax
import jax.numpy as jnp
import pytest

import test_classification as clf
import test_complementarypurchase as cp
import test_experimental_templates as exp
import test_leadscoring as lead
import test_recommendation as rec
import test_templates as tpl
import test_textclassification as text
from fake_engine import FakeAlgorithm
from predictionio_tpu.core import EngineParams
from predictionio_tpu.core.registry import engine_registry
from predictionio_tpu.core.workflow import load_deployment, run_train
from predictionio_tpu.models.complementarypurchase import (
    CPAlgoParams,
    CPDataSourceParams,
)
from predictionio_tpu.models.helloworld import HelloDataSourceParams
from predictionio_tpu.models.leadscoring import (
    LeadDataSourceParams,
    LeadScoringParams,
)
from predictionio_tpu.models.regression import RegressionAlgorithmParams
from predictionio_tpu.models.textclassification import (
    TextDataSourceParams,
    TextNBParams,
    TextPreparatorParams,
)
from predictionio_tpu.obs import MetricRegistry
from predictionio_tpu.parallel.mesh import ComputeContext
from predictionio_tpu.serving.engine_server import EngineServer


@pytest.fixture(scope="module")
def ctx():
    return ComputeContext.create(batch="seam-test")


#: template -> (seed its test file's small data, its params there, queries)
TEMPLATES = {
    "recommendation": (
        rec._seed, rec._params,
        [{"user": "u0", "num": 4}, {"user": "u1", "num": 3}],
    ),
    "classification": (
        clf._seed, clf._params,
        [{"features": [9.0, 1.0, 0.0]}, {"features": [0.0, 1.0, 9.0]}],
    ),
    "textclassification": (
        text._seed,
        lambda: EngineParams(
            data_source=("", TextDataSourceParams(app_name="TextApp")),
            preparator=("", TextPreparatorParams(n_features=512)),
            algorithms=[("nb", TextNBParams())],
        ),
        [{"text": "win free money now"}, {"text": "meeting at noon"}],
    ),
    "leadscoring": (
        lead._seed,
        lambda: EngineParams(
            data_source=("", LeadDataSourceParams(app_name="LeadApp")),
            preparator=("", None),
            algorithms=[("logreg", LeadScoringParams())],
        ),
        [{"features": [8.0, 24.0, 40.0]}, {"features": [2.0, 6.0, 10.0]}],
    ),
    "complementarypurchase": (
        cp._seed,
        lambda: EngineParams(
            data_source=("", CPDataSourceParams(app_name="CPApp")),
            preparator=("", None),
            algorithms=[("cooccurrence", CPAlgoParams())],
        ),
        [{"items": ["bread"], "num": 2}, {"items": ["beer"], "num": 2}],
    ),
    "helloworld": (
        exp.TestHelloWorld()._seed,
        lambda: EngineParams(
            data_source=("", HelloDataSourceParams(app_name="helloapp")),
            algorithms=[("hello", None)],
        ),
        [{"day": "Mon"}, {"day": "Sat"}],
    ),
    "regression": (
        lambda storage: exp.TestRegression()._seed(storage, n=60),
        lambda: exp.TestRegression()._params(
            [("SGD", RegressionAlgorithmParams())]
        ),
        [{"features": [0.2, -0.3, 0.8]}, {"features": [0.5, 0.5, 0.5]}],
    ),
    "similarproduct": (
        lambda storage: tpl._seed(storage, "simapp"),
        tpl.TestSimilarProduct()._params,
        [{"items": ["i0"], "num": 5}, {"items": ["i1"], "num": 3}],
    ),
    "ecommerce": (
        lambda storage: tpl._seed(storage, "ecomapp"),
        tpl.TestECommerce()._params,
        [{"user": "u0", "num": 6}, {"user": "nobody", "num": 3}],
    ),
}


def test_every_registered_template_has_a_case():
    import predictionio_tpu.models  # noqa: F401 - templates self-register

    assert set(TEMPLATES) == set(engine_registry())


@pytest.mark.parametrize("name", sorted(TEMPLATES))
def test_template_serves_through_launch_and_collect(
    name, ctx, memory_storage
):
    """Staged by ``EngineServer``, a batch through the batcher is the
    template's own ``batch_predict``, and it went through both hooks:
    the launch and the collect were each timed once."""
    import predictionio_tpu.models  # noqa: F401 - templates self-register

    seed, params, queries = TEMPLATES[name]
    seed(memory_storage)
    engine, params = engine_registry()[name](), params()
    engine_id = f"seam-{name}"
    run_train(
        engine, params, engine_id=engine_id, ctx=ctx,
        storage=memory_storage,
    )
    _, algos, models, _ = load_deployment(
        engine, params, engine_id=engine_id, ctx=ctx,
        storage=memory_storage,
    )
    expected = algos[0].batch_predict(models[0], queries)
    registry = MetricRegistry()
    es = EngineServer(
        engine, params, engine_id=engine_id, storage=memory_storage,
        ctx=ctx, warmup=False, registry=registry, max_wait_ms=200.0,
        adaptive_wait=False,
    )
    try:
        futures = [es._batchers[0].submit(q) for q in queries]
        assert [f.result(30) for f in futures] == expected
    finally:
        es.close()
    data = registry.to_dict()
    for family in (
        "pio_device_enqueue_seconds", "pio_device_sync_seconds",
        "pio_batches_total",
    ):
        [sample] = data[family]["samples"]
        assert sample.get("count", sample.get("value")) == 1, family


class _AsyncAlgorithm(FakeAlgorithm):
    """Only ``batch_predict``, and its predictions are device arrays
    that JAX has dispatched and not waited for."""

    def predict(self, model, query):
        return self.batch_predict(model, [query])[0]

    def batch_predict(self, model, queries):
        x = jnp.ones((1500, 1500))
        for _ in range(6):
            x = (x @ x) / 1500.0
        return [{"scores": x} for _ in queries]


def test_default_collect_blocks_on_device_outputs():
    """The batcher stops its sync clock when collect returns: the
    default must have waited for the device by then."""
    algo = _AsyncAlgorithm.__new__(_AsyncAlgorithm)
    queries = [{}, {}]
    jax.block_until_ready(algo.batch_predict(None, queries))  # compiled
    handle = algo.batch_predict_launch(None, queries)
    assert handle is queries  # nothing launched, the queries handed on
    out = algo.batch_predict_collect(None, handle, queries)
    assert len(out) == 2 and all(p["scores"].is_ready() for p in out)


def test_a_two_algorithm_engine_stages_two_batchers_a_tenant_in_a_pool(
    ctx, memory_storage
):
    """The similar-product engine of ``examples/similarproduct`` (two ALS
    algorithms) as a pool's tenant: two batchers, each wired to the
    template's own launch and collect, the tenant charged the device
    bytes of both models, and a post through the HTTP route crosses both
    and is combined by the Serving."""
    import json
    import urllib.request

    import predictionio_tpu.models  # noqa: F401 - templates self-register
    from predictionio_tpu.core.controller import Algorithm
    from predictionio_tpu.models.similarproduct import SimilarALSAlgorithm
    from predictionio_tpu.ops import quantize

    assert (
        SimilarALSAlgorithm.batch_predict_launch
        is not Algorithm.batch_predict_launch
    )
    tpl._seed(memory_storage, "simapp")
    engine = engine_registry()["similarproduct"]()
    params = tpl.TestSimilarProduct()._params(multi=True)
    run_train(
        engine, params, engine_id="seam-pool", ctx=ctx,
        storage=memory_storage, engine_variant="shop-a",
    )
    _, algos, models, serving = load_deployment(
        engine, params, engine_id="seam-pool", ctx=ctx,
        storage=memory_storage, engine_variant="shop-a",
    )
    queries = [{"items": ["i0"], "num": 5}, {"items": ["i1", "i3"], "num": 3}]
    expected = [
        serving.serve(q, [a.predict(m, q) for a, m in zip(algos, models)])
        for q in queries
    ]
    registry = MetricRegistry()
    es = EngineServer(
        engine, params, engine_id="seam-pool", storage=memory_storage,
        ctx=ctx, registry=registry, tenants={"a": "shop-a"},
    )
    http = es.serve(host="127.0.0.1", port=0)
    http.start()
    try:
        request = urllib.request.Request(
            f"http://127.0.0.1:{http.port}/batch/queries.json?accessKey=a",
            data=json.dumps(queries).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(request, timeout=60) as reply:
            slots = json.loads(reply.read())
        assert [s["status"] for s in slots] == [200, 200]
        assert [s["prediction"] for s in slots] == expected
        with es._pool.pin("a", es._tenant_loader("a")) as staged:
            assert [b.name for b in staged.batchers] == [
                "seam-pool/a/algo0", "seam-pool/a/algo1"
            ]
            assert staged.warmed
            assert staged.nbytes == sum(
                quantize.model_resident_bytes(m) for m in models
            ) > 2 * models[0].item_factors.nbytes
    finally:
        http.shutdown()
        es.close()
    data = registry.to_dict()
    batchers = {
        s["labels"]["batcher"]: s["value"]
        for s in data["pio_batches_total"]["samples"]
    }
    assert batchers == {"seam-pool/a/algo0": 1, "seam-pool/a/algo1": 1}
    stages = {
        s["labels"]["stage"]: s["count"]
        for s in data["pio_stage_seconds"]["samples"]
    }
    # one launch and one collect an algorithm; the warm-up's are the
    # loader thread's, on the process's registry
    for stage in ("predict.prep", "predict.enqueue", "predict.device_get",
                  "predict.materialize"):
        assert stages[stage] == 2, stage
    assert stages["engine.serve"] == 1
