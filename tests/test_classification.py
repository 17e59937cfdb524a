"""End-to-end slice: app → events → train → deploy → predict with the
Naive Bayes classification template (SURVEY.md §7 stage 4), plus NB
kernel correctness against a hand-computed reference."""

import numpy as np
import pytest

import jax.numpy as jnp
from predictionio_tpu.core.engine import EngineParams
from predictionio_tpu.core.workflow import load_deployment, run_train
from predictionio_tpu.data import DataMap, Event
from predictionio_tpu.data.storage import App
from predictionio_tpu.models.classification import (
    ClassificationDataSourceParams,
    NaiveBayesParams,
    classification_engine,
)
from predictionio_tpu.ops import naive_bayes as nb
from predictionio_tpu.parallel.mesh import ComputeContext


@pytest.fixture(scope="module")
def ctx():
    return ComputeContext.create(batch="clf-test")


def _seed(storage, n=60):
    """Two well-separated classes over 3 attributes."""
    apps = storage.get_meta_data_apps()
    app_id = apps.insert(App(id=0, name="clfapp"))
    events = storage.get_events()
    events.init(app_id)
    rng = np.random.default_rng(0)
    for i in range(n):
        label = i % 2
        base = np.array([8.0, 1.0, 1.0]) if label == 0 else np.array(
            [1.0, 1.0, 8.0]
        )
        feats = np.clip(base + rng.poisson(1.0, 3), 0, None)
        events.insert(
            Event(
                event="$set",
                entity_type="user",
                entity_id=f"u{i}",
                properties=DataMap(
                    {
                        "attr0": float(feats[0]),
                        "attr1": float(feats[1]),
                        "attr2": float(feats[2]),
                        "plan": str(label),
                    }
                ),
            ),
            app_id,
        )
    return app_id


def _params(eval_k=0):
    return EngineParams(
        data_source=(
            "",
            ClassificationDataSourceParams(
                app_name="clfapp", eval_k=eval_k
            ),
        ),
        algorithms=[("naive", NaiveBayesParams(lambda_=1.0))],
    )


class TestKernel:
    def test_multinomial_nb_matches_hand_computation(self):
        x = jnp.asarray(
            [[2.0, 1.0], [3.0, 0.0], [0.0, 4.0]], dtype=jnp.float32
        )
        y = jnp.asarray([0, 0, 1])
        model = nb.fit_multinomial(x, y, n_classes=2, alpha=1.0)
        # class 0: counts [5, 1]; theta00 = log(6/8), theta01 = log(2/8)
        np.testing.assert_allclose(
            np.asarray(model.theta[0]),
            np.log(np.array([6.0, 2.0]) / 8.0),
            rtol=1e-5,
        )
        # priors: log((2+1)/(3+2)), log((1+1)/(3+2))
        np.testing.assert_allclose(
            np.asarray(model.pi),
            np.log(np.array([3.0, 2.0]) / 5.0),
            rtol=1e-5,
        )

    def test_padding_mask_exactness(self):
        x = np.asarray([[2.0, 1.0], [3.0, 0.0], [0.0, 4.0]], np.float32)
        y = np.asarray([0, 0, 1])
        ref = nb.fit_multinomial(jnp.asarray(x), jnp.asarray(y), 2)
        x_pad = np.vstack([x, np.full((5, 2), 7.0, np.float32)])
        y_pad = np.concatenate([y, np.zeros(5, np.int64)])
        mask = np.concatenate([np.ones(3), np.zeros(5)]).astype(np.float32)
        padded = nb.fit_multinomial(
            jnp.asarray(x_pad), jnp.asarray(y_pad), 2,
            mask=jnp.asarray(mask),
        )
        np.testing.assert_allclose(
            np.asarray(ref.theta), np.asarray(padded.theta), rtol=1e-6
        )
        np.testing.assert_allclose(
            np.asarray(ref.pi), np.asarray(padded.pi), rtol=1e-6
        )

    @pytest.mark.parametrize("n_classes", [3, 128, 130])
    def test_sparse_fit_equals_dense_fit(self, n_classes):
        """The fit from (doc, feature, count) pairs is the dense fit,
        transposed, with classes padded to a lane multiple: theta 0 and
        pi -inf past the real ones. Pairs of count 0 change nothing."""
        rng = np.random.default_rng(n_classes)
        n, d = 40, 64
        x = rng.poisson(0.3, (n, d)).astype(np.float32)
        y = np.arange(n) % n_classes
        dense = nb.fit_multinomial(jnp.asarray(x), jnp.asarray(y), n_classes)
        doc, feature = np.nonzero(x)
        count = x[doc, feature].astype(np.int32)
        pad = np.zeros(7, np.int32)
        pi, theta_t = nb.fit_multinomial_sparse(
            np.concatenate([doc, pad]), np.concatenate([feature, pad + 3]),
            np.concatenate([count, pad]), y, n_features=d, n_classes=n_classes,
        )
        padded = nb.padded_classes(n_classes)
        assert padded % nb.LANES == 0 and theta_t.shape == (d, padded)
        np.testing.assert_allclose(
            np.asarray(theta_t)[:, :n_classes], np.asarray(dense.theta).T,
            rtol=1e-6,
        )
        np.testing.assert_allclose(
            np.asarray(pi)[:n_classes], np.asarray(dense.pi), rtol=1e-6
        )
        assert np.all(np.asarray(theta_t)[:, n_classes:] == 0.0)
        assert np.all(np.asarray(pi)[n_classes:] == -np.inf)

    def test_categorical_nb(self):
        codes = np.asarray([[0, 1], [0, 0], [1, 1]])
        onehot = nb.encode_categorical(codes, [2, 2])
        assert onehot.shape == (3, 4)
        model = nb.fit_categorical(
            jnp.asarray(onehot), jnp.asarray([0, 0, 1]), 2, (2, 2)
        )
        scores = nb.categorical_log_scores(model, jnp.asarray(onehot))
        assert scores.shape == (3, 2)
        assert int(jnp.argmax(scores[2])) == 1


class TestEndToEnd:
    def test_train_deploy_predict(self, ctx, memory_storage):
        _seed(memory_storage)
        engine = classification_engine()
        iid = run_train(
            engine,
            _params(),
            engine_id="clf",
            ctx=ctx,
            storage=memory_storage,
        )
        assert (
            memory_storage.get_meta_data_engine_instances()
            .get(iid)
            .status
            == "COMPLETED"
        )
        _, algorithms, models, serving = load_deployment(
            engine,
            _params(),
            engine_id="clf",
            ctx=ctx,
            storage=memory_storage,
        )
        q = serving.supplement({"features": [9.0, 1.0, 0.0]})
        preds = [
            a.predict(m, q) for a, m in zip(algorithms, models)
        ]
        result = serving.serve(q, preds)
        assert result["label"] == "0"
        assert set(result["scores"]) == {"0", "1"}
        q2 = {"features": [0.0, 1.0, 9.0]}
        assert algorithms[0].predict(models[0], q2)["label"] == "1"

    def test_eval_kfold_accuracy(self, ctx, memory_storage):
        _seed(memory_storage)
        engine = classification_engine()
        results = engine.eval(ctx, _params(eval_k=3))
        assert len(results) == 3
        correct = total = 0
        for _info, qpa in results:
            for _q, p, a in qpa:
                correct += p["label"] == a
                total += 1
        assert total == 60
        assert correct / total > 0.9  # separable data

    def test_empty_training_data_fails_sanity(self, ctx, memory_storage):
        memory_storage.get_meta_data_apps().insert(App(id=0, name="clfapp"))
        memory_storage.get_events().init(1)
        engine = classification_engine()
        with pytest.raises(ValueError, match="empty"):
            run_train(
                engine,
                _params(),
                engine_id="clf",
                ctx=ctx,
                storage=memory_storage,
            )
