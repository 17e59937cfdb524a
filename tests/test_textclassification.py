"""Text-classification template (gallery parity: labeled documents →
hashed bag-of-words → multinomial NB)."""

from __future__ import annotations

import numpy as np
import pytest

from predictionio_tpu.data import DataMap, Event
from predictionio_tpu.data.storage import App
from predictionio_tpu.models.textclassification import (
    TextDataSourceParams,
    TextNBAlgorithm,
    TextNBParams,
    TextPreparator,
    TextPreparatorParams,
    TextTrainingData,
    hash_counts,
    textclassification_engine,
    tokenize,
)
from predictionio_tpu.parallel.mesh import ComputeContext

SPAM = [
    "win a free prize now claim your money",
    "free money click now to win big prize",
    "claim your free prize win money now",
    "exclusive offer win money free claim",
]
HAM = [
    "meeting moved to tuesday please review the agenda",
    "please review the quarterly report before the meeting",
    "agenda attached for the tuesday planning meeting",
    "notes from the review meeting attached",
]


@pytest.fixture(scope="module")
def ctx():
    return ComputeContext.create(batch="text-test")


def _seed(storage, app_name="TextApp"):
    app_id = storage.get_meta_data_apps().insert(App(id=0, name=app_name))
    events = storage.get_events()
    events.init(app_id)
    batch = []
    for i, text in enumerate(SPAM):
        batch.append(Event(
            event="$set", entity_type="document", entity_id=f"s{i}",
            properties=DataMap({"text": text, "label": "spam"}),
        ))
    for i, text in enumerate(HAM):
        batch.append(Event(
            event="$set", entity_type="document", entity_id=f"h{i}",
            properties=DataMap({"text": text, "label": "ham"}),
        ))
    events.insert_batch(batch, app_id)
    return app_id


def _train(ctx, storage, n_features=512):
    from predictionio_tpu.models.textclassification import TextDataSource

    ds = TextDataSource(TextDataSourceParams(app_name="TextApp"))
    td = ds.read_training(ctx)
    td.sanity_check()
    prepared = TextPreparator(
        TextPreparatorParams(n_features=n_features)
    ).prepare(ctx, td)
    return TextNBAlgorithm(TextNBParams()).train(ctx, prepared)


class TestHashing:
    def test_tokenize(self):
        assert tokenize("Hello, World! it's 42") == [
            "hello", "world", "it's", "42"
        ]

    def test_hashing_is_process_stable(self):
        # FNV-1a, not builtin hash(): same buckets in every process
        v = hash_counts(["alpha", "beta", "alpha"], 64)
        assert v.sum() == 3.0
        assert (v == hash_counts(["alpha", "beta", "alpha"], 64)).all()
        assert v.max() >= 2.0  # the repeated token stacks

    def test_fixed_width_regardless_of_vocabulary(self):
        a = hash_counts(tokenize("one two three"), 128)
        b = hash_counts(tokenize("totally different words here now"), 128)
        assert a.shape == b.shape == (128,)


class TestTraining:
    def test_classifies_planted_corpus(self, ctx, memory_storage):
        _seed(memory_storage)
        model = _train(ctx, memory_storage)
        algo = TextNBAlgorithm(TextNBParams())
        spam = algo.predict(
            model, {"text": "claim your free money prize"}
        )
        ham = algo.predict(
            model, {"text": "please review the meeting agenda"}
        )
        assert spam["label"] == "spam"
        assert ham["label"] == "ham"
        assert set(spam["scores"]) == {"spam", "ham"}
        assert spam["scores"]["spam"] > spam["scores"]["ham"]

    def test_sanity_checks(self):
        with pytest.raises(ValueError, match="no labeled documents"):
            TextTrainingData(texts=[], labels=[]).sanity_check()
        with pytest.raises(ValueError, match="two distinct labels"):
            TextTrainingData(
                texts=["a", "b"], labels=["x", "x"]
            ).sanity_check()

    def test_batch_matches_single(self, ctx, memory_storage):
        _seed(memory_storage)
        model = _train(ctx, memory_storage)
        algo = TextNBAlgorithm(TextNBParams())
        queries = [{"text": t} for t in ("free prize", "agenda review")]
        batch = algo.batch_predict(model, queries)
        singles = [algo.predict(model, q) for q in queries]
        # float32 matmul sums differ in the last ulp across batch
        # shapes (XLA reassociates); labels and scores agree to 1e-5
        for b, s in zip(batch, singles):
            assert b["label"] == s["label"]
            for lbl in b["scores"]:
                assert b["scores"][lbl] == pytest.approx(
                    s["scores"][lbl], rel=1e-5
                )

    def test_kfold_evaluation_accuracy(self, ctx, memory_storage):
        """read_eval folds feed MetricEvaluator; the planted corpus is
        separable, so held-out accuracy must be high."""
        from predictionio_tpu.core import EngineParams
        from predictionio_tpu.core.evaluation import (
            AverageMetric,
            MetricEvaluator,
        )
        from predictionio_tpu.models.textclassification import (
            textclassification_engine,
        )

        class Accuracy(AverageMetric):
            def calculate_point(self, ei, q, p, a):
                return 1.0 if p["label"] == a else 0.0

        _seed(memory_storage)
        params = EngineParams(
            data_source=(
                "", TextDataSourceParams(app_name="TextApp", eval_k=2)
            ),
            preparator=("", TextPreparatorParams(n_features=512)),
            algorithms=[("nb", TextNBParams())],
        )
        result = MetricEvaluator(Accuracy()).evaluate(
            ctx, textclassification_engine(), [params]
        )
        assert result.best_score.score >= 0.75

    def test_engine_end_to_end(self, ctx, memory_storage):
        from predictionio_tpu.core import EngineParams
        from predictionio_tpu.core.workflow import (
            load_deployment,
            run_train,
        )

        _seed(memory_storage)
        engine = textclassification_engine()
        params = EngineParams(
            data_source=("", TextDataSourceParams(app_name="TextApp")),
            preparator=("", TextPreparatorParams(n_features=512)),
            algorithms=[("nb", TextNBParams())],
        )
        run_train(
            engine, params, engine_id="text", ctx=ctx,
            storage=memory_storage,
        )
        _inst, algorithms, models, serving = load_deployment(
            engine, params, engine_id="text", ctx=ctx,
            storage=memory_storage,
        )
        query = {"text": "win free money now"}
        preds = algorithms[0].batch_predict(models[0], [query])
        assert serving.serve(query, [preds[0]])["label"] == "spam"


# -- the sparse form: hashing, scoring, answers, the parent's pickles --------

import os  # noqa: E402
import pickle  # noqa: E402
import sys  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from predictionio_tpu.models import textclassification as text_module  # noqa: E402
from predictionio_tpu.models.textclassification import (  # noqa: E402
    PAIRS_PER_QUERY,
    TextNBModel,
    hash_buckets,
    hash_pairs,
)
from predictionio_tpu.ops import naive_bayes as nb  # noqa: E402
from predictionio_tpu.utils.bimap import BiMap  # noqa: E402

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks", "chip"
))
import reference_textcls  # noqa: E402

#: texts with repeated tokens, no tokens at all, case and punctuation, and
#: one longer than a query's least share of a batch's pair slots
TEXTS = [
    "the the the red shoe",
    "",
    "!!! ---",
    "Men's LEATHER wallet, brown / 2-pack",
    "4k tv 55\" smart tv 2024 model x55q",
    " ".join(f"w{i}" for i in range(3 * PAIRS_PER_QUERY[0])),
    "a b c d e f g h i j k l m n o p q r s t u v w x y z",
]


def _random_ascii(rng, n):
    alphabet = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz0123456789'", np.uint8)
    return [
        bytes(alphabet[rng.integers(0, len(alphabet), rng.integers(1, 12))]).decode()
        for _ in range(n)
    ]


def _model(seed, n_features, n_classes=5):
    """A random model, as host arrays of the real classes (unpadded)."""
    rng = np.random.default_rng(seed)
    pi = rng.standard_normal(n_classes).astype(np.float32) - 2.0
    theta_t = (rng.standard_normal((n_features, n_classes)) - 8.0).astype(np.float32)
    labels = BiMap([f"c{c:04d}" for c in range(n_classes)])
    return TextNBModel(pi=pi, theta_t=theta_t, label_map=labels, n_features=n_features)


def _score_matrix(answers, n_classes):
    return np.array([[a["scores"][f"c{c:04d}"] for c in range(n_classes)] for a in answers])


class TestSparseHashing:
    def test_vectorised_hash_equals_hash_counts(self):
        rng = np.random.default_rng(7)
        tokens = _random_ascii(rng, 500) + ["don't", "o'", "'", "42", "a1b2"]
        for d in (64, 4096, 2**20):
            got = hash_buckets(tokens, d)
            want = [int(np.argmax(hash_counts([t], d))) for t in tokens]
            assert got.tolist() == want
        assert hash_buckets([], 64).shape == (0,)
        # tokenize yields ASCII whatever the text; a token that is not
        # ASCII is refused, not hashed as some other bytes
        assert tokenize("Café NAÏVE x") == ["caf", "na", "ve", "x"]
        with pytest.raises(UnicodeEncodeError):
            hash_buckets(["café"], 64)

    @pytest.mark.parametrize("d", [64, 4096])
    def test_pairs_are_the_nonzeros_of_the_dense_rows(self, d):
        texts = TEXTS + [" ".join(_random_ascii(np.random.default_rng(d), 30))]
        row, bucket, count, n_tokens = hash_pairs(texts, d)
        dense = np.stack([hash_counts(tokenize(t), d) for t in texts])
        want_row, want_bucket = np.nonzero(dense)
        assert row.tolist() == want_row.tolist()
        assert bucket.tolist() == want_bucket.tolist()
        assert count.tolist() == dense[want_row, want_bucket].tolist()
        assert n_tokens == dense.sum()


#: titles beside TEXTS that a tokenizer or a packer could get wrong: none,
#: a title of 40 tokens, tokens of one and of 100+ characters, repeats,
#: apostrophes and digits, and text that is not ASCII (Unicode lower-casing
#: gives "i" and a combining dot for "İ", and "k" for the Kelvin sign)
EDGE_TEXTS = [
    "",
    " ".join(f"t{i % 29}x" for i in range(40)),
    "a " + "b" * 130 + " a 7 " + "0123456789" * 11,
    "dup dup DUP Dup dup x dup",
    "it's o'neil's '' 4'' 2x4 007 1.5",
    "Café crème", "İstanbul İİ", "\u212a\u212a k", "👟 shoe 👟 🇯🇵",
]


def _cell_titles(seed, n=64):
    """``n`` titles as the text cell's generator draws them: Zipf words
    over a vocabulary, 1-40 tokens, mixed case and separators."""
    import json

    import loadgen_textcls
    from loadgen import zipf_weights

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
        root, "benchmarks", "chip", "configs", "textcls-rakuten-sigir18.json"
    )) as f:
        config = json.load(f)
    words = loadgen_textcls.vocabulary(seed, 20_000)
    cdf = np.cumsum(zipf_weights(len(words), config["word_zipf_exponent"]))
    rng = np.random.default_rng(seed)
    return loadgen_textcls.draw_titles(rng, words, cdf, n, config["title_tokens"])[0]


def _parents_operand(texts, d, n_rows):
    """The launch's pairs as the template built them before the native
    prep (call it on the numpy path): numpy pairs, padded into a
    power-of-two bucket."""
    row, bucket, count, _ = hash_pairs(texts, d)
    slots = max(1 << max(0, len(row) - 1).bit_length(), PAIRS_PER_QUERY[0] * n_rows)
    pairs = np.zeros((3, slots), np.int32)
    pairs[0] = n_rows - 1
    pairs[0, :len(row)] = row
    pairs[1, :len(row)] = bucket
    pairs[2, :len(row)] = count
    return pairs


@pytest.fixture
def native(monkeypatch):
    """The native text prep, loaded afresh."""
    monkeypatch.delenv("PIO_NO_NATIVE", raising=False)
    monkeypatch.setattr(text_module, "_TEXTPACK", None)
    monkeypatch.setattr(text_module, "_TEXTPACK_TRIED", False)
    lib = text_module._load_textpack()
    assert lib is not None
    return lib


def _use_numpy(monkeypatch):
    """Force the fallback, as a failed load leaves the loader."""
    monkeypatch.setattr(text_module, "_TEXTPACK", None)
    monkeypatch.setattr(text_module, "_TEXTPACK_TRIED", True)


class TestNativePrep:
    """`native/textpack.cc` against the numpy code it stands in for: the
    same bits on every input, and no numpy call over the batch on the
    launching thread."""

    @pytest.mark.parametrize("seed", [0, 2**33 + 17])
    @pytest.mark.parametrize("d", [64, 4096, 2**20])
    def test_native_hash_equals_numpy(self, native, monkeypatch, d, seed):
        rng = np.random.default_rng(d + seed)
        printable = [chr(c) for c in range(32, 127)]
        tokens = _random_ascii(rng, 700) + [
            "".join(rng.choice(printable, int(n))) for n in (1, 2, 63, 64, 65, 300)
        ] + ["don't", "o'", "'", "42", "a1b2", "dup", "dup"]
        got = hash_buckets(tokens, d, seed)
        _use_numpy(monkeypatch)
        want = hash_buckets(tokens, d, seed)
        assert got.dtype == want.dtype == np.int64
        assert got.tolist() == want.tolist()
        # and both are hash_counts' bucket, token by token
        assert got[:50].tolist() == [
            int(np.argmax(hash_counts([t], d, seed))) for t in tokens[:50]
        ]

    @pytest.mark.parametrize("path", ["native", "numpy"])
    def test_a_token_that_is_not_ascii_raises(self, native, monkeypatch, path):
        if path == "numpy":
            _use_numpy(monkeypatch)
        assert hash_buckets([], 64).shape == (0,)
        with pytest.raises(UnicodeEncodeError):
            hash_buckets(["ok", "café"], 64)

    def test_buckets_that_disagree_with_the_token_counts_raise(self, native):
        """The pack checks the token counts against the buckets before it
        reads one, whatever the hash handed it."""
        pack = native.pack
        sizes = np.array([2, 3], np.int64)
        for buckets in (np.arange(4), np.arange(6)):
            with pytest.raises(ValueError, match="token counts"):
                text_module._native_pack(pack, sizes, buckets, 16, 1)
        with pytest.raises(ValueError, match="token counts"):
            text_module._native_pack(pack, np.array([-1, 6]), np.arange(5), 16, 1)
        pairs, n = text_module._native_pack(pack, sizes, np.array([4, 4, 1, 1, 1]), 2, 1)
        assert n == 2 and pairs.tolist() == [[0, 1], [4, 1], [2, 3]]

    @pytest.mark.parametrize("d", [64, 4096, 2**20])
    def test_native_pairs_equal_numpy(self, native, monkeypatch, d):
        texts = TEXTS + EDGE_TEXTS + _cell_titles(d)
        got = hash_pairs(texts, d)
        _use_numpy(monkeypatch)
        want = hash_pairs(texts, d)
        for g, w in zip(got[:3], want[:3]):
            assert g.dtype == w.dtype == np.int64
            np.testing.assert_array_equal(g, w)
        assert got[3] == want[3] == sum(len(tokenize(t)) for t in texts)
        # the edge titles tokenize as Python's Unicode lower() then the class
        assert tokenize(EDGE_TEXTS[6]) == ["i", "stanbul", "i", "i"]
        assert tokenize(EDGE_TEXTS[7]) == ["kk", "k"]

    @pytest.mark.parametrize("batch", ["cell", "overflow", "warmup"])
    @pytest.mark.parametrize("path", ["native", "numpy"])
    def test_launch_operand_is_the_parents(self, native, monkeypatch, path, batch):
        """The [3, slots] operand the step receives, padding included: a
        batch of cell titles; one whose pairs overflow 16 a row into the
        next power of two; the empty warm-up query."""
        d = 4096
        texts = {
            "cell": _cell_titles(3),
            "overflow": [" ".join(f"w{q}x{i}" for i in range(40)) for q in range(3)],
            "warmup": [""],
        }[batch]
        n_rows = 1 << max(0, len(texts) - 1).bit_length()
        with monkeypatch.context() as numpy_only:
            _use_numpy(numpy_only)
            want = _parents_operand(texts, d, n_rows)
        if batch == "overflow":
            assert want.shape[1] == 128 > PAIRS_PER_QUERY[0] * n_rows
        if path == "numpy":
            _use_numpy(monkeypatch)
        model = _model(9, d)
        algo = TextNBAlgorithm(TextNBParams())
        seen = []
        step = nb.sparse_log_scores

        def capturing(theta_t, pi, pairs, n_rows, k=None):
            seen.append(pairs)
            return step(theta_t, pi, pairs, n_rows, k)

        monkeypatch.setattr(nb, "sparse_log_scores", capturing)
        algo.batch_predict(model, [{"text": t, "num": 1} for t in texts])
        assert seen[-1].dtype == np.int32
        np.testing.assert_array_equal(seen[-1], want)

    def test_a_failed_load_falls_back_with_a_warning(self, monkeypatch, caplog):
        import logging

        from predictionio_tpu.utils import native as native_loader

        def broken(name):
            raise RuntimeError(f"building lib{name}.so failed:\ng++: boom")

        monkeypatch.setattr(native_loader, "load_native_lib", broken)
        monkeypatch.setattr(text_module, "_TEXTPACK", None)
        monkeypatch.setattr(text_module, "_TEXTPACK_TRIED", False)
        monkeypatch.delenv("PIO_NO_NATIVE", raising=False)
        with caplog.at_level(logging.WARNING, logger=text_module.__name__):
            assert text_module._load_textpack() is None
        (record,) = [r for r in caplog.records if "text prep" in r.getMessage()]
        assert "numpy fallback" in record.getMessage()
        assert "g++: boom" in record.getMessage()
        sizes, flat = text_module._tokens(TEXTS)
        _, n_pairs, path = text_module._pair_operand(
            sizes, hash_buckets(flat, 64), 64, 8
        )
        assert (n_pairs, path) == (len(hash_pairs(TEXTS, 64)[0]), "numpy")

    def test_pio_no_native_selects_the_fallback(self, monkeypatch):
        from predictionio_tpu.utils import native as native_loader

        def never(name):
            raise AssertionError("PIO_NO_NATIVE=1 must not load a library")

        monkeypatch.setattr(native_loader, "load_native_lib", never)
        monkeypatch.setattr(text_module, "_TEXTPACK", None)
        monkeypatch.setattr(text_module, "_TEXTPACK_TRIED", False)
        monkeypatch.setenv("PIO_NO_NATIVE", "1")
        assert text_module._load_textpack() is None
        assert text_module._pair_operand(
            np.array([2]), np.array([5, 5]), 64, 1
        )[1:] == (1, "numpy")

    @pytest.mark.parametrize("path", ["native", "numpy"])
    def test_the_native_prep_makes_no_numpy_call_over_the_batch(
        self, native, monkeypatch, path
    ):
        """The lock rule: a numpy call over 500 elements hands the
        interpreter lock back, which a busy server's launching thread pays
        for in milliseconds. On 64 cell titles (some 700 tokens) the
        native launch's prep makes none but the allocations it fills
        (`np.fromiter`, `np.empty`); the numpy fallback makes many."""
        if path == "numpy":
            _use_numpy(monkeypatch)
        model = _model(8, 4096)
        algo = TextNBAlgorithm(TextNBParams())
        titles = _cell_titles(8)
        queries = [{"text": t, "num": 5} for t in titles]
        algo.batch_predict_launch(model, queries)  # warm: compiles
        calls = []

        class Recording:
            def __getattr__(self, name):
                attr = getattr(np, name)
                if isinstance(attr, type) or not callable(attr):
                    return attr

                def recorded(*args, **kwargs):
                    out = attr(*args, **kwargs)
                    sizes = [
                        a.size for a in (*args, *kwargs.values(), out)
                        if isinstance(a, np.ndarray)
                    ]
                    calls.append((name, max(sizes, default=0)))
                    return out

                return recorded

        monkeypatch.setattr(text_module, "np", Recording())
        algo.batch_predict_launch(model, queries)
        monkeypatch.undo()
        assert sum(len(tokenize(t)) for t in titles) > 500
        large = {name for name, size in calls if size > 500}
        if path == "native":
            assert large == {"fromiter", "empty"}, calls
        else:
            assert {"unique", "repeat", "flatnonzero"} <= large, calls


class TestSparseScoring:
    @pytest.mark.parametrize("d", [64, 4096])
    def test_sparse_scores_equal_the_reference_and_the_dense_product(self, d):
        """Every class's score, sparse, against `reference_textcls`'s
        arithmetic (float64) and the template's earlier dense product at
        full f32 precision: to 1e-5. At d 64 the texts' tokens collide."""
        model = _model(d, d)
        algo = TextNBAlgorithm(TextNBParams())
        got = _score_matrix(
            algo.batch_predict(model, [{"text": t} for t in TEXTS]), 5
        )
        want = np.tile(model.pi.astype(np.float64), (len(TEXTS), 1))
        for q, text in enumerate(TEXTS):
            for b, n in reference_textcls.bucket_counts(text, d).items():
                want[q] += n * model.theta_t[b].astype(np.float64)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        x = np.stack([hash_counts(tokenize(t), d) for t in TEXTS])
        with jax.default_matmul_precision("highest"):
            dense = nb.log_scores(
                nb.MultinomialNBModel(
                    pi=jnp.asarray(model.pi), theta=jnp.asarray(model.theta_t.T)
                ),
                x,
            )
        np.testing.assert_allclose(got, np.asarray(dense), rtol=1e-5, atol=1e-5)

    def test_without_num_the_answer_is_every_class(self):
        model = _model(3, 256)
        algo = TextNBAlgorithm(TextNBParams())
        answers = algo.batch_predict(model, [{"text": t} for t in TEXTS])
        for answer in answers:
            scores = answer["scores"]
            assert list(scores) == [f"c{c:04d}" for c in range(5)]
            assert answer["label"] == max(scores, key=scores.get)

    @pytest.mark.parametrize("num", [1, 3, 5, 9])
    def test_num_gives_the_k_best_in_order(self, num):
        model = _model(4, 256)
        algo = TextNBAlgorithm(TextNBParams())
        full = algo.batch_predict(model, [{"text": t} for t in TEXTS])
        top = algo.batch_predict(model, [{"text": t, "num": num} for t in TEXTS])
        for whole, best in zip(full, top):
            ranked = sorted(whole["scores"], key=whole["scores"].get, reverse=True)
            assert list(best["scores"]) == ranked[:min(num, 5)]
            assert best["label"] == whole["label"] == ranked[0]
            for name, score in best["scores"].items():
                assert score == pytest.approx(whole["scores"][name], abs=1e-5)
        # a batch that mixes the two: each query as asked
        mixed = algo.batch_predict(
            model, [{"text": TEXTS[0], "num": 2}, {"text": TEXTS[0]}]
        )
        assert len(mixed[0]["scores"]) == 2 and len(mixed[1]["scores"]) == 5

    def test_a_pickle_of_the_dense_form_loads_and_answers_the_same(self):
        """A model pickled with the template's earlier form (a
        MultinomialNBModel of theta [C, d]) loads as the transposed,
        lane-padded table and answers as the earlier dense path did."""
        model = _model(5, 512)
        old = TextNBModel.__new__(TextNBModel)
        old.__dict__.update(
            nb_model=nb.MultinomialNBModel(
                pi=jnp.asarray(model.pi), theta=jnp.asarray(model.theta_t.T)
            ),
            label_map=model.label_map, n_features=512,
        )
        loaded = pickle.loads(pickle.dumps(old))
        assert not hasattr(loaded, "nb_model")
        assert loaded.theta_t.shape == (512, nb.padded_classes(5))
        np.testing.assert_array_equal(loaded.theta_t[:, :5], model.theta_t)
        algo = TextNBAlgorithm(TextNBParams())
        got = algo.batch_predict(
            algo.stage_model(ComputeContext.create(batch="t"), loaded),
            [{"text": t} for t in TEXTS],
        )
        x = np.stack([hash_counts(tokenize(t), 512) for t in TEXTS])
        with jax.default_matmul_precision("highest"):
            dense = np.asarray(nb.log_scores(old.nb_model, x))
        for answer, row in zip(got, dense):
            assert answer["label"] == f"c{int(row.argmax()):04d}"
            np.testing.assert_allclose(
                [answer["scores"][f"c{c:04d}"] for c in range(5)], row,
                rtol=1e-5, atol=1e-5,
            )

    def test_a_batch_bucket_compiles_its_pair_buckets_once(self, monkeypatch):
        """The first launch of a batch bucket runs the step at each of its
        pair buckets for queries with num; later launches run it once."""
        model = _model(6, 256)
        algo = TextNBAlgorithm(TextNBParams())
        calls = []
        step = nb.sparse_log_scores

        def counting(theta_t, pi, pairs, n_rows, k=None):
            calls.append((pairs.shape[1], n_rows, k))
            return step(theta_t, pi, pairs, n_rows, k)

        monkeypatch.setattr(nb, "sparse_log_scores", counting)
        algo.batch_predict(model, [{"text": "a b"}] * 3)
        assert calls == [(p * 4, 4, 5) for p in PAIRS_PER_QUERY] + [(64, 4, None)]
        calls.clear()
        algo.batch_predict(model, [{"text": TEXTS[5], "num": 2}] * 4)
        assert calls == [(256, 4, 5)]


def test_stages_and_counters_on_the_engine_server(ctx, memory_storage):
    """Served through a single-tenant EngineServer's batcher: the launch
    and collect run the four predict stages once, and the three counters
    hold the batch's tokens, distinct pairs and padded slots."""
    from predictionio_tpu.core import EngineParams
    from predictionio_tpu.core.workflow import run_train
    from predictionio_tpu.obs import MetricRegistry
    from predictionio_tpu.serving.engine_server import EngineServer

    _seed(memory_storage)
    engine = textclassification_engine()
    params = EngineParams(
        data_source=("", TextDataSourceParams(app_name="TextApp")),
        preparator=("", TextPreparatorParams(n_features=512)),
        algorithms=[("nb", TextNBParams())],
    )
    run_train(engine, params, engine_id="text-stages", ctx=ctx, storage=memory_storage)
    registry = MetricRegistry()
    es = EngineServer(
        engine, params, engine_id="text-stages", storage=memory_storage,
        ctx=ctx, warmup=False, registry=registry, max_wait_ms=200.0,
        adaptive_wait=False,
    )
    queries = [{"text": "win free money now", "num": 1}, {"text": "money money"}]
    try:
        futures = [es._batchers[0].submit(q) for q in queries]
        answers = [f.result(30) for f in futures]
    finally:
        es.close()
    assert answers[0]["label"] == "spam" and len(answers[0]["scores"]) == 1
    assert len(answers[1]["scores"]) == 2
    data = registry.to_dict()
    stages = {
        s["labels"]["stage"]: s["count"] for s in data["pio_stage_seconds"]["samples"]
    }
    for stage in ("predict.prep", "predict.enqueue", "predict.device_get",
                  "predict.materialize"):
        assert stages.get(stage) == 1, stage

    def value(family):
        [sample] = data[family]["samples"]
        return sample["value"]

    assert value("pio_textcls_tokens_total") == 6
    assert value("pio_textcls_pairs_total") == 5  # "money money" is one pair
    assert value("pio_textcls_pair_slots_total") == PAIRS_PER_QUERY[0] * 2
    path = "numpy" if text_module._load_textpack() is None else "native"
    prep = {
        s["labels"]["path"]: s["value"]
        for s in data["pio_textcls_prep_batches_total"]["samples"]
    }
    assert prep == {path: 1, ({"native", "numpy"} - {path}).pop(): 0}
