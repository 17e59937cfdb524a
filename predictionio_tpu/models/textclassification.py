"""Text-classification template — hashed bag-of-words + multinomial NB.

Gallery parity: PredictionIO's template gallery shipped a Text
Classification engine (tf-idf + MLlib NaiveBayes over labeled
documents; the reference repo links the gallery rather than bundling
it — the nearest in-tree pattern is
``examples/scala-parallel-classification``, whose DASE layout this
follows). Documents arrive as ``$set`` events on a ``document`` entity
carrying ``text`` and ``label`` properties; queries
``{"text": "...", "num": k}`` answer ``{"label": ..., "scores": {...}}``:
the ``k`` best classes, best first, or every class without ``num``.

TPU-first redesign: instead of a collected vocabulary + tf-idf RDD
pipeline, tokens are FEATURE-HASHED into a fixed width ``d`` — the
table's shape is a compile-time constant independent of corpus
vocabulary, so the jitted fit/score programs never recompile as data
grows. A document is a handful of (bucket, count) pairs, never a
``d``-wide row: the fit scatter-adds the pairs into the [d, C] count
table (:func:`predictionio_tpu.ops.naive_bayes.fit_multinomial_sparse`),
and scoring a batch gathers the rows its pairs name from the transposed
log-likelihood table and sums them per query
(:func:`~predictionio_tpu.ops.naive_bayes.sparse_log_scores`). At a
taxonomy's width (3,008 classes × 2^20 buckets, 12.6 GB) a launch reads
a few MB of the table, where a dense [B, d] @ [d, C] product read it
all.
"""

from __future__ import annotations

import ctypes
import dataclasses
import logging
import os
import re
import types

import jax
import numpy as np

from predictionio_tpu.core import (
    Algorithm,
    DataSource,
    Engine,
    FirstServing,
    Params,
    Preparator,
    register_engine,
)
from predictionio_tpu.core.controller import SanityCheck
from predictionio_tpu.data.store import EventStore
from predictionio_tpu.models.staged_rules import RegistryCounters
from predictionio_tpu.obs import tracing
from predictionio_tpu.ops import naive_bayes as nb
from predictionio_tpu.ops.similarity import host_operand
from predictionio_tpu.parallel.mesh import ComputeContext
from predictionio_tpu.utils.bimap import BiMap

logger = logging.getLogger(__name__)

_TOKEN = re.compile(r"[a-z0-9']+")


def tokenize(text: str) -> list[str]:
    return _TOKEN.findall(text.lower())


_FNV_OFFSET = 14695981039346656037
_FNV_PRIME = 1099511628211
_MASK64 = (1 << 64) - 1


def hash_counts(
    tokens: list[str], n_features: int, seed: int = 0
) -> np.ndarray:
    """Feature hashing (the 'hashing trick'): token → stable bucket.
    Python's builtin hash() is salted per process, so use a stable
    FNV-1a (explicit 64-bit wraparound) — the model must score
    identically across restarts."""
    vec = np.zeros(n_features, np.float32)
    for tok in tokens:
        h = (_FNV_OFFSET + seed) & _MASK64
        for byte in tok.encode("utf-8"):
            h = ((h ^ byte) * _FNV_PRIME) & _MASK64
        vec[h % n_features] += 1.0
    return vec


_TEXTPACK = None
_TEXTPACK_TRIED = False


def _load_textpack():
    """The two loops of ``native/textpack.cc`` as ctypes functions that
    keep the interpreter lock (``PYFUNCTYPE``: they take tens of µs, and
    a lock handed back costs a busy server's thread more than that to win
    again), or None where the library cannot be built or loaded: the
    numpy code then runs. ``PIO_NO_NATIVE=1`` turns it off (tests run
    both paths). Staging a model loads it, so a server's first launch
    does not."""
    global _TEXTPACK, _TEXTPACK_TRIED
    if _TEXTPACK_TRIED:
        return _TEXTPACK
    _TEXTPACK_TRIED = True
    if os.environ.get("PIO_NO_NATIVE", "").strip() in ("1", "true"):
        return None
    from predictionio_tpu.utils.native import load_native_lib

    try:
        lib = load_native_lib("textpack")
        c = ctypes
        i64 = np.ctypeslib.ndpointer(np.int64, 1, flags="C_CONTIGUOUS")
        out64 = np.ctypeslib.ndpointer(
            np.int64, 1, flags="C_CONTIGUOUS,WRITEABLE"
        )
        out32 = np.ctypeslib.ndpointer(
            np.int32, 2, flags="C_CONTIGUOUS,WRITEABLE"
        )
        _TEXTPACK = types.SimpleNamespace(
            hash=c.PYFUNCTYPE(
                None, c.c_char_p, i64, c.c_int64, c.c_uint64, c.c_uint64,
                out64,
            )(("pio_text_hash", lib)),
            pack=c.PYFUNCTYPE(
                c.c_int64, i64, c.c_int64, i64, c.c_int64, out32, c.c_int64,
                c.c_int32,
            )(("pio_text_pack", lib)),
        )
        logger.info("text prep: native (native/libpio_textpack.so)")
    except Exception as e:  # noqa: BLE001 - native is an optimization only
        # load_native_lib's RuntimeError carries the compiler's output
        logger.warning(
            "text prep: numpy fallback — native/libpio_textpack.so "
            "unavailable (%s: %s)", type(e).__name__, e,
        )
        _TEXTPACK = None
    return _TEXTPACK


def hash_buckets(
    tokens: list[str], n_features: int, seed: int = 0
) -> np.ndarray:
    """:func:`hash_counts`' bucket of each token, int64 [len(tokens)]:
    the same FNV-1a 64-bit, in one native pass over the tokens' bytes,
    else vectorised over the tokens one byte column at a time in numpy
    ``uint64`` (which wraps, as the mask does). Tokens are ASCII, as
    ``tokenize`` yields them (its pattern has no other character), so a
    token's bytes are its characters; any other raises
    ``UnicodeEncodeError``."""
    if not tokens:
        return np.zeros(0, np.int64)
    lengths = np.fromiter(map(len, tokens), np.int64, len(tokens))
    text = "".join(tokens).encode("ascii")
    basis = (_FNV_OFFSET + seed) & _MASK64
    native = _load_textpack()
    if native is not None and n_features > 0:  # a 0 would trap in C++
        out = np.empty(len(tokens), np.int64)
        native.hash(text, lengths, len(tokens), basis, n_features, out)
        return out
    data = np.frombuffer(text, np.uint8)
    starts = np.cumsum(lengths) - lengths
    h = np.full(len(tokens), basis, np.uint64)
    prime = np.uint64(_FNV_PRIME)
    for j in range(int(lengths.max())):
        live = np.flatnonzero(lengths > j)
        byte = data[starts[live] + j].astype(np.uint64)
        h[live] = (h[live] ^ byte) * prime
    return (h % np.uint64(n_features)).astype(np.int64)


def _tokens(texts: list[str]) -> tuple[np.ndarray, list[str]]:
    """Each text's token count, int64, and all their tokens in order."""
    per_text = [tokenize(t) for t in texts]
    sizes = np.fromiter(map(len, per_text), np.int64, len(per_text))
    return sizes, [tok for toks in per_text for tok in toks]


def _unique_pairs(sizes, buckets, n_features: int):
    """numpy's ``(row, bucket, count)``, int64, of the distinct pairs."""
    keys = np.repeat(np.arange(len(sizes), dtype=np.int64), sizes)
    keys = keys * n_features + buckets
    keys, counts = np.unique(keys, return_counts=True)
    return keys // n_features, keys % n_features, counts


def _native_pack(pack, sizes, buckets, slots: int, pad_row: int):
    """``native/textpack.cc``'s pairs in an int32 [3, slots] array, the
    slots past them padding; where the pairs exceed ``slots``, the next
    power of two of them. Returns the array and the number of pairs."""
    pairs = np.empty((3, slots), np.int32)
    n = pack(sizes, len(sizes), buckets, len(buckets), pairs, slots, pad_row)
    if n < 0:
        raise ValueError(
            f"{len(buckets)} buckets for {len(sizes)} texts' token counts"
        )
    if n > slots:
        slots = _pow2(n)
        pairs = np.empty((3, slots), np.int32)
        pack(sizes, len(sizes), buckets, len(buckets), pairs, slots, pad_row)
    return pairs, n


def hash_pairs(
    texts: list[str], n_features: int, seed: int = 0
) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """The distinct (text, bucket) pairs of ``texts`` with their token
    counts, sorted by text then bucket: ``(row, bucket, count, tokens)``
    as int64 arrays and the number of tokens hashed. What
    ``np.stack([hash_counts(tokenize(t), d) for t in texts])`` holds as
    nonzeros, without the [len(texts), d] array. Packed by the serving
    launch's own routine where it loads (int32, so below 2^31 buckets),
    so that train and serve hash alike."""
    sizes, flat = _tokens(texts)
    buckets = hash_buckets(flat, n_features, seed)
    native = _load_textpack()
    if native is None or n_features > 2**31:
        return (*_unique_pairs(sizes, buckets, n_features), len(flat))
    pairs, n = _native_pack(native.pack, sizes, buckets, len(flat), 0)
    row, bucket, count = pairs[:, :n].astype(np.int64)
    return row, bucket, count, len(flat)


@dataclasses.dataclass(frozen=True)
class TextDataSourceParams(Params):
    app_name: str = "MyApp"
    entity_type: str = "document"
    text_property: str = "text"
    label_property: str = "label"
    eval_k: int = 0  # >0 enables k-fold read_eval


@dataclasses.dataclass
class TextTrainingData(SanityCheck):
    texts: list[str]
    labels: list[str]

    def sanity_check(self) -> None:
        if not self.texts:
            raise ValueError("no labeled documents found — seed data first")
        if len(set(self.labels)) < 2:
            raise ValueError(
                "need at least two distinct labels to classify"
            )


class TextDataSource(DataSource[TextTrainingData, dict, dict, list]):
    params_class = TextDataSourceParams

    def read_training(self, ctx: ComputeContext) -> TextTrainingData:
        p = self.params
        props = EventStore().aggregate_properties(
            p.app_name, p.entity_type,
            required=[p.text_property, p.label_property],
        )
        texts, labels = [], []
        for pm in props.values():
            texts.append(str(pm[p.text_property]))
            labels.append(str(pm[p.label_property]))
        return TextTrainingData(texts=texts, labels=labels)

    def read_eval(self, ctx: ComputeContext):
        """k-fold split (shared :func:`~predictionio_tpu.core.evaluation
        .kfold_indices`); actuals are the held-out labels, for
        accuracy-style metrics."""
        from predictionio_tpu.core.evaluation import kfold_indices

        full = self.read_training(ctx)
        folds = []
        for fold, train_idx, test_idx in kfold_indices(
            len(full.texts), self.params.eval_k
        ):
            td = TextTrainingData(
                texts=[full.texts[i] for i in train_idx],
                labels=[full.labels[i] for i in train_idx],
            )
            qa = [
                ({"text": full.texts[i]}, full.labels[i])
                for i in test_idx
            ]
            folds.append((td, {"fold": fold}, qa))
        return folds


@dataclasses.dataclass(frozen=True)
class TextPreparatorParams(Params):
    #: hashed feature width (compile-time constant: the jitted programs
    #: never recompile as the corpus vocabulary grows). 4,096 is a
    #: quick-start size; a taxonomy of thousands of classes wants 2^20
    #: (scikit-learn HashingVectorizer's default) so that words collide
    #: rarely
    n_features: int = 4096


@dataclasses.dataclass
class TextPrepared:
    """The corpus as the nonzeros of its hashed count matrix."""

    doc: np.ndarray      # int32 [nnz] document of each pair
    bucket: np.ndarray   # int32 [nnz] hashed feature of each pair
    count: np.ndarray    # int32 [nnz] the token's count in the document
    y: np.ndarray        # int32 [n_docs] label code of each document
    label_map: BiMap
    n_features: int


class TextPreparator(Preparator[TextTrainingData, TextPrepared]):
    """Hash every document to its (bucket, count) pairs at the constant
    feature width: the fit never sees a [n_docs, n_features] matrix,
    which at 800,000 titles × 2^20 buckets would be 3.4 TB."""

    params_class = TextPreparatorParams

    def prepare(
        self, ctx: ComputeContext, td: TextTrainingData
    ) -> TextPrepared:
        n = self.params.n_features
        label_map, y = BiMap.string_int_with_codes(
            np.asarray(td.labels)
        )
        doc, bucket, count, _ = hash_pairs(td.texts, n)
        return TextPrepared(
            doc=doc.astype(np.int32),
            bucket=bucket.astype(np.int32),
            count=count.astype(np.int32),
            y=np.asarray(y, np.int32),
            label_map=label_map,
            n_features=n,
        )


@dataclasses.dataclass(frozen=True)
class TextNBParams(Params):
    #: additive (Laplace) smoothing, the reference NB lambda
    alpha: float = 1.0


@dataclasses.dataclass
class TextNBModel:
    """log-prior ``pi`` [C] and the log-likelihood table transposed,
    ``theta_t`` [d, C] (f32): a hashed feature's row is what a query
    gathers. Classes are padded to a lane multiple
    (:func:`~predictionio_tpu.ops.naive_bayes.pad_classes`; the label map
    holds the real ones). Device arrays after train and after
    :meth:`TextNBAlgorithm.stage_model`."""

    pi: np.ndarray | jax.Array
    theta_t: np.ndarray | jax.Array
    label_map: BiMap
    n_features: int

    def __setstate__(self, state: dict) -> None:
        # a model pickled before the transposed layout held a
        # MultinomialNBModel of theta [C, d]: transposed once, here, on
        # the host, and it answers as it did
        old = state.pop("nb_model", None)
        if old is not None:
            state["pi"], state["theta_t"] = nb.pad_classes(
                np.asarray(old.pi), np.asarray(old.theta).T
            )
        self.__dict__.update(state)


#: each query's share of a batch's pair slots: a batch of B queries sends
#: a power of two of at least ``PAIRS_PER_QUERY[0] * B`` slots, and the
#: first launch of a batch bucket compiles the pair buckets up to
#: ``PAIRS_PER_QUERY[-1] * B`` beside it
PAIRS_PER_QUERY = (16, 32, 64)
#: the least top-k a batch with ``num`` asks the device for: every num up
#: to it shares one program
MIN_TOP_K = 8


def _pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


def _pair_operand(sizes, buckets, n_features: int, n_rows: int):
    """A launch's int32 [3, slots] pairs, the number of them, and the
    path that packed them (``"native"`` or ``"numpy"``). Slots are a
    power of two of at least ``PAIRS_PER_QUERY[0] * n_rows``; padding
    pairs have count 0 on the last row, so rows stay sorted. The native
    pack writes straight into the operand, so no numpy call over the
    batch runs between the tokens and the step."""
    slots = PAIRS_PER_QUERY[0] * n_rows
    native = _load_textpack()
    if native is not None:
        pairs, n = _native_pack(native.pack, sizes, buckets, slots, n_rows - 1)
        return pairs, n, "native"
    row, bucket, count = _unique_pairs(sizes, buckets, n_features)
    pairs = np.zeros((3, max(_pow2(len(row)), slots)), np.int32)
    pairs[0] = n_rows - 1
    pairs[0, :len(row)] = row
    pairs[1, :len(row)] = bucket
    pairs[2, :len(row)] = count
    return pairs, len(row), "numpy"


class _Counters(RegistryCounters):
    """The template's counters in one registry."""

    def __init__(self, registry):
        self.tokens = registry.counter(
            "pio_textcls_tokens_total",
            "Tokens the text-classification launch tokenized and hashed",
        )
        self.pairs = registry.counter(
            "pio_textcls_pairs_total",
            "Distinct (query, hashed bucket) pairs sent to the device: "
            "the table rows a batch gathers",
        )
        self.slots = registry.counter(
            "pio_textcls_pair_slots_total",
            "Pair slots sent to the device, padding to the batch's "
            "power-of-two pair bucket included",
        )
        prep = registry.counter(
            "pio_textcls_prep_batches_total",
            "Text-classification launches by the path their prep took: "
            "native (native/textpack.cc) or numpy (its fallback)",
            label_names=("path",),
        )
        self.prep = {path: prep.labels(path) for path in ("native", "numpy")}


class TextNBAlgorithm(Algorithm[TextPrepared, TextNBModel, dict, dict]):
    params_class = TextNBParams

    def __init__(self, params=None):
        super().__init__(params)
        #: (table shape, batch bucket) whose pair buckets are compiled
        self._warm: set = set()

    def train(self, ctx: ComputeContext, data: TextPrepared) -> TextNBModel:
        pi, theta_t = nb.fit_multinomial_sparse(
            data.doc, data.bucket, data.count, data.y,
            n_features=data.n_features,
            n_classes=len(data.label_map),
            alpha=self.params.alpha,
        )
        logger.info(
            "text NB: %d classes, %d hashed features, %d pairs",
            len(data.label_map), data.n_features, len(data.doc),
        )
        return TextNBModel(
            pi=pi, theta_t=theta_t,
            label_map=data.label_map,
            n_features=data.n_features,
        )

    def stage_model(
        self, ctx: ComputeContext, model: TextNBModel
    ) -> TextNBModel:
        """Commit the table once at deploy, classes padded; device arrays
        (a train's, already padded) pass through. The native text prep
        loads here, not in a server's first launch."""
        _load_textpack()
        if isinstance(model.theta_t, jax.Array):
            return model
        pi, theta_t = nb.pad_classes(model.pi, model.theta_t)
        return dataclasses.replace(
            model, pi=ctx.replicate(pi), theta_t=ctx.replicate(theta_t)
        )

    def predict(self, model: TextNBModel, query: dict) -> dict:
        return self.batch_predict(model, [query])[0]

    def batch_predict(self, model: TextNBModel, queries) -> list[dict]:
        if not queries:
            return []
        return self.batch_predict_collect(
            model, self.batch_predict_launch(model, queries), queries
        )

    def _top_k(self, model: TextNBModel, queries) -> int | None:
        """The batch's top-k on the device: a power of two of at least
        ``MIN_TOP_K`` over the largest ``num``, or None (every class's
        score) where a query asks for all."""
        nums = [q.get("num") for q in queries]
        if any(n is None for n in nums):
            return None
        n_classes = len(model.label_map)
        return min(n_classes, max(MIN_TOP_K, _pow2(max(map(int, nums)))))

    def _warm_pair_buckets(self, model, n_rows: int) -> None:
        """A batch bucket's first launch (a server's warm-up) also
        compiles the step at each of its pair buckets for queries with a
        ``num`` up to ``MIN_TOP_K``, so that such traffic compiles nothing
        after warm-up while its titles run up to ``PAIRS_PER_QUERY[-1]``
        tokens. (Each compile of a pair bucket takes seconds on the TPU,
        so the all-class form compiles at the least bucket only, by the
        warm-up's own launch.)"""
        key = (model.theta_t.shape, n_rows)
        if key in self._warm:
            return
        self._warm.add(key)
        k = min(MIN_TOP_K, len(model.label_map))
        jax.block_until_ready([
            nb.sparse_log_scores(
                model.theta_t, model.pi,
                np.zeros((3, per_query * n_rows), np.int32), n_rows, k,
            )
            for per_query in PAIRS_PER_QUERY
        ])

    def batch_predict_launch(self, model: TextNBModel, queries):
        """Host prep + device enqueue, no barrier: tokenize and hash the
        batch into its distinct (query, bucket) pairs, pad them to a
        power-of-two bucket, and enqueue the gather-and-sum step."""
        if not queries:
            return None
        n_rows = _pow2(len(queries))
        self._warm_pair_buckets(model, n_rows)
        with tracing.stage(tracing.PREDICT_PREP):
            sizes, flat = _tokens([str(q.get("text", "")) for q in queries])
            buckets = hash_buckets(flat, model.n_features)
            pairs, n_pairs, path = _pair_operand(
                sizes, buckets, model.n_features, n_rows
            )
            k = self._top_k(model, queries)
            counters = _Counters.of(tracing.bound_registry())
            counters.prep[path].inc()
            counters.tokens.inc(len(flat))
            counters.pairs.inc(n_pairs)
            counters.slots.inc(pairs.shape[1])
        with tracing.stage(tracing.PREDICT_ENQUEUE):
            tracing.launch_call(2)  # the pairs' upload, the step
            out = nb.sparse_log_scores(
                host_operand(model.theta_t), host_operand(model.pi),
                pairs, n_rows, k,
            )
        return out, k

    def batch_predict_collect(
        self, model: TextNBModel, handle, queries
    ) -> list[dict]:
        """Device barrier + per-query answers for a launch's handle."""
        if handle is None:
            return []
        out, k = handle
        with tracing.stage(tracing.PREDICT_DEVICE_GET):
            out = jax.device_get(out)
        with tracing.stage(tracing.PREDICT_MATERIALIZE):
            label = model.label_map.inverse
            if k is None:
                return self._answers_of_scores(model, out, queries)
            scores, classes = out
            answers = []
            for i, q in enumerate(queries):
                n = max(1, int(q["num"]))
                answers.append({
                    "label": label(int(classes[i, 0])),
                    "scores": {
                        label(int(c)): float(s)
                        for c, s in zip(classes[i, :n], scores[i, :n])
                    },
                })
            return answers

    @staticmethod
    def _answers_of_scores(model, logp, queries) -> list[dict]:
        """Answers from every class's score: all classes for a query
        without ``num``, its ``num`` best, best first, for one with."""
        names = model.label_map.keys().tolist()
        answers = []
        for row, q in zip(logp[:, :len(names)], queries):
            if q.get("num") is None:
                order = range(len(names))
            else:
                n = min(max(1, int(q["num"])), len(names))
                order = np.argsort(-row, kind="stable")[:n].tolist()
            answers.append({
                "label": names[int(row.argmax())],
                "scores": {names[c]: float(row[c]) for c in order},
            })
        return answers

    def warmup_query(self) -> dict:
        return {"text": ""}


def textclassification_engine() -> Engine:
    return Engine(
        TextDataSource,
        TextPreparator,
        {"nb": TextNBAlgorithm},
        FirstServing,
    )


register_engine("textclassification", textclassification_engine)
