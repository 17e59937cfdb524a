"""Naive Bayes — sufficient statistics as one matmul.

Replaces MLlib ``NaiveBayes.train`` (used by the reference classification
template, examples/scala-parallel-classification/add-algorithm/src/main/
scala/NaiveBayesAlgorithm.scala:19-21) and the e2 library's
``CategoricalNaiveBayes`` (e2/src/main/scala/.../engine/
CategoricalNaiveBayes.scala:29-170).

TPU-first design: the per-class feature sums are ``onehot(y).T @ X`` — a
single [C, n] × [n, d] matmul on the MXU — instead of the reference's
``combineByKey`` shuffle. Everything is jitted with static (n, d, C)
shapes; a padding row mask makes padded batches exact.

For hashed text, whose rows are a few nonzeros of a width d of 2^20, the
sparse form takes (row, feature, count) pairs: the fit scatter-adds them
into the transposed [d, C] table, and scoring gathers the rows a batch's
pairs name and sums them per row (:func:`sparse_log_scores`), so neither
builds a [n, d] matrix nor reads the whole table.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class MultinomialNBModel:
    """log-prior pi [C] and log-likelihood theta [C, d]."""

    pi: jax.Array
    theta: jax.Array

    def tree_flatten(self):
        return (self.pi, self.theta), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def n_classes(self) -> int:
        return self.pi.shape[0]


@partial(jax.jit, static_argnames=("n_classes",))
def fit_multinomial(
    x: jax.Array,
    y: jax.Array,
    n_classes: int,
    alpha: float = 1.0,
    mask: jax.Array | None = None,
) -> MultinomialNBModel:
    """Multinomial NB fit (MLlib NaiveBayes semantics, lambda=alpha).

    x: [n, d] non-negative features; y: [n] int labels;
    mask: [n] 1.0 for real rows, 0.0 for padding.
    """
    n, d = x.shape
    onehot = jax.nn.one_hot(y, n_classes, dtype=x.dtype)  # [n, C]
    if mask is not None:
        onehot = onehot * mask[:, None]
    class_count = onehot.sum(axis=0)                       # [C]
    feat_sum = onehot.T @ x                                # [C, d]  (MXU)
    total = class_count.sum()
    pi = jnp.log(class_count + alpha) - jnp.log(
        total + alpha * n_classes
    )
    theta = jnp.log(feat_sum + alpha) - jnp.log(
        feat_sum.sum(axis=1, keepdims=True) + alpha * d
    )
    return MultinomialNBModel(pi=pi, theta=theta)


@jax.jit
def log_scores(model: MultinomialNBModel, x: jax.Array) -> jax.Array:
    """Joint log-scores [n, C] for feature rows [n, d]."""
    return x @ model.theta.T + model.pi[None, :]


@jax.jit
def predict_classes(model: MultinomialNBModel, x: jax.Array) -> jax.Array:
    return jnp.argmax(log_scores(model, x), axis=1)


# --------------------------------------------------------------------------
# Multinomial NB over sparse (row, feature, count) pairs, table [d, C]
# --------------------------------------------------------------------------

#: lanes of a TPU vector register. A [d, C] f32 table whose C is no
#: multiple of them is laid out class-major on the TPU (d minor, no
#: padding), and a row gather then copies the whole table first; at C a
#: multiple of 128 the rows are the minor dimension. So the sparse form
#: pads its classes to a lane multiple: padding classes have theta 0 and
#: pi -inf, and never rank.
LANES = 128


def padded_classes(n_classes: int) -> int:
    return -(-n_classes // LANES) * LANES


def pad_classes(
    pi: np.ndarray, theta_t: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """Host ``(pi [C], theta_t [d, C])`` padded to a lane multiple of
    classes (f32), as :func:`fit_multinomial_sparse` makes them."""
    pi = np.asarray(pi, np.float32)
    theta_t = np.asarray(theta_t, np.float32)
    extra = padded_classes(len(pi)) - len(pi)
    if extra == 0:
        return pi, theta_t
    return (
        np.concatenate([pi, np.full(extra, -np.inf, np.float32)]),
        np.pad(theta_t, ((0, 0), (0, extra))),
    )


@partial(jax.jit, static_argnames=("n_features", "n_classes"))
def fit_multinomial_sparse(
    doc: jax.Array,
    feature: jax.Array,
    count: jax.Array,
    y: jax.Array,
    n_features: int,
    n_classes: int,
    alpha: float = 1.0,
) -> tuple[jax.Array, jax.Array]:
    """``fit_multinomial`` from the nonzeros of ``x``: ``(pi, theta_t)``,
    the log-likelihood table transposed so that a feature's row is what
    sparse scoring gathers, both padded to :func:`padded_classes`.

    doc, feature: int [nnz]; count: [nnz]; y: int [n] (one label a row).
    Padding pairs carry count 0. The counts are scatter-added into one
    [d, C] buffer and normalised in place: no [n, d] matrix exists, and
    the table is the only buffer of its size."""
    padded = padded_classes(n_classes)
    real = jnp.arange(padded) < n_classes
    class_count = jnp.zeros(padded, jnp.float32).at[y].add(1.0)
    counts = jnp.zeros((n_features, padded), jnp.float32).at[
        feature, y[doc]
    ].add(count.astype(jnp.float32))
    pi = jnp.where(
        real,
        jnp.log(class_count + alpha)
        - jnp.log(class_count.sum() + alpha * n_classes),
        -jnp.inf,
    )
    theta_t = jnp.where(
        real,
        jnp.log(counts + alpha)
        - jnp.log(counts.sum(axis=0, keepdims=True) + alpha * n_features),
        0.0,
    )
    return pi, theta_t


#: the named scope of the sparse scoring step (device-trace attribution)
SPARSE_SCOPE = "textcls_score"


@partial(jax.jit, static_argnames=("n_rows", "k"))
def sparse_log_scores(
    theta_t: jax.Array,
    pi: jax.Array,
    pairs: jax.Array,
    n_rows: int,
    k: int | None = None,
):
    """Joint log-scores of ``n_rows`` sparse rows: ``pi + sum count *
    theta_t[feature]`` per row, as f32 adds (no matmul, so no operand
    is rounded to bfloat16 on the way).

    pairs: int32 [3, P] of (row, feature, count), sorted by row; padding
    pairs carry count 0. Returns the [n_rows, C] scores (padding classes
    at -inf), or with ``k`` the ``(scores, classes)`` of each row's ``k``
    best, best first."""
    with jax.named_scope(SPARSE_SCOPE):
        rows = jnp.take(theta_t, pairs[1], axis=0)          # [P, C]
        terms = rows * pairs[2].astype(jnp.float32)[:, None]
        scores = jax.ops.segment_sum(
            terms, pairs[0], num_segments=n_rows, indices_are_sorted=True
        ) + pi[None, :]
    if k is None:
        return scores
    return jax.lax.top_k(scores, k)


# --------------------------------------------------------------------------
# Categorical NB (string features, reference e2 CategoricalNaiveBayes)
# --------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CategoricalNBModel:
    """Per-class priors + per-(feature-slot, value) log-likelihoods.

    Feature slots are concatenated one-hot blocks; ``slot_offsets``
    (static) mark each block's start so likelihoods normalize per slot —
    matching CategoricalNaiveBayes' P(feature_j = v | label).
    """

    pi: jax.Array       # [C]
    theta: jax.Array    # [C, sum(vocab_sizes)]

    def tree_flatten(self):
        return (self.pi, self.theta), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def encode_categorical(
    codes: np.ndarray, vocab_sizes: list[int]
) -> np.ndarray:
    """[n, J] int codes → [n, sum(vocab)] concatenated one-hot (host)."""
    n, j = codes.shape
    assert j == len(vocab_sizes)
    offsets = np.concatenate([[0], np.cumsum(vocab_sizes)[:-1]])
    out = np.zeros((n, int(sum(vocab_sizes))), dtype=np.float32)
    rows = np.arange(n)
    for slot, off in enumerate(offsets):
        valid = codes[:, slot] >= 0
        out[rows[valid], off + codes[valid, slot]] = 1.0
    return out


@partial(jax.jit, static_argnames=("n_classes", "vocab_sizes"))
def fit_categorical(
    onehot_x: jax.Array,
    y: jax.Array,
    n_classes: int,
    vocab_sizes: tuple[int, ...],
    alpha: float = 1.0,
    mask: jax.Array | None = None,
) -> CategoricalNBModel:
    """Categorical NB over concatenated one-hot blocks."""
    onehot_y = jax.nn.one_hot(y, n_classes, dtype=onehot_x.dtype)
    if mask is not None:
        onehot_y = onehot_y * mask[:, None]
    class_count = onehot_y.sum(axis=0)
    counts = onehot_y.T @ onehot_x  # [C, sum(vocab)]
    total = class_count.sum()
    pi = jnp.log(class_count + alpha) - jnp.log(
        total + alpha * n_classes
    )
    # normalize per feature slot: denominator is the class count + alpha*|V_j|
    blocks = []
    off = 0
    for size in vocab_sizes:
        block = counts[:, off:off + size]
        blocks.append(
            jnp.log(block + alpha)
            - jnp.log(class_count[:, None] + alpha * size)
        )
        off += size
    theta = jnp.concatenate(blocks, axis=1)
    return CategoricalNBModel(pi=pi, theta=theta)


@jax.jit
def categorical_log_scores(
    model: CategoricalNBModel, onehot_x: jax.Array
) -> jax.Array:
    return onehot_x @ model.theta.T + model.pi[None, :]
