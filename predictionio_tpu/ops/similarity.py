"""Scoring / similarity kernels for serving.

Replaces the reference's per-query RDD predict (ALSAlgorithm.predict:
``productFeatures.lookup`` + cosine ``collect`` — a Spark job per query,
the serving anti-pattern SURVEY.md §3.2 flags) with pre-compiled dense
scoring: one [B, k] × [k, I] matmul + ``lax.top_k``. The same kernels
serve the recommendation template (dot-product scores) and the
similar-product template (cosine over item factors,
examples/scala-parallel-similarproduct/multi/.../ALSAlgorithm.scala).
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp

# the fused pallas kernel is meant to win once XLA's [B, I] score
# intermediate gets big enough to dominate HBM traffic; below that XLA's
# fused top-k needs no kernel dispatch. Where the crossover lies on the
# installed JAX is not measured (ROADMAP S4 sets this threshold)
_PALLAS_MIN_INTERMEDIATE_BYTES = 512 * 1024 * 1024


@jax.jit
def l2_normalize(x: jax.Array, eps: float = 1e-9) -> jax.Array:
    return x / (jnp.linalg.norm(x, axis=-1, keepdims=True) + eps)


@partial(jax.jit, static_argnames=("num",))
def _top_k_dot_xla(
    queries: jax.Array,      # [B, k]
    items: jax.Array,        # [I, k]
    num: int,
    mask: jax.Array | None = None,  # [B, I] or [I] — True = exclude
) -> tuple[jax.Array, jax.Array]:
    # the scopes name the device operations in a profiler trace
    # (docs/observability.md "Named device work")
    with jax.named_scope("score"):
        scores = queries @ items.T  # [B, I] — MXU
        # NaN scores (corrupted factors) map to -inf, matching the
        # Pallas kernel's masking — both top_k_dot paths must rank
        # identically
        scores = jnp.where(jnp.isnan(scores), -jnp.inf, scores)
        if mask is not None:
            # [I] masks (per-item, e.g. phantom padding rows of a
            # sharded catalog) broadcast over the batch dim
            scores = jnp.where(mask, -jnp.inf, scores)
    with jax.named_scope("top_k"):
        return jax.lax.top_k(scores, num)


def _pallas_mask(mask, batch: int):
    """The Pallas kernel streams ``[B, IB]`` mask blocks through VMEM,
    so a per-item ``[I]`` mask must materialize its batch dim first
    (the XLA path broadcasts lazily and never pays this)."""
    if mask is not None and mask.ndim == 1:
        return jnp.broadcast_to(mask[None, :], (batch, mask.shape[0]))
    return mask


def _use_pallas(batch: int, n_items: int) -> bool:
    override = os.environ.get("PIO_PALLAS_TOPK")
    if override is not None:
        return override.strip().lower() in {"1", "true", "yes", "on"}
    # compiled Mosaic kernels exist only for TPU; every other backend
    # would hit the (slow) interpreter, so never auto-select it there
    return (
        batch * n_items * 4 >= _PALLAS_MIN_INTERMEDIATE_BYTES
        and jax.default_backend() == "tpu"
    )


def _quantized(x) -> bool:
    """True when ``x`` is an ``ops.quantize.QuantizedFactors`` table
    (lazy import: quantize imports this module at top level)."""
    from predictionio_tpu.ops import quantize

    return isinstance(x, quantize.QuantizedFactors)


def top_k_dot(
    queries: jax.Array,
    items: jax.Array,
    num: int,
    mask: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Top-``num`` items by dot product. Returns (scores, indices) [B, num].

    Large batch×catalog products on TPU take the fused Pallas path
    (:func:`predictionio_tpu.ops.pallas_topk.fused_top_k_dot`), which
    streams item blocks through VMEM instead of writing the [B, I]
    score matrix to HBM. ``PIO_PALLAS_TOPK=0/1`` overrides the choice.

    ``items`` may be a quantized table
    (:class:`predictionio_tpu.ops.quantize.QuantizedFactors`): the
    pooled multi-tenant server stores int8/bf16 catalogs and every
    serving entry point here accepts them in place of f32 arrays."""
    if _quantized(items):
        from predictionio_tpu.ops import quantize

        return quantize.top_k_dot_quantized(queries, items, num, mask)
    num = min(num, items.shape[0])  # same clamp on both paths
    if _use_pallas(queries.shape[0], items.shape[0]):
        from predictionio_tpu.ops.pallas_topk import fused_top_k_dot

        # a forced override off-TPU runs the interpreter (slow but
        # correct); Mosaic kernels only compile for TPU
        return fused_top_k_dot(
            queries, items, num, _pallas_mask(mask, queries.shape[0]),
            interpret=jax.default_backend() != "tpu",
        )
    return _top_k_dot_xla(queries, items, num, mask)


def top_k_cosine(
    queries: jax.Array,
    items: jax.Array,
    num: int,
    mask: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Top-``num`` by cosine similarity (similar-product scoring).

    A quantized ``items`` table stays quantized: the symmetric per-row
    scale cancels under l2 normalization, so cosine runs on the same
    int8/bf16 data with a ``1/‖row‖`` scale vector
    (:func:`predictionio_tpu.ops.quantize.normalized`)."""
    if _quantized(items):
        from predictionio_tpu.ops import quantize

        return quantize.top_k_dot_quantized(
            l2_normalize(queries), quantize.normalized(items), num, mask
        )
    return top_k_dot(
        l2_normalize(queries), l2_normalize(items), num, mask
    )


# -- staged serving ---------------------------------------------------------
#
# Serving must never re-upload factor matrices per request: at 1M items ×
# rank 64 × f32 the catalog is ~256 MB, and a per-request host→device
# transfer of that size dwarfs every kernel here. Models are
# staged once at deploy (Algorithm.stage_model → stage_factors) and the
# per-request traffic is a handful of int32 indices; gathers happen on
# the device inside the same compiled program as the score + top-k
# (reference keeps the model resident in the server JVM the same way,
# CreateServer.scala:495-647).


def stage_factors(x) -> jax.Array:
    """Upload a factor matrix to the default device once; idempotent —
    an already device-resident ``jax.Array`` is returned as-is (a
    mesh-sharded array keeps its placement). Catalogs that should be
    committed SHARDED go through
    ``parallel.partition.stage_factor_matrix`` instead, which also
    pads rows and builds the phantom mask."""
    if isinstance(x, jax.Array) and not x.is_deleted():
        return x
    return jax.device_put(jnp.asarray(x))


@partial(jax.jit, static_argnames=("num",))
def _gather_top_k_dot_xla(
    factors: jax.Array,   # [U, k] staged
    idx: jax.Array,       # [B] int32 (already clipped to valid rows)
    items: jax.Array,     # [I, k] staged
    num: int,
    mask: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    with jax.named_scope("gather"):
        vecs = jnp.take(factors, idx, axis=0)
    return _top_k_dot_xla(vecs, items, num, mask)


def gather_top_k_dot(
    factors, idx, items, num: int, mask=None
) -> tuple[jax.Array, jax.Array]:
    """Fused row-gather + dot scores + top-``num``: one device dispatch,
    uploading only ``idx``. ``factors``/``items`` may be host arrays
    (evaluation path) — they are uploaded per call then; staged serving
    passes resident ``jax.Array``s. Either side may also be a
    quantized table: gathered user rows dequantize to f32 (a handful
    of rows), the item catalog stays int8/bf16 end to end."""
    if _quantized(factors) or _quantized(items):
        from predictionio_tpu.ops import quantize

        vecs = quantize.gather_rows(factors, idx)
        if _quantized(items):
            return quantize.top_k_dot_quantized(vecs, items, num, mask)
        return top_k_dot(vecs, jnp.asarray(items), num, mask)
    factors, items = jnp.asarray(factors), jnp.asarray(items)
    num = min(num, items.shape[0])
    idx = jnp.asarray(idx, jnp.int32)
    if _use_pallas(idx.shape[0], items.shape[0]):
        from predictionio_tpu.ops.pallas_topk import fused_top_k_dot

        with jax.named_scope("gather"):
            vecs = jnp.take(factors, idx, axis=0)
        return fused_top_k_dot(
            vecs, items, num, _pallas_mask(mask, idx.shape[0]),
            interpret=jax.default_backend() != "tpu",
        )
    return _gather_top_k_dot_xla(factors, idx, items, num, mask)


@partial(jax.jit, static_argnames=("num",))
def _gather_mean_top_k_cosine_xla(
    items_f: jax.Array,   # [I, k] staged
    idx: jax.Array,       # [L] int32, -1 = padding
    num: int,
    mask: jax.Array | None = None,  # [I] True = exclude (phantom rows)
) -> tuple[jax.Array, jax.Array]:
    with jax.named_scope("gather"):
        valid = idx >= 0
        rows = jnp.take(items_f, jnp.clip(idx, 0, None), axis=0)
        w = valid.astype(items_f.dtype)[:, None]
        q = (rows * w).sum(axis=0, keepdims=True) / jnp.maximum(
            w.sum(), 1.0
        )
    return _top_k_dot_xla(
        l2_normalize(q), l2_normalize(items_f), num, mask
    )


def gather_mean_top_k_cosine(
    items_f, idx, num: int, mask=None
) -> tuple[jax.Array, jax.Array]:
    """Similar-product query in one dispatch: mean of the (``-1``-padded)
    gathered item rows → cosine against the whole catalog → top-``num``.
    ``mask`` ([I] bool, True = exclude) drops rows from the ranking —
    the phantom padding rows of a model-sharded catalog score -inf.
    Returns ([1, num] scores, [1, num] indices)."""
    if _quantized(items_f):
        from predictionio_tpu.ops import quantize

        idx = jnp.asarray(idx, jnp.int32)
        valid = idx >= 0
        rows = quantize.gather_rows(items_f, jnp.clip(idx, 0, None))
        w = valid.astype(rows.dtype)[:, None]
        q = (rows * w).sum(axis=0, keepdims=True) / jnp.maximum(
            w.sum(), 1.0
        )
        return quantize.top_k_dot_quantized(
            l2_normalize(q),
            quantize.normalized(items_f),
            min(num, items_f.shape[0]),
            mask,
        )
    items_f = jnp.asarray(items_f)
    return _gather_mean_top_k_cosine_xla(
        items_f,
        jnp.asarray(idx, jnp.int32),
        min(num, items_f.shape[0]),
        mask,
    )
