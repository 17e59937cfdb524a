"""Quantized factor tables for device-resident multi-tenant serving.

A pooled engine server (:mod:`predictionio_tpu.serving.modelpool`)
holds MANY tenants' factor matrices in one chip's HBM, so bytes per
tenant is the capacity knob. This module quantizes ALS/similarity
factor matrices per row — symmetric int8 with an f32 scale vector
(4× smaller than f32) or plain bf16 (2×) — and serves them through
the same top-k entry points as f32:

* the Pallas path passes the int8/bf16 table straight to
  :func:`predictionio_tpu.ops.pallas_topk.fused_top_k_dot`, which
  casts each block to f32 in VMEM on the way to the MXU and folds the
  per-item scale into the scores, so HBM read traffic drops with the
  table size;
* the XLA fallback dequantizes inside one jitted program
  (``convert_element_type`` fuses into the matmul).

Quantized and f32 rankings agree approximately, not exactly — callers
gate on :func:`recall_at_k` against the f32 order (the density bench
enforces the bound), never on exact index equality.

Row-wise symmetric scaling (``scale[i] = max|row_i| / 127``) keeps the
argmax-per-row structure of dot-product retrieval: each item's score
error is bounded by its own row's quant step, so a ~1% score
perturbation only reorders near-ties, which is exactly what the
recall@k gate tolerates.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from functools import partial

from predictionio_tpu.obs import tracing
from predictionio_tpu.ops import similarity

MODES = ("int8", "bf16")

_EPS = 1e-9


@dataclasses.dataclass(frozen=True)
class QuantizedFactors:
    """A quantized factor matrix: ``data`` ([N, k] int8 or bf16) plus
    an optional per-row f32 ``scale`` ([N]); row ``i`` dequantizes to
    ``data[i].astype(f32) * scale[i]`` (scale ``None`` means 1.0).
    Duck-types the few attributes the serving stack reads off a plain
    factor array (``shape``, ``ndim``, ``nbytes``)."""

    data: jax.Array          # [N, k] int8 | bf16
    scale: jax.Array | None  # [N] f32, or None (bf16 mode)
    mode: str                # "int8" | "bf16"

    @property
    def shape(self) -> tuple[int, ...]:
        return tuple(self.data.shape)

    @property
    def ndim(self) -> int:
        return self.data.ndim

    @property
    def nbytes(self) -> int:
        n = int(self.data.size) * self.data.dtype.itemsize
        if self.scale is not None:
            n += int(self.scale.size) * self.scale.dtype.itemsize
        return n


jax.tree_util.register_pytree_node(
    QuantizedFactors,
    lambda qf: ((qf.data, qf.scale), qf.mode),
    lambda mode, children: QuantizedFactors(
        data=children[0], scale=children[1], mode=mode
    ),
)


def quantize_factors(x, mode: str = "int8") -> QuantizedFactors:
    """Quantize a ``[N, k]`` float factor matrix per row. ``int8``:
    symmetric absmax scaling (zero rows get scale 1.0 so they stay
    exactly zero); ``bf16``: a plain cast, no scale vector."""
    if mode not in MODES:
        raise ValueError(f"unknown quantize mode {mode!r}")
    x = jnp.asarray(x)
    if x.ndim != 2:
        raise ValueError(f"expected [N, k] factors, got shape {x.shape}")
    if mode == "bf16":
        return QuantizedFactors(
            data=jnp.asarray(x, jnp.bfloat16), scale=None, mode="bf16"
        )
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale[:, None]), -127, 127).astype(
        jnp.int8
    )
    return QuantizedFactors(data=q, scale=scale, mode="int8")


def dequantize(qf: QuantizedFactors) -> jax.Array:
    """Full f32 reconstruction. Serving materializes it only inside the
    rules step's program, which has no quantized kernel
    (:func:`predictionio_tpu.ops.similarity.rules_top_k`)."""
    x = qf.data.astype(jnp.float32)
    if qf.scale is not None:
        x = x * qf.scale[:, None]
    return x


def stage_quantized(qf: QuantizedFactors) -> QuantizedFactors:
    """Device-resident copy of a quantized table (idempotent, like
    :func:`predictionio_tpu.ops.similarity.stage_factors`)."""
    return QuantizedFactors(
        data=similarity.stage_factors(qf.data),
        scale=(
            None
            if qf.scale is None
            else similarity.stage_factors(qf.scale)
        ),
        mode=qf.mode,
    )


@partial(jax.jit, static_argnames=("num",))
def _top_k_dot_quant_xla(queries, data, scale, num, mask=None, table=None):
    if table is not None:
        # a launch's one program: `queries` are the [B] rows to gather
        # of `table`, the (data, scale) of `_gather_rows_quant`
        queries = _gather_rows_quant(*table, queries)
    with jax.named_scope("score_dequant"):
        scores = queries @ data.astype(jnp.float32).T  # dequant fuses in
        if scale is not None:
            scores = scores * scale[None, :]
        scores = jnp.where(jnp.isnan(scores), -jnp.inf, scores)
        if mask is not None:
            scores = jnp.where(mask, -jnp.inf, scores)
    with jax.named_scope("top_k"):
        return jax.lax.top_k(scores, num)


def top_k_dot_quantized(
    queries: jax.Array,
    items: QuantizedFactors,
    num: int,
    mask=None,
) -> tuple[jax.Array, jax.Array]:
    """Quantized twin of :func:`similarity.top_k_dot`; the same
    ``similarity._use_pallas`` decides between the dequantizing Pallas
    kernel and the XLA fallback."""
    queries = jnp.asarray(queries, jnp.float32)
    num = min(num, items.shape[0])
    if similarity._use_pallas(queries.shape[0], items.shape[0]):
        from predictionio_tpu.ops.pallas_topk import fused_top_k_dot

        return fused_top_k_dot(
            queries,
            items.data,
            num,
            similarity._pallas_mask(mask, queries.shape[0]),
            interpret=jax.default_backend() != "tpu",
            scale=items.scale,
        )
    return _top_k_dot_quant_xla(
        queries, items.data, items.scale, num, mask
    )


def gather_top_k_dot_quantized(
    factors, idx, items: QuantizedFactors, num: int, mask=None
) -> tuple[jax.Array, jax.Array]:
    """Quantized side of :func:`similarity.gather_top_k_dot`: the rows
    ``idx`` of ``factors`` (a quantized table or a float one) dequantized
    and scored against ``items`` in one program, one call into the
    runtime. Composes :func:`gather_rows` and
    :func:`top_k_dot_quantized`, and equals them exactly."""
    if similarity._use_pallas(len(idx), items.shape[0]):
        tracing.launch_call(3)  # idx's upload, the gather, the kernel
        return top_k_dot_quantized(gather_rows(factors, idx), items, num, mask)
    if isinstance(factors, QuantizedFactors):
        table = (factors.data, factors.scale)
    else:
        table = (similarity.host_operand(factors), None)
    tracing.launch_call()
    return _top_k_dot_quant_xla(
        idx, items.data, items.scale, min(num, items.shape[0]), mask, table
    )


@jax.jit
def _gather_rows_quant(data, scale, idx):
    with jax.named_scope("gather_dequant"):
        rows = jnp.take(data, idx, axis=0).astype(jnp.float32)
        if scale is not None:
            rows = rows * jnp.take(scale, idx)[:, None]
        return rows


def gather_rows(qf: "QuantizedFactors | jax.Array", idx) -> jax.Array:
    """Dequantized f32 rows ``qf[idx]`` — only the gathered handful of
    rows is ever reconstructed, never the table."""
    idx = jnp.asarray(idx, jnp.int32)
    if isinstance(qf, QuantizedFactors):
        return _gather_rows_quant(qf.data, qf.scale, idx)
    return _gather_rows_quant(jnp.asarray(qf, jnp.float32), None, idx)


def normalized(qf: QuantizedFactors) -> QuantizedFactors:
    """Row-normalized view for cosine scoring: the symmetric scale
    cancels under l2 normalization, so the result keeps the SAME
    int8/bf16 data with ``scale = 1/‖data_row‖`` — no f32 table."""
    d = qf.data.astype(jnp.float32)
    norm = jnp.linalg.norm(d, axis=1)
    return QuantizedFactors(
        data=qf.data,
        scale=1.0 / (norm + _EPS),
        mode=qf.mode,
    )


def recall_at_k(ref_idx, got_idx) -> float:
    """Mean per-row overlap fraction between two ``[B, k]`` top-k index
    sets — the agreement metric quantized serving is gated on."""
    ref = np.asarray(ref_idx)
    got = np.asarray(got_idx)
    if ref.shape != got.shape:
        raise ValueError(
            f"shape mismatch {ref.shape} vs {got.shape}"
        )
    k = ref.shape[-1]
    hits = [
        len(set(r.tolist()) & set(g.tolist()))
        for r, g in zip(ref.reshape(-1, k), got.reshape(-1, k))
    ]
    return float(np.mean(hits)) / k if hits else 1.0


# -- model-level helpers ----------------------------------------------------


def quantize_model_factors(model, mode: str = "int8"):
    """Quantize + stage every 2-D float ``*_factors`` field of a
    dataclass model (ALS user/item factors, similar-product item
    factors), returning a replaced copy. Anything else — non-dataclass
    models, already-quantized fields, int/1-D fields — passes through
    unchanged, so the pool can apply this to every tenant blindly."""
    if not mode:
        return model
    if not dataclasses.is_dataclass(model) or isinstance(model, type):
        return model
    updates = {}
    for field in dataclasses.fields(model):
        if not field.name.endswith("_factors"):
            continue
        value = getattr(model, field.name, None)
        if value is None or isinstance(value, QuantizedFactors):
            continue
        arr = jnp.asarray(value)
        if arr.ndim != 2 or not jnp.issubdtype(
            arr.dtype, jnp.floating
        ):
            continue
        updates[field.name] = stage_quantized(
            quantize_factors(arr, mode)
        )
    if not updates:
        return model
    return dataclasses.replace(model, **updates)


def model_resident_bytes(model, _depth: int = 3) -> int:
    """Device bytes a staged model holds: sum of ``nbytes`` over array
    and :class:`QuantizedFactors` attributes (dataclass fields, else
    ``__dict__``), recursing into nested dataclasses a few levels so
    template models that wrap their arrays (``ALSRecModel.factors``,
    ``NaiveBayesModel.nb``) are charged, not counted as 0. The pool
    charges tenants against its byte budget with this."""
    if dataclasses.is_dataclass(model) and not isinstance(model, type):
        values = [
            getattr(model, f.name, None)
            for f in dataclasses.fields(model)
        ]
    elif hasattr(model, "__dict__"):
        values = list(vars(model).values())
    else:
        values = [model]
    total = 0
    for value in values:
        nbytes = getattr(value, "nbytes", None)
        if isinstance(nbytes, (int, np.integer)):
            total += int(nbytes)
        elif (
            _depth > 0
            and dataclasses.is_dataclass(value)
            and not isinstance(value, type)
        ):
            total += model_resident_bytes(value, _depth - 1)
    return total
