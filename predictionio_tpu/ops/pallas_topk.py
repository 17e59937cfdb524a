"""Fused scoring + top-k Pallas TPU kernel for the serving hot path.

The XLA path in :mod:`predictionio_tpu.ops.similarity` materializes the
full ``[B, I]`` score matrix in HBM before ``lax.top_k`` reads it back —
at catalog scale (I in the millions) serving becomes HBM-bandwidth-bound
on an array nobody needs. This kernel streams the item-factor matrix
through VMEM in blocks, scores each block on the MXU, and folds it into
a running ``[B, num]`` best-list held in VMEM scratch, so HBM traffic is
just the factors once plus the final ``[B, num]`` result.

Top-k inside the kernel is lazy extraction (Mosaic has no ``lax.top_k``
lowering): a ``while_loop`` of (row-max, first-argmax-by-iota,
sorted-insert) that runs only while some row's remaining block scores
beat that row's kth-best — a warm best-list absorbs a random-order
block in ~1-2 iterations. Memory is O(B·num) instead of the [B, I]
intermediate (4 GB at B=1024 × I=1M). How it compares in time with the
XLA matmul+top_k path is not measured on the installed JAX (ROADMAP
S4); the dispatcher in :mod:`predictionio_tpu.ops.similarity` hands it
only shapes whose intermediate reaches 512 MiB.

Replaces the reference's per-query Spark job
(examples/scala-parallel-recommendation/custom-query/src/main/scala/
ALSAlgorithm.scala:79-105: ``productFeatures`` lookup + cosine +
``collect``) — same math, resident and batched.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# -inf, not finfo.min: unrankable slots (over-masked rows, NaN factors)
# must come back with score -inf exactly like the XLA lax.top_k path
_NEG = float("-inf")


def _merge_block(scores, gcols, num, best_s, best_i):
    """Fold one block's scores into the sorted best-lists.

    Lazy extraction: loop (extract row max → sorted-insert) only while
    some row's remaining block scores beat that row's kth best. A warm
    list absorbs a random-order block in ~1-2 iterations, vs a fixed
    ``num`` full-width selection rounds."""
    b, c = scores.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (b, c), dimension=1)
    pos = jax.lax.broadcasted_iota(jnp.int32, (b, num), dimension=1)

    def cond(carry):
        work, best_s, best_i = carry
        kth = best_s[:, num - 1 : num]
        return jnp.any(work > kth)

    def body(carry):
        work, best_s, best_i = carry
        m = jnp.max(work, axis=1, keepdims=True)                 # [B, 1]
        is_max = work == m
        # first occurrence wins (matches lax.top_k tie order)
        am = jnp.min(
            jnp.where(is_max, cols, jnp.int32(c)), axis=1, keepdims=True
        )
        sel = cols == am
        picked = jnp.sum(
            jnp.where(sel, gcols, 0), axis=1, keepdims=True
        )
        work = jnp.where(sel, _NEG, work)
        # sorted insert of (m, picked) at its rank; stable for ties so
        # earlier blocks (lower indices) stay first, like lax.top_k
        rank = jnp.sum(best_s >= m, axis=1, keepdims=True)       # [B, 1]
        prev_s = jnp.concatenate(
            [jnp.full((b, 1), _NEG, best_s.dtype), best_s[:, :-1]], axis=1
        )
        prev_i = jnp.concatenate(
            [jnp.zeros((b, 1), best_i.dtype), best_i[:, :-1]], axis=1
        )
        new_s = jnp.where(
            pos < rank, best_s, jnp.where(pos == rank, m, prev_s)
        )
        new_i = jnp.where(
            pos < rank, best_i, jnp.where(pos == rank, picked, prev_i)
        )
        improved = m > best_s[:, num - 1 : num]                  # [B, 1]
        best_s = jnp.where(improved, new_s, best_s)
        best_i = jnp.where(improved, new_i, best_i)
        return work, best_s, best_i

    return jax.lax.while_loop(cond, body, (scores, best_s, best_i))[1:]


def _topk_kernel(
    q_ref,        # [B, k] VMEM (whole queries, every step)
    items_ref,    # [IB, k] VMEM (current item block; f32, bf16 or int8)
    mask_ref,     # [B, IB] int8 VMEM or None (True/1 = exclude)
    scale_ref,    # [1, IB] f32 VMEM or None (per-item dequant scale)
    out_s_ref,    # [B, num]
    out_i_ref,    # [B, num]
    best_s_ref,   # scratch [B, num] f32
    best_i_ref,   # scratch [B, num] i32
    *,
    num: int,
    block: int,
    n_blocks: int,
):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        best_s_ref[:] = jnp.full_like(best_s_ref, _NEG)
        # index 0, not -1: slots that never fill (fewer rankable items
        # than num) must still hold a VALID index, matching the XLA
        # path's contract (arbitrary index, score -inf)
        best_i_ref[:] = jnp.zeros_like(best_i_ref)

    items = items_ref[:]
    if items.dtype != jnp.float32:
        # quantized tables dequantize in VMEM on the way to the MXU:
        # only int8/bf16 blocks ever cross HBM, so per-tenant read
        # traffic drops ~4× (int8) vs f32 factors
        items = items.astype(jnp.float32)
    scores = jax.lax.dot_general(
        q_ref[:],
        items,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [B, IB]
    if scale_ref is not None:
        scores = scores * scale_ref[:]  # [1, IB] broadcasts over B
    b = scores.shape[0]
    local = jax.lax.broadcasted_iota(jnp.int32, (b, block), dimension=1)
    gcols = local + j * block
    # NaN scores (corrupted factors) are excluded rather than propagated:
    # a NaN row-max would make the merge loop spin forever (NaN != NaN)
    scores = jnp.where(jnp.isnan(scores), _NEG, scores)
    if mask_ref is not None:
        scores = jnp.where(mask_ref[:] != 0, _NEG, scores)

    best_s, best_i = _merge_block(
        scores, gcols, num, best_s_ref[:], best_i_ref[:]
    )
    best_s_ref[:] = best_s
    best_i_ref[:] = best_i

    @pl.when(j == n_blocks - 1)
    def _emit():
        out_s_ref[:] = best_s_ref[:]
        out_i_ref[:] = best_i_ref[:]


@functools.partial(
    jax.jit,
    static_argnames=("num", "block", "interpret"),
)
def fused_top_k_dot(
    queries: jax.Array,              # [B, k]
    items: jax.Array,                # [I, k] f32/bf16/int8
    num: int,
    mask: jax.Array | None = None,   # [B, I] bool/int8, True/1 = exclude
    block: int = 1024,
    interpret: bool = False,
    scale: jax.Array | None = None,  # [I] f32 per-item dequant scale
) -> tuple[jax.Array, jax.Array]:
    """Pallas-fused equivalent of
    :func:`predictionio_tpu.ops.similarity.top_k_dot`: top-``num`` items
    per query by dot product, without a ``[B, I]`` HBM intermediate.

    ``items`` may be a quantized (int8/bf16) table; a non-f32 block is
    cast to f32 in VMEM and, when ``scale`` is given, each item's score
    is multiplied by its per-row dequant scale (see
    :mod:`predictionio_tpu.ops.quantize`).

    ``interpret=True`` runs the Pallas interpreter (CPU tests)."""
    b, k = queries.shape
    n_items = items.shape[0]
    num = min(num, n_items)
    # fit scores + the merge loop's working copy + double-buffered item
    # blocks in VMEM (~16 MB); shrink the block as B grows
    budget = 10 * 1024 * 1024
    per_col = 4 * (3 * b + 2 * k)
    fit = max(256, budget // per_col)
    block = min(block, 1 << (fit.bit_length() - 1))
    # the kernel covers whole blocks; the ragged tail (and the
    # whole catalog, when it is smaller than one block) merges in the
    # jnp epilogue below — no O(I) pad copy per call
    n_blocks = n_items // block
    head = n_blocks * block

    if n_blocks > 0:
        kernel = functools.partial(
            _topk_kernel, num=num, block=block, n_blocks=n_blocks
        )
        in_specs = [
            pl.BlockSpec((b, k), lambda j: (0, 0)),      # queries: resident
            pl.BlockSpec((block, k), lambda j: (j, 0)),  # item block j
        ]
        operands = [queries, items[:head]]
        if mask is not None:
            in_specs.append(pl.BlockSpec((b, block), lambda j: (0, j)))
            operands.append(mask[:, :head].astype(jnp.int8))
        if scale is not None:
            in_specs.append(pl.BlockSpec((1, block), lambda j: (0, j)))
            operands.append(
                scale[:head].astype(jnp.float32).reshape(1, head)
            )
        kernel = functools.partial(
            _bind_optional_refs, kernel, mask is not None,
            scale is not None,
        )

        with jax.named_scope("fused_top_k"):
            best_s, best_i = pl.pallas_call(
                kernel,
                grid=(n_blocks,),
                in_specs=in_specs,
                out_specs=[
                    pl.BlockSpec((b, num), lambda j: (0, 0)),
                    pl.BlockSpec((b, num), lambda j: (0, 0)),
                ],
                out_shape=[
                    jax.ShapeDtypeStruct((b, num), jnp.float32),
                    jax.ShapeDtypeStruct((b, num), jnp.int32),
                ],
                scratch_shapes=[
                    pltpu.VMEM((b, num), jnp.float32),
                    pltpu.VMEM((b, num), jnp.int32),
                ],
                interpret=interpret,
            )(*operands)
    else:
        best_s = jnp.full((b, num), _NEG, jnp.float32)
        best_i = jnp.zeros((b, num), jnp.int32)

    if head < n_items:
        with jax.named_scope("tail_top_k"):
            tail_items = items[head:]
            if tail_items.dtype != jnp.float32:
                tail_items = tail_items.astype(jnp.float32)
            ts = queries @ tail_items.T
            if scale is not None:
                ts = ts * scale[None, head:].astype(jnp.float32)
            tail_s = jnp.where(jnp.isnan(ts), _NEG, ts).astype(jnp.float32)
            if mask is not None:
                tail_s = jnp.where(mask[:, head:], _NEG, tail_s)
            tail_i = head + jax.lax.broadcasted_iota(
                jnp.int32, (b, n_items - head), dimension=1
            )
            # best entries precede tail candidates, so lax.top_k's
            # first-occurrence tie rule keeps lower item indices first
            cat_s = jnp.concatenate([best_s, tail_s], axis=1)
            cat_i = jnp.concatenate([best_i, tail_i], axis=1)
            best_s, pos = jax.lax.top_k(cat_s, num)
            best_i = jnp.take_along_axis(cat_i, pos, axis=1)
    return best_s, best_i


def _bind_optional_refs(
    kernel, has_mask, has_scale, q_ref, items_ref, *rest, **kwargs
):
    """Route the variable operand list (mask? scale?) to the kernel's
    fixed keyword-free signature, passing None for absent refs."""
    i = 0
    mask_ref = rest[i] if has_mask else None
    i += 1 if has_mask else 0
    scale_ref = rest[i] if has_scale else None
    i += 1 if has_scale else 0
    return kernel(q_ref, items_ref, mask_ref, scale_ref, *rest[i:],
                  **kwargs)
