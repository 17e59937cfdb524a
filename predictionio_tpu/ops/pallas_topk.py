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
intermediate (4 GB at B=1024 × I=1M). The dispatcher in
:mod:`predictionio_tpu.ops.similarity` hands it only shapes whose
intermediate reaches 512 MiB; the unmasked kernel has not been timed
against XLA on the installed JAX (ROADMAP S6, R2).

:func:`fused_rules_top_k` is the e-commerce template's step on the same
merge: each block's scores go through the business rules in VMEM, the
rows' seen / black / white lists arrive as scalar-prefetched entries
sorted by item, and the item table is read with the items along the
lanes. At [64, 4,162,560] × rank 16 on a v5e it took 5.4–6.0 ms against
17.0 ms for XLA's unmasked top-k alone (PERF.md §6, PR 28).

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

from predictionio_tpu.ops.similarity import rule_scores

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


# -- business rules before the top-k -----------------------------------------


def _rules_kernel(
    ptr_ref,      # SMEM [n_blocks + 1]: block j's list entries are
                  # packed_ref[ptr[j]:ptr[j + 1]]
    packed_ref,   # SMEM [N]: query row * block + column inside the block
    q_ref,        # [B, k] VMEM
    items_ref,    # [k, IB] VMEM: the item block, items along the lanes
    mode_ref,     # [B, 1] int32   \
    allow_ref,    # [B, 1] int32    > per query
    qcats_ref,    # [B, QC] int32  /
    cats_ref,     # [C, IB] int32  \
    unavail_ref,  # [1, IB] int32   \ per item
    inv_ref,      # [1, IB] f32     /
    pop_ref,      # [1, IB] f32    /
    out_s_ref,    # [B, num]
    out_i_ref,    # [B, num]
    listed_ref,   # scratch [B, IB] int32: this block's listed items
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
        best_i_ref[:] = jnp.zeros_like(best_i_ref)

    # the rows' lists (seen + blackList, or a whiteList) reach the block
    # as the few entries that fall into it: one row of the plane is
    # rewritten per entry, so the cost follows the entries, and no
    # [B, I] mask is ever built
    listed_ref[:] = jnp.zeros_like(listed_ref)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, block), dimension=1)

    def mark(e, carry):
        packed = packed_ref[e]
        row = packed // block
        at = pl.ds(row, 1)
        listed_ref[at, :] = jnp.where(
            lane == packed - row * block, 1, listed_ref[at, :]
        )
        return carry

    jax.lax.fori_loop(ptr_ref[j], ptr_ref[j + 1], mark, 0)

    dots = jax.lax.dot_general(
        q_ref[:],
        items_ref[:],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [B, IB]
    scores = rule_scores(
        dots, listed_ref[:], mode_ref[:], allow_ref[:], qcats_ref[:],
        cats_ref[:], unavail_ref[:], inv_ref[:], pop_ref[:],
    )
    # a NaN popularity would make the merge loop spin (NaN != NaN)
    scores = jnp.where(jnp.isnan(scores), _NEG, scores)
    b = scores.shape[0]
    gcols = jax.lax.broadcasted_iota(jnp.int32, (b, block), 1) + j * block
    best_s, best_i = _merge_block(
        scores, gcols, num, best_s_ref[:], best_i_ref[:]
    )
    best_s_ref[:] = best_s
    best_i_ref[:] = best_i

    @pl.when(j == n_blocks - 1)
    def _emit():
        out_s_ref[:] = best_s_ref[:]
        out_i_ref[:] = best_i_ref[:]


def _rules_block(batch: int, rank: int, n_categories: int) -> int:
    """Items per grid step of the rules kernel: the scores, the rules'
    planes and the merge loop's copies of a [B, block] tile, and the
    double-buffered item rows, inside ~10 MB of VMEM."""
    per_col = 4 * (8 * batch + 2 * (rank + n_categories + 3))
    fit = max(128, (10 * 1024 * 1024) // per_col)
    return min(1024, 1 << (fit.bit_length() - 1))


def fused_rules_top_k(
    queries: jax.Array,    # [B, k] f32
    items: jax.Array,      # [I, k] f32, I a multiple of the block
    num: int,
    per_query,             # mode [B, 1], allow [B, 1], categories [B, QC]
    per_item,              # categories [C, I], unavailable, 1/norm,
                           # popularity [1, I]
    list_rows: jax.Array,  # [N] int32 query row of each list entry
    list_cols: jax.Array,  # [N] int32 item row of it, ascending; the
                           # unused tail holds INT32_MAX
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """The e-commerce step in one kernel: item blocks stream through VMEM
    once, each block's scores go through
    :func:`predictionio_tpu.ops.similarity.rule_scores` there, and the
    running best-lists take what is left. Called inside a jitted step
    (``similarity._rules_top_k``)."""
    b, k = queries.shape
    n_items = items.shape[0]
    block = _rules_block(b, k, per_item[0].shape[0])
    if n_items % block:
        raise ValueError(
            f"{n_items} item rows are no whole number of blocks of {block}"
        )
    n_blocks = n_items // block
    num = min(num, n_items)
    with jax.named_scope("mask"):
        # block j's entries start where the sorted columns reach j * block
        # (all compared at once: a binary search is a serial loop here)
        starts = jnp.arange(n_blocks + 1, dtype=jnp.int32) * block
        ptr = jnp.sum(
            list_cols[None, :] < starts[:, None], axis=1, dtype=jnp.int32
        )
        packed = list_rows * block + list_cols % block
    whole = lambda x: pl.BlockSpec(x.shape, lambda j, *_: (0, 0))  # noqa: E731
    along = lambda x: pl.BlockSpec(  # noqa: E731
        (x.shape[0], block), lambda j, *_: (0, j)
    )
    # items along the lanes: a [I, k] table with k under 128 lies that
    # way on the TPU already (XLA keeps the long dimension minor), so the
    # transpose is a relabelling; a [block, k] block would be copied out
    # k -> 128 lanes wide first
    items_t = items.T
    per_query = [x.astype(jnp.int32) for x in per_query]
    per_item = [
        x if x.dtype == jnp.float32 else x.astype(jnp.int32)
        for x in per_item
    ]
    kernel = functools.partial(
        _rules_kernel, num=num, block=block, n_blocks=n_blocks
    )
    with jax.named_scope("fused_top_k"):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(n_blocks,),
                in_specs=[whole(queries), along(items_t)]
                + [whole(x) for x in per_query]
                + [along(x) for x in per_item],
                out_specs=[
                    pl.BlockSpec((b, num), lambda j, *_: (0, 0)),
                    pl.BlockSpec((b, num), lambda j, *_: (0, 0)),
                ],
                scratch_shapes=[
                    pltpu.VMEM((b, block), jnp.int32),
                    pltpu.VMEM((b, num), jnp.float32),
                    pltpu.VMEM((b, num), jnp.int32),
                ],
            ),
            out_shape=[
                jax.ShapeDtypeStruct((b, num), jnp.float32),
                jax.ShapeDtypeStruct((b, num), jnp.int32),
            ],
            interpret=interpret,
        )(ptr, packed, queries, items_t, *per_query, *per_item)
