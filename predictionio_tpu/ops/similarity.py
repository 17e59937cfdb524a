"""Scoring / similarity kernels for serving.

Replaces the reference's per-query RDD predict (ALSAlgorithm.predict:
``productFeatures.lookup`` + cosine ``collect`` — a Spark job per query,
the serving anti-pattern SURVEY.md §3.2 flags) with pre-compiled dense
scoring: one [B, k] × [k, I] matmul + ``lax.top_k``. The same kernels
serve the recommendation template (dot-product scores); the e-commerce
and similar-product templates share the rules step (`rules_top_k`: the
summed cosine to a set of items is its `SIMILAR` branch,
examples/scala-parallel-similarproduct/multi/.../ALSAlgorithm.scala).
"""

from __future__ import annotations

import dataclasses
from functools import lru_cache, partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.obs import tracing

# the fused pallas kernel is meant to win once XLA's [B, I] score
# intermediate gets big enough to dominate HBM traffic; below that XLA's
# fused top-k needs no kernel dispatch. Measured above the threshold only
# (1.07 GB, `_use_pallas`); where the crossover lies below it is not
# measured (ROADMAP S6, R2)
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


def _use_pallas(batch: int, n_items: int, listed: bool = False) -> bool:
    """Whether a top-k step of this shape takes the fused kernel.
    ``listed``: the step also masks per-query lists of items (the
    e-commerce rules)."""
    # compiled Mosaic kernels exist only for TPU; every other backend
    # would hit the (slow) interpreter, so never auto-select it there
    if jax.default_backend() != "tpu":
        return False
    # measured on a v5e at [64, 4162560] x rank 16 (PERF.md section 6, PR
    # 28): XLA's top-k 17.0 ms, the fused kernel 5.4; and XLA forms a
    # per-query mask from lists by a scatter that runs at 6 us an entry
    # (50 ms for 64 users' 93 seen items), the kernel inside its stream
    return listed or batch * n_items * 4 >= _PALLAS_MIN_INTERMEDIATE_BYTES


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
    score matrix to HBM (:func:`_use_pallas` chooses from the platform
    and the shape).

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

        # Mosaic kernels compile only for the TPU: a test that patches
        # the choice elsewhere gets the interpreter
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


def host_operand(x, dtype=None):
    """A launch's operand as the jitted program takes it: a device array
    as it is, anything else as a host array, which the compiled call
    uploads itself. An upload by a call of its own (``jnp.asarray``) is
    a second hand-over to the runtime and costs a busy server's thread
    as much as the program's call, whatever its size (PERF.md section
    6, PR 29)."""
    return x if isinstance(x, jax.Array) else np.asarray(x, dtype)


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
    """Fused row-gather + dot scores + top-``num``: one call into the
    runtime, which uploads the host ``idx`` itself. ``factors``/``items``
    may be host arrays (evaluation path) — the call uploads them too,
    every time; staged serving passes resident ``jax.Array``s. Either
    side may also be a quantized table: gathered user rows dequantize to
    f32 (a handful of rows), the item catalog stays int8/bf16 end to
    end."""
    idx = host_operand(idx, np.int32)
    if _quantized(factors) or _quantized(items):
        from predictionio_tpu.ops import quantize

        if _quantized(items):
            return quantize.gather_top_k_dot_quantized(
                factors, idx, items, num, mask
            )
        tracing.launch_call(3)  # idx's upload, the gather, the top-k
        return top_k_dot(
            quantize.gather_rows(factors, idx), jnp.asarray(items), num, mask
        )
    factors, items = host_operand(factors), host_operand(items)
    num = min(num, items.shape[0])
    if _use_pallas(idx.shape[0], items.shape[0]):
        from predictionio_tpu.ops.pallas_topk import fused_top_k_dot

        tracing.launch_call(3)  # idx's upload, the gather, the kernel
        with jax.named_scope("gather"):
            vecs = jnp.take(factors, jnp.asarray(idx), axis=0)
        return fused_top_k_dot(
            vecs, items, num, _pallas_mask(mask, idx.shape[0]),
            interpret=jax.default_backend() != "tpu",
        )
    tracing.launch_call()
    return _gather_top_k_dot_xla(factors, idx, items, num, mask)


# -- business rules before the top-k ------------------------------------------
#
# The e-commerce template's candidates differ per query: the user's seen
# items, a blackList or whiteList, a category, the catalog's unavailable
# items, and "score > 0". A [B, I] mask built on the host is 266 MB a
# batch at 4M items; what crosses to the device instead is compact (a few
# ids per query and one packed list of item indices) and the mask is
# formed on the device against per-catalog resident arrays.

#: a query's branch (reference ECommAlgorithm.predict / predictSimilar /
#: predictDefault)
KNOWN, SIMILAR, POPULAR = 0, 1, 2
#: item rows a query of the SIMILAR branch may name (the e-commerce
#: template's latest 10 views, padded); a caller whose queries name more (the
#: similar-product template's baskets) asks for a power of two times this
#: many (`recent_slots`), a shape of the compiled step
RECENT_SLOTS = 16
#: padding of a query's category slots; an unknown category is any other
#: negative id, which filters and matches nothing
NO_CATEGORY = -2
#: the smallest capacity of a batch's packed lists, and the factor between
#: one capacity and the next (`list_capacity`)
LIST_CAPACITY, LIST_CAPACITY_STEP = 16384, 4
#: the fused kernel holds a batch's packed lists in scalar memory (1 MiB
#: on a v5e, shared with the blocks' offsets): longer ones take the XLA side
FUSED_LIST_CAPACITY = 65536
#: the column of an unused slot of the packed lists: past every item
NO_ITEM = np.iinfo(np.int32).max
#: a staged rules catalog has a whole number of these rows (phantom rows
#: unavailable): whole blocks for the fused kernel, whatever its block
CATALOG_ROW_MULTIPLE = 1024


class CatalogRules(NamedTuple):
    """Per-catalog arrays, resident on the device (``rows`` = rows of the
    item table, phantom padding rows included and marked unavailable)."""

    categories: jax.Array    # [C, rows] int32, -1 = no category
    unavailable: jax.Array   # [rows] bool
    inv_norm: jax.Array      # [rows] f32, 1/|V[i]| (0 for a zero row)
    popularity: jax.Array | None  # [rows] f32; None: no query is POPULAR


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("per_query", "lists"), meta_fields=("item_slots",),
)
@dataclasses.dataclass(frozen=True)
class QueryRules:
    """One batch's rules as two compact host arrays, which the jitted step
    uploads itself and slices apart on the device: each host operand of a
    call costs the launching thread 0.7-0.9 ms on a busy server, whatever
    its size (PERF.md section 6, PR 29). The rows' lists (seen + blackList,
    or a whiteList) are packed together, sorted by item row so that a
    kernel streaming item blocks finds each block's entries side by side.
    ``item_slots`` is no operand: it says where ``per_query``'s columns
    divide, and is a shape of the compiled step."""

    per_query: np.ndarray    # [B, 3 + item_slots + QC] int32, by column:
                             # idx, mode, allow, recent, categories
    lists: np.ndarray        # [2, N] int32: list_rows, list_cols (`pack_lists`)
    item_slots: int = RECENT_SLOTS  # item rows a SIMILAR query may name

    @classmethod
    def blank(
        cls, batch: int, slots: int, item_slots: int = RECENT_SLOTS,
        mode: int = POPULAR,
    ) -> "QueryRules":
        """``batch`` rows of branch ``mode`` that name no item (POPULAR:
        user row 0 asks for the popular items of the whole catalog;
        SIMILAR: an empty answer), with ``item_slots`` item slots
        (`recent_slots`), ``slots`` category slots and no lists yet: a
        launch fills the views in place and adds ``lists`` last
        (``dataclasses.replace``), so nothing is copied together."""
        return cls(
            _fresh(_blank_per_query(batch, slots, item_slots, mode)), None,
            item_slots,
        )

    # views, of the host arrays here and of the traced ones in the step
    idx = property(lambda r: r.per_query[:, 0])     # [B] user rows
    mode = property(lambda r: r.per_query[:, 1])    # [B] KNOWN, SIMILAR, POPULAR
    allow = property(lambda r: r.per_query[:, 2])   # [B] 1: a whiteList
    recent = property(                              # [B, item_slots], -1 = none
        lambda r: r.per_query[:, 3:3 + r.item_slots]
    )
    categories = property(                          # [B, QC], NO_CATEGORY = padding
        lambda r: r.per_query[:, 3 + r.item_slots:]
    )
    list_rows = property(lambda r: r.lists[0])      # [N] each entry's query row
    list_cols = property(lambda r: r.lists[1])      # [N] its item row, ascending;
                                                    # NO_ITEM in the unused tail


def recent_slots(longest: int) -> int:
    """Item slots of a batch whose longest query names ``longest`` items:
    `RECENT_SLOTS` times a power of two, so that baskets of any length
    take few shapes of the compiled step."""
    slots = RECENT_SLOTS
    while slots < longest:
        slots *= 2
    return slots


def list_capacity(total: int) -> int:
    """Length of the packed lists for ``total`` entries: a shape of the
    compiled step, so it takes few values (16,384, which a batch of 64
    users with 93 seen items each fills to a third, then four times that,
    and so on)."""
    capacity = LIST_CAPACITY
    while capacity < total:
        capacity *= LIST_CAPACITY_STEP
    return capacity


#: numpy keeps the interpreter lock around a copy, fill or gather of at
#: most this many elements and lets go of it around a longer one; on a
#: busy server the launching thread then waits its turn to get the lock
#: back, about a millisecond each time (PERF.md section 6, PR 31)
_HELD = 500


def _fresh(kept: np.ndarray) -> np.ndarray:
    """A writable copy of ``kept`` (C-contiguous): the copy is
    `bytearray`'s, made under the interpreter lock (`_HELD`)."""
    return np.frombuffer(bytearray(kept), kept.dtype).reshape(kept.shape)


def _copy_held(dst: np.ndarray, src) -> None:
    """``dst[:] = src`` for one-dimensional operands of one length, in
    steps short enough that the interpreter lock is kept (`_HELD`)."""
    for at in range(0, len(src), _HELD):
        dst[at:at + _HELD] = src[at:at + _HELD]


@lru_cache(maxsize=64)
def _blank_per_query(
    batch: int, slots: int, item_slots: int, mode: int
) -> np.ndarray:
    rules = QueryRules(
        np.zeros((batch, 3 + item_slots + slots), np.int32), None, item_slots
    )
    rules.mode[:] = mode
    rules.recent[:] = -1
    rules.categories[:] = NO_CATEGORY
    rules.per_query.setflags(write=False)
    return rules.per_query


@lru_cache(maxsize=8)
def _blank_lists(capacity: int) -> np.ndarray:
    packed = np.zeros((2, capacity), np.int32)
    packed[1] = NO_ITEM
    packed.setflags(write=False)
    return packed


@lru_cache(maxsize=64)
def _row_numbers(rows: int) -> np.ndarray:
    """0 .. rows-1 as 64-bit little-endian numbers, kept (`np.arange`
    lets go of the interpreter lock at any length)."""
    numbers = np.arange(rows, dtype="<i8")
    numbers.setflags(write=False)
    return numbers


def pack_lists(lists) -> np.ndarray:
    """``lists`` of ``QueryRules`` ([2, N]: list_rows, list_cols) from one
    int array of item rows per query row: every entry as (item row, query
    row), ascending. Each pair is sorted as one 64-bit number, the item
    row in its high half: one vectorized sort in place of a stable
    argsort and two gathers, and the only call here that lets go of the
    interpreter lock (`_HELD`)."""
    lengths = [len(x) for x in lists]
    total = sum(lengths)
    packed = _fresh(_blank_lists(list_capacity(total)))
    if total:
        # little-endian by dtype, so the low half is the first of a pair
        pairs = np.repeat(_row_numbers(len(lists)), lengths)
        halves = pairs.view("<i4").reshape(total, 2)
        if max(lengths) > _HELD:  # `np.concatenate` copies piece by piece
            lists = [
                x[at:at + _HELD] for x in lists
                for at in range(0, len(x), _HELD)
            ]
        _copy_held(halves[:, 1], np.concatenate(lists))
        pairs.sort()
        _copy_held(packed[0, :total], halves[:, 0])
        _copy_held(packed[1, :total], halves[:, 1])
    return packed


# -- shapes and per-item arrays a template stages for the step ---------------


def bucket(n: int) -> int:
    """The power of two at or above ``n``: batch rows, category slots and
    the top-k size take few shapes of the compiled step."""
    return 1 << max(0, n - 1).bit_length()


@jax.jit
def inverse_norms(items):
    norm = jnp.linalg.norm(items, axis=1)
    return jnp.where(norm > 0, 1.0 / norm, 0.0).astype(jnp.float32)


def pad_rows(x, rows: int, value=0):
    """``x`` with its first axis padded to ``rows`` (host or device)."""
    short = rows - x.shape[0]
    if short <= 0:
        return x
    widths = [(0, short)] + [(0, 0)] * (x.ndim - 1)
    if isinstance(x, jax.Array):
        return jnp.pad(x, widths, constant_values=value)
    return np.pad(np.asarray(x), widths, constant_values=value)


def _listed_mask(list_rows, list_cols, batch: int, rows: int) -> jax.Array:
    """[B, rows] int8, 1 where row b's list names the item (XLA side: a
    scatter, which the TPU runs serially at about 6 us an entry, so
    `_use_pallas` sends the rules step to the fused kernel there)."""
    return jnp.zeros((batch, rows), jnp.int8).at[list_rows, list_cols].set(
        1, mode="drop"
    )


def rule_scores(
    dots,         # [B, n] f32 query . item
    listed,       # [B, n] int8 / bool
    mode,         # [B, 1] int32
    allow,        # [B, 1] bool / int
    q_cats,       # [B, QC] int32
    categories,   # [C, n] int32
    unavailable,  # [1, n] bool / int
    inv_norm,     # [1, n] f32
    popularity,   # [1, n] f32
):
    """Scores of one block of items with every rule applied: -inf where
    the item is no candidate of the row's query, or scores <= 0 on a
    branch that keeps positive scores only. 2-D broadcasts only, so the
    Pallas kernel runs it on a VMEM block and XLA on the whole row."""
    scores = jnp.where(mode == SIMILAR, dots * inv_norm, dots)
    scores = jnp.where(mode == POPULAR, popularity, scores)
    excluded = (listed != 0) != (allow != 0)
    filtered = q_cats[:, 0:1] != NO_CATEGORY  # slots fill from the first
    in_category = jnp.zeros(dots.shape, jnp.bool_)
    for c in range(categories.shape[0]):
        for k in range(q_cats.shape[1]):
            in_category |= categories[c:c + 1, :] == q_cats[:, k:k + 1]
    excluded |= unavailable != 0
    excluded |= filtered & ~in_category
    # NaN (corrupted factors) fails `> 0` and is excluded with the rest
    excluded |= (mode != POPULAR) & ~(scores > 0)
    return jnp.where(excluded, -jnp.inf, scores)


@partial(jax.jit, static_argnames=("num", "fused", "interpret"))
def _rules_top_k(
    factors, items, catalog: CatalogRules, rules: QueryRules,
    num: int, fused: bool, interpret: bool,
):
    batch, rows = rules.per_query.shape[0], items.shape[0]
    mode = rules.mode[:, None]
    named, q_cats = rules.recent, rules.categories
    items = _widened(items)
    with jax.named_scope("gather"):
        at = jnp.clip(named, 0, None)
        weight = jnp.where(named >= 0, jnp.take(catalog.inv_norm, at), 0.0)
        vecs = (jnp.take(items, at, axis=0) * weight[:, :, None]).sum(1)
        if factors is not None:  # None: no user table, every row SIMILAR
            vecs = jnp.where(mode == SIMILAR, vecs, _rows(factors, rules.idx))
    per_query = (mode, rules.allow[:, None], q_cats)
    # a catalog with no popularity serves no POPULAR row: any per-item
    # array stands in its operand's place and is never read into a score
    popularity = (
        catalog.inv_norm if catalog.popularity is None else catalog.popularity
    )
    per_item = (
        catalog.categories, catalog.unavailable[None, :],
        catalog.inv_norm[None, :], popularity[None, :],
    )
    if fused:
        from predictionio_tpu.ops.pallas_topk import fused_rules_top_k

        return fused_rules_top_k(
            vecs, items, num, per_query, per_item,
            rules.list_rows, rules.list_cols, interpret=interpret,
        )
    with jax.named_scope("mask"):
        listed = _listed_mask(rules.list_rows, rules.list_cols, batch, rows)
    with jax.named_scope("score"):
        dots = vecs @ items.T
    with jax.named_scope("mask"):
        scores = rule_scores(dots, listed, *per_query, *per_item)
    with jax.named_scope("top_k"):
        return jax.lax.top_k(scores, num)


def _widened(table):
    """``table`` as f32 in a trace. The rules step has no quantized
    kernel: a pool's int8/bf16 table is widened here, inside the one
    program (`rules_top_k`)."""
    if _quantized(table):
        from predictionio_tpu.ops import quantize

        return quantize.dequantize(table)
    return table


def _rows(table, at):
    """``table[at]`` in a trace, f32 rows of a quantized table too."""
    if _quantized(table):
        from predictionio_tpu.ops import quantize

        return quantize.gather_rows(table, at)
    return jnp.take(table, at, axis=0)


def rules_top_k(
    factors, items, num: int, catalog: CatalogRules, rules: QueryRules
) -> tuple[jax.Array, jax.Array]:
    """Gather + score + business rules + top-``num`` in one call into the
    runtime, the mask formed on the device (the call uploads the two
    host arrays of ``QueryRules`` itself, and nothing else).
    Row b's candidates: not ``catalog.unavailable``; in one of the
    query's categories if it names any; on its list if ``allow[b]``, off
    it otherwise. Its scores: ``factors[idx[b]] . item`` (KNOWN), the
    summed cosine to each of its ``recent`` items (SIMILAR), both kept only
    where positive, or the item's popularity (POPULAR). ``factors`` is
    None for a caller with no user table, none of whose rows is KNOWN.
    Either table may be a pool's quantized one: the step has no quantized
    kernel, so the program gathers a user's row as f32 and widens the
    item table to f32 on the device first, every call (at 4.16 M x 16
    int8: PERF.md section 5); the tenant still keeps the smaller table.
    Returns ([B, num] scores, [B, num] item rows); a slot with no
    candidate left has score -inf. Chosen by :func:`_use_pallas` like the
    unmasked step; the fused side takes catalogs of whole blocks
    (`CATALOG_ROW_MULTIPLE`)."""
    batch, rows = len(rules.per_query), items.shape[0]
    tracing.launch_call()
    return _rules_top_k(
        factors, items, catalog, rules,
        num=min(num, rows),
        fused=_use_pallas(batch, rows, listed=True)
        and rows % CATALOG_ROW_MULTIPLE == 0
        and len(rules.list_cols) <= FUSED_LIST_CAPACITY,
        interpret=jax.default_backend() != "tpu",
    )
