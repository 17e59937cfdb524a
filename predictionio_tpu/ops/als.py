"""Alternating Least Squares on the device mesh.

Replaces MLlib ``ALS.trainImplicit`` / ``ALS.train`` (the reference
recommendation + similar-product templates, examples/scala-parallel-
recommendation/custom-query/src/main/scala/ALSAlgorithm.scala:24-77)
with a TPU-native formulation (Hu-Koren-Volinsky implicit feedback).

Design — built around what the TPU is good at (dense batched matmul on
the MXU) and bad at (scatter with colliding indices, which XLA
serializes):

* Host side, interactions are packed into **degree-bucketed slabs**
  (:func:`build_bucketed`): rows are grouped by ``ceil(degree /
  block_len)`` rounded up to a power of two, so every row in a bucket
  owns one dense ``[s * L]`` slot row. A row's whole interaction list
  lives in one slab row — the fixed-shape boundary that replaces
  MLlib's by-key RDD blocking.
* Device side, one half-iteration is, per bucket: gather factors
  ``[R, W, k]`` → batched einsum Gramians (MXU) → **dense** per-row
  normal equations — no scatter, no segment-sum. Only rows heavier
  than ``s_max`` blocks (the handful at the head of the power law) are
  split into sub-rows whose partial stats are combined with one small
  scatter-add. Batched Cholesky solves finish the update.
* On the mesh, every slab is sharded over the ``data`` axis **by row**,
  so each device owns its rows' normal equations end-to-end: the only
  collective per half-iteration is the all-gather that rebuilds the
  replicated factor matrix for the next gather pass (SURVEY.md §2.9 —
  the collectives replacing Spark's shuffle).
* Whole epochs run inside a single jitted ``lax.fori_loop``
  (:func:`train_als` dispatches ``checkpoint_every``-sized chunks), so
  host↔device round-trips are amortized across iterations.

Both implicit (confidence c=1+αr, preferences) and explicit (observed
ratings, MLlib-style weighted-λ regularization) modes are provided.
"""

from __future__ import annotations

import dataclasses
import logging
import math
import os
import time
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from predictionio_tpu.parallel import partition
from predictionio_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    ComputeContext,
    assert_phantom_rows_zero,
)
from predictionio_tpu.parallel.partition import shard_map
from predictionio_tpu.utils import profiling

logger = logging.getLogger(__name__)


# --------------------------------------------------------------------------
# Host-side packing
# --------------------------------------------------------------------------


@dataclasses.dataclass
class PaddedCSR:
    """Fixed-shape blocked interaction lists for one solve direction.

    Retained as the simple packing primitive (tests / external callers);
    :func:`train_als` itself uses the bucketed layout below.
    """

    idx: np.ndarray      # [R, L] int32 — column ids (0 where padded)
    weights: np.ndarray  # [R, L] float32 — interaction value
    valid: np.ndarray    # [R, L] float32 — 1.0 real nnz / 0.0 padding
    owner: np.ndarray    # [R] int32 — row entity of each block
    n_rows: int          # entity count (unpadded)
    n_rows_padded: int   # entity count padded for the mesh

    @property
    def n_blocks(self) -> int:
        return len(self.owner)


def build_padded_csr(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    n_rows: int,
    block_len: int = 64,
    row_multiple: int = 1,
    block_multiple: int = 1,
) -> PaddedCSR:
    """Pack COO → blocked CSR (vectorized, no Python loop over nnz).

    ``row_multiple`` pads the entity count (so factor matrices shard
    evenly); ``block_multiple`` pads the block count (so blocks split
    evenly over devices × scan chunks).
    """
    rows = np.asarray(rows, np.int64)
    order = np.argsort(rows, kind="stable")
    r, c, v = rows[order], np.asarray(cols)[order], np.asarray(vals)[order]
    deg = np.bincount(r, minlength=n_rows)
    nseg = -(-deg // block_len)  # ceil; 0 for empty rows
    seg_base = np.concatenate([[0], np.cumsum(nseg)[:-1]])
    n_blocks = int(nseg.sum())
    row_start = np.concatenate([[0], np.cumsum(deg)[:-1]])
    idx_in_row = np.arange(len(r)) - row_start[r]
    seg_of_nnz = seg_base[r] + idx_in_row // block_len
    pos_in_seg = idx_in_row % block_len

    blocks_padded = max(
        1, -(-n_blocks // block_multiple) * block_multiple
    )
    idx = np.zeros((blocks_padded, block_len), np.int32)
    weights = np.zeros((blocks_padded, block_len), np.float32)
    valid = np.zeros((blocks_padded, block_len), np.float32)
    owner = np.zeros(blocks_padded, np.int32)
    idx[seg_of_nnz, pos_in_seg] = c
    weights[seg_of_nnz, pos_in_seg] = v
    valid[seg_of_nnz, pos_in_seg] = 1.0
    owner[:n_blocks] = np.repeat(np.arange(n_rows), nseg)
    # padding blocks carry zero weights → zero contribution; owner 0 is safe
    n_rows_padded = max(
        row_multiple, -(-n_rows // row_multiple) * row_multiple
    )
    return PaddedCSR(
        idx=idx,
        weights=weights,
        valid=valid,
        owner=owner,
        n_rows=n_rows,
        n_rows_padded=n_rows_padded,
    )


@dataclasses.dataclass
class Slab:
    """One degree bucket: every row owns one dense slot row."""

    idx: np.ndarray      # [R, W] int32 — column ids (0 where padded)
    weights: np.ndarray  # [R, W] float32
    valid: np.ndarray    # [R, W] float32


@dataclasses.dataclass
class Bucketed:
    """Degree-bucketed interaction layout for one solve direction.

    ``slabs`` hold rows with ≤ ``s_max`` blocks (one slot row each,
    phantom rows appended so each slab splits evenly over the mesh).
    ``heavy`` holds the sub-row slab groups of rows heavier than
    ``s_max`` blocks; ``heavy_owner_pos[g]`` maps each sub-row of group
    ``g`` to its owner's position in the concatenated stats layout.
    ``inv_perm[row]`` is the row's position in that layout (heavy rows
    own one zero-initialized slot each, after all regular slab rows).

    Slabs (regular and heavy) are split so no single slab exceeds
    ``max_slab_slots`` slots: the per-slab factor gather materializes a
    ``[R·W, k]`` temp whose lane padding XLA rounds up to 128, so an
    uncapped slab at MovieLens-20M scale allocates >15 GB of HBM for
    one gather. Splitting bounds the peak temp; the concatenated stats
    layout (and therefore ``inv_perm``) is unchanged by the split.
    """

    slabs: list[Slab]
    heavy: list[Slab]
    heavy_owner_pos: list[np.ndarray]   # per group: [R_sub] int32
    inv_perm: np.ndarray                # [n_rows_padded] int32
    n_stat_rows: int                    # rows in the concatenated layout
    n_rows: int
    n_rows_padded: int

    @property
    def padded_nnz(self) -> int:
        total = sum(s.idx.size for s in self.slabs)
        total += sum(h.idx.size for h in self.heavy)
        return total


_ALSPACK_LIB = None
_ALSPACK_TRIED = False


def _load_alspack():
    """ctypes handle to native/libpio_alspack.so (built on first use);
    None when the toolchain/sources are unavailable — callers fall back
    to the numpy path. ``PIO_NO_NATIVE=1`` disables it (tests exercise
    both paths)."""
    global _ALSPACK_LIB, _ALSPACK_TRIED
    if _ALSPACK_TRIED:
        return _ALSPACK_LIB
    _ALSPACK_TRIED = True
    if os.environ.get("PIO_NO_NATIVE", "").strip() in ("1", "true"):
        return None
    import ctypes

    from predictionio_tpu.utils.native import load_native_lib

    try:
        lib = load_native_lib("alspack")
        c = ctypes
        lib.pio_alspack_fill.restype = None
        lib.pio_alspack_fill.argtypes = [
            c.POINTER(c.c_int32), c.POINTER(c.c_int32),
            c.POINTER(c.c_float), c.c_int64, c.POINTER(c.c_int64),
            c.POINTER(c.c_int64), c.POINTER(c.c_int32),
            c.POINTER(c.c_float), c.POINTER(c.c_float),
        ]
        _ALSPACK_LIB = lib
        logger.info("ALS packer: native (native/libpio_alspack.so)")
    except Exception as e:  # noqa: BLE001 - native is an optimization only
        # load_native_lib's RuntimeError carries the compiler's output
        logger.warning(
            "ALS packer: numpy fallback — native/libpio_alspack.so "
            "unavailable (%s: %s)", type(e).__name__, e,
        )
        _ALSPACK_LIB = None
    return _ALSPACK_LIB


def _fill_flat(rows, cols, vals, off_of_row, total_flat, deg):
    """Scatter every nnz into the combined flat slot buffer.

    ``dest(i) = off_of_row[rows[i]] + occurrence(rows[i])`` — rows keep
    their interactions contiguous in original input order (the same
    order the stable-argsort formulation produced). Native path: one
    sequential O(nnz) pass; numpy fallback: stable argsort to derive
    occurrence indices, then three vectorized scatters.
    """
    flat_idx = np.zeros(total_flat, np.int32)
    flat_w = np.zeros(total_flat, np.float32)
    flat_vd = np.zeros(total_flat, np.float32)
    if len(rows) == 0:
        return flat_idx, flat_w, flat_vd
    lib = _load_alspack()
    if lib is not None:
        import ctypes

        c = ctypes
        cursor = np.zeros(len(off_of_row), np.int64)
        off64 = np.ascontiguousarray(off_of_row, np.int64)
        lib.pio_alspack_fill(
            rows.ctypes.data_as(c.POINTER(c.c_int32)),
            cols.ctypes.data_as(c.POINTER(c.c_int32)),
            vals.ctypes.data_as(c.POINTER(c.c_float)),
            c.c_int64(len(rows)),
            off64.ctypes.data_as(c.POINTER(c.c_int64)),
            cursor.ctypes.data_as(c.POINTER(c.c_int64)),
            flat_idx.ctypes.data_as(c.POINTER(c.c_int32)),
            flat_w.ctypes.data_as(c.POINTER(c.c_float)),
            flat_vd.ctypes.data_as(c.POINTER(c.c_float)),
        )
        return flat_idx, flat_w, flat_vd
    order = np.argsort(rows, kind="stable")
    r = rows[order]
    row_start = np.concatenate([[0], np.cumsum(deg)[:-1]])
    occ = np.arange(len(r)) - row_start[r]
    dest = off_of_row[r] + occ
    flat_idx[dest] = cols[order]
    flat_w[dest] = vals[order]
    flat_vd[dest] = 1.0
    return flat_idx, flat_w, flat_vd


def _split_rows(arrays: tuple, rows_per_group: int) -> list[tuple]:
    """Split row-aligned arrays into groups of ≤ ``rows_per_group`` rows
    (host-side; slicing preserves global row order, so stats layouts are
    unaffected)."""
    n = arrays[0].shape[0]
    if n <= rows_per_group:
        return [arrays]
    return [
        tuple(a[i:i + rows_per_group] for a in arrays)
        for i in range(0, n, rows_per_group)
    ]


def build_bucketed(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    n_rows: int,
    block_len: int = 64,
    row_multiple: int = 1,
    s_max: int = 16,
    max_slab_slots: int = 0,
) -> Bucketed:
    """Pack COO → degree-bucketed slabs (vectorized host preprocessing).

    Rows are assigned to buckets of ``s`` blocks (``s`` a power of two,
    ``s ≤ s_max``); a bucket's slab is a dense ``[R_b, s·block_len]``
    array where row ``j`` holds that entity's entire interaction list
    (zero-padded). Rows needing more than ``s_max`` blocks are split
    into sub-rows of width ``s_max·block_len`` in the ``heavy`` slabs.
    No slab exceeds ``max_slab_slots`` (= R·W) slots — the HBM bound on
    the per-slab factor-gather temp (see :class:`Bucketed`).
    """
    if block_len < 1 or s_max < 1:
        raise ValueError("block_len and s_max must be ≥ 1")
    max_slab_slots = _resolve_max_slab_slots(max_slab_slots)

    def rows_per_group(width: int) -> int:
        per = max(1, max_slab_slots // width) // row_multiple
        return max(1, per) * row_multiple
    n_rows_padded = max(
        row_multiple, -(-n_rows // row_multiple) * row_multiple
    )
    rows = np.ascontiguousarray(rows, np.int32)
    cols = np.ascontiguousarray(cols, np.int32)
    vals = np.ascontiguousarray(vals, np.float32)
    deg = np.bincount(rows, minlength=n_rows_padded).astype(np.int64)

    nseg = np.maximum(-(-deg // block_len), 1)
    # bucket size: next power of two ≥ nseg, capped at s_max
    s_of_row = np.minimum(
        2 ** np.ceil(np.log2(nseg)).astype(np.int64), s_max
    )
    is_heavy = nseg > s_max

    bucket_sizes = sorted(int(s) for s in np.unique(s_of_row[~is_heavy]))
    if not bucket_sizes:
        bucket_sizes = [1]

    # Layout planning runs on n_rows-sized arrays (cheap); the only
    # O(nnz) work is ONE fill pass into a combined flat buffer whose
    # slices become the slab views. A row's nnz land contiguously from
    # its flat offset in original input order — for heavy rows too,
    # since their sub-rows are consecutive in the heavy region — so the
    # destination of every nnz is `off[row] + occurrence(row)`, which
    # the native kernel (native/alspack.cc) computes in a single
    # sequential pass (the numpy fallback derives occurrence via a
    # stable argsort).
    inv_perm = np.zeros(n_rows_padded, np.int64)
    row_ids = np.arange(n_rows_padded)
    sizes_arr = np.asarray(bucket_sizes, np.int64)
    widths = sizes_arr * block_len
    reg = ~is_heavy
    bucket_of_row = np.searchsorted(sizes_arr, s_of_row)  # valid where reg
    counts = np.bincount(
        bucket_of_row[reg], minlength=len(bucket_sizes)
    )
    rb_of = np.maximum(
        row_multiple, -(-counts // row_multiple) * row_multiple
    )
    slab_row_base = np.concatenate([[0], np.cumsum(rb_of)[:-1]])
    flat_base = np.concatenate([[0], np.cumsum(rb_of * widths)[:-1]])
    # local index of each member row within its bucket (row-id order —
    # stable sort over the per-row bucket ids preserves ascending ids)
    reg_rows = row_ids[reg]
    reg_buckets = bucket_of_row[reg]
    order = np.argsort(reg_buckets, kind="stable")
    local = np.empty(len(reg_rows), np.int64)
    bucket_start = np.concatenate([[0], np.cumsum(counts)[:-1]])
    local[order] = np.arange(len(reg_rows)) - np.repeat(
        bucket_start, counts
    )
    inv_perm[reg_rows] = slab_row_base[reg_buckets] + local
    off_of_row = np.zeros(n_rows_padded, np.int64)
    off_of_row[reg_rows] = (
        flat_base[reg_buckets] + local * widths[reg_buckets]
    )
    regular_flat = int((rb_of * widths).sum())
    offset = int(rb_of.sum())

    # heavy layout: one stats slot per heavy row after all regular rows;
    # sub-rows of width s_max·block_len appended after the regular flats
    heavy_rows = row_ids[is_heavy]
    width_h = s_max * block_len
    rb_h = 0
    n_sub = 0
    nsub_of = None
    if len(heavy_rows):
        inv_perm[heavy_rows] = offset + np.arange(len(heavy_rows))
        nsub_of = -(-deg[heavy_rows] // width_h)
        n_sub = int(nsub_of.sum())
        rb_h = max(
            row_multiple, -(-n_sub // row_multiple) * row_multiple
        )
        sub_base = np.concatenate([[0], np.cumsum(nsub_of)[:-1]])
        off_of_row[heavy_rows] = regular_flat + sub_base * width_h

    total_flat = regular_flat + rb_h * width_h
    flat_idx, flat_w, flat_vd = _fill_flat(
        rows, cols, vals, off_of_row, total_flat, deg
    )

    slabs: list[Slab] = []
    for b, s in enumerate(bucket_sizes):
        width = int(widths[b])
        n_b = int(rb_of[b])
        start = int(flat_base[b])
        end = start + n_b * width
        full = (
            flat_idx[start:end].reshape(n_b, width),
            flat_w[start:end].reshape(n_b, width),
            flat_vd[start:end].reshape(n_b, width),
        )
        for g_idx, g_wt, g_vd in _split_rows(full, rows_per_group(width)):
            slabs.append(Slab(idx=g_idx, weights=g_wt, valid=g_vd))

    heavy: list[Slab] = []
    heavy_owner_pos: list[np.ndarray] = []
    if len(heavy_rows):
        hs = (
            flat_idx[regular_flat:].reshape(rb_h, width_h),
            flat_w[regular_flat:].reshape(rb_h, width_h),
            flat_vd[regular_flat:].reshape(rb_h, width_h),
        )
        owner = np.zeros(rb_h, np.int32)
        owner[:n_sub] = np.repeat(
            inv_perm[heavy_rows], nsub_of
        ).astype(np.int32)
        # phantom sub-rows have zero valid/weights: owner 0 is harmless
        for g_idx, g_wt, g_vd, g_own in _split_rows(
            (*hs, owner), rows_per_group(width_h)
        ):
            heavy.append(Slab(idx=g_idx, weights=g_wt, valid=g_vd))
            heavy_owner_pos.append(g_own)
        offset += len(heavy_rows)

    return Bucketed(
        slabs=slabs,
        heavy=heavy,
        heavy_owner_pos=heavy_owner_pos,
        inv_perm=inv_perm.astype(np.int32),
        n_stat_rows=offset,
        n_rows=n_rows,
        n_rows_padded=n_rows_padded,
    )


# --------------------------------------------------------------------------
# Device-side solve
# --------------------------------------------------------------------------


def _resolve_compute(compute_dtype: str | None):
    """Gather/Gramian compute dtype: None result = factor dtype (f32).

    ``"bfloat16"``/``"bf16"`` halves the gather temp + HBM traffic (the
    factor matrix is cast BEFORE the gather) and doubles MXU rate;
    Gramians still accumulate in f32 (``preferred_element_type``) and
    the Cholesky solve stays f32. Empty/None falls back to the
    ``PIO_ALS_COMPUTE_DTYPE`` env knob, then ``auto``: bf16 on the TPU
    backend, f32 elsewhere. The default is bf16-on-TPU because the
    quality impact is unmeasurable on ranking tasks — planted-cluster
    precision@10 0.9729 (f32) vs 0.9730 (bf16), top-10 overlap 99.5%
    (CPU quality A/B); pass ``"float32"`` (or set the env knob) to opt
    out. Unknown names fail here — at solver build — with the
    supported list.
    """
    name = (compute_dtype or "").strip().lower()
    if not name:
        name = os.environ.get("PIO_ALS_COMPUTE_DTYPE", "").strip().lower()
    if not name:
        name = "auto"
    if name == "auto":
        return (
            jnp.bfloat16 if jax.default_backend() == "tpu" else None
        )
    if name in ("float32", "f32"):
        return None
    if name in ("bfloat16", "bf16"):
        return jnp.bfloat16
    # no float16: its 65504 max overflows implicit-mode confidence
    # weights (alpha × counts) and _solve would silently zero the
    # affected rows; bf16 has the f32 exponent range and is immune
    raise ValueError(
        f"unsupported ALS compute_dtype {name!r}; supported: "
        "auto, float32/f32, bfloat16/bf16"
    )


#: default HBM bound on the per-slab factor-gather temp (in R·W slots)
DEFAULT_MAX_SLAB_SLOTS = 2 << 20


def _resolve_max_slab_slots(value: int) -> int:
    """Slab-size cap: explicit value wins, then the
    ``PIO_ALS_MAX_SLAB_SLOTS`` env knob, then the default. The default
    was sized for the kminor gather temp (slots × 128 lanes-padded ×4 B
    = 1 GB/slab at 2M slots); under the kmajor layout the same HBM
    admits ~4× the slots — a knob worth A/B-ing at 20M-nnz scale."""
    if value:
        if value < 0:
            raise ValueError(
                f"max_slab_slots must be positive, got {value}"
            )
        return value
    raw = os.environ.get("PIO_ALS_MAX_SLAB_SLOTS", "").strip()
    if raw:
        try:
            parsed = int(raw)
        except ValueError as e:
            raise ValueError(
                f"PIO_ALS_MAX_SLAB_SLOTS {raw!r} is not an integer"
            ) from e
        if parsed <= 0:
            raise ValueError(
                f"PIO_ALS_MAX_SLAB_SLOTS must be positive, got {parsed}"
            )
        return parsed
    return DEFAULT_MAX_SLAB_SLOTS


def _resolve_gather_layout() -> str:
    """Layout of the factor-gather temp (``PIO_ALS_GATHER_LAYOUT``),
    resolved + validated ONCE at solver build (like _resolve_compute):

    * ``kminor`` — gather to ``[R, W, k]``. Simple, but the minor dim
      is the rank: XLA lane-pads k=32 to 128, 4× the HBM footprint and
      traffic of the epoch's biggest temp.
    * ``kmajor`` — gather to ``[k, R, W]``: the minor dim is the slot
      width, unpadded whenever ``s·block_len`` is a multiple of 128
      (true for every bucket with s ≥ 2 at the default block_len=64;
      the s=1 bucket stays lane-padded). Same math, same results.
    * ``auto`` (default) — kmajor on the TPU backend, kminor
      elsewhere.
    """
    name = os.environ.get(
        "PIO_ALS_GATHER_LAYOUT", "auto"
    ).strip().lower()
    if name not in ("auto", "kminor", "kmajor"):
        raise ValueError(
            f"unsupported PIO_ALS_GATHER_LAYOUT {name!r}; "
            "supported: auto, kminor, kmajor"
        )
    if name == "auto":
        return (
            "kmajor" if jax.default_backend() == "tpu" else "kminor"
        )
    return name


def _slab_stats(y, idx, weights, valid, implicit, alpha, dtype,
                compute=None, gather_layout="kminor"):
    """Per-row normal-equation pieces for one dense slab — pure MXU."""
    # y arrives pre-cast to `compute` (see _assemble_and_solve), so the
    # gather temp itself is low-precision — that is where the memory and
    # bandwidth live
    mask = valid  # a real 0-valued explicit rating still counts
    if implicit:
        aw = alpha * weights * mask          # C − I (zero on padding)
        bw = mask + alpha * weights * mask   # c·p on observed
    else:
        aw = mask
        bw = weights * mask
    if compute is not None:
        aw = aw.astype(compute)
        bw = bw.astype(compute)
    # the scopes name the device operations in a profiler trace
    # (docs/observability.md "Named device work")
    if gather_layout == "kmajor":
        with jax.named_scope("gather"):
            ygT = jnp.take(y.T, idx, axis=1)  # [k, R, W] — unpadded minor W
        with jax.named_scope("gramian"):
            a = jnp.einsum(
                "krl,rl,mrl->rkm", ygT, aw, ygT,
                preferred_element_type=dtype,
            )
            b = jnp.einsum(
                "krl,rl->rk", ygT, bw, preferred_element_type=dtype
            )
    else:
        with jax.named_scope("gather"):
            yg = y[idx]  # [R, W, k] gather (unique rows per device slice)
        with jax.named_scope("gramian"):
            a = jnp.einsum(
                "rlk,rl,rlm->rkm", yg, aw, yg,
                preferred_element_type=dtype,
            )
            b = jnp.einsum(
                "rlk,rl->rk", yg, bw, preferred_element_type=dtype
            )
    cnt = mask.sum(axis=1)
    return a, b, cnt


def _chol_solve_batched(a, b):
    """Solve ``a @ x = b`` for huge batches of small SPD systems.

    XLA's TPU Cholesky serializes poorly for [N, k, k] with tiny k and
    huge N (≈7× slower than this). Same math, reordered: unrolled
    Cholesky–Crout + forward/back substitution where every step is a
    ``[N, ·]`` batch-vectorized op (k is the static factor rank, so the
    unroll is small).
    """
    n, k, _ = a.shape
    dtype = a.dtype
    cols = []   # columns of L, each [N, k]
    diag = []   # [N] diagonal entries
    for j in range(k):
        if j:
            l_mat = jnp.stack(cols, axis=-1)              # [N, k, j]
            l_row = jnp.stack([c[:, j] for c in cols], axis=-1)
            s = jnp.einsum("nip,np->ni", l_mat, l_row)
        else:
            s = jnp.zeros((), dtype)
        col = a[:, :, j] - s
        d = jnp.sqrt(col[:, j])
        mask = (jnp.arange(k) >= j).astype(dtype)
        cols.append(col / d[:, None] * mask)
        diag.append(d)
    low = jnp.stack(cols, axis=-1)                        # [N, k, k]
    ys = []
    for j in range(k):  # forward: L y = b
        s = b[:, j]
        if j:
            s = s - jnp.einsum(
                "np,np->n", low[:, j, :j], jnp.stack(ys, axis=-1)
            )
        ys.append(s / diag[j])
    xs: list = [None] * k
    for j in reversed(range(k)):  # back: Lᵀ x = y
        s = ys[j]
        if j < k - 1:
            s = s - jnp.einsum(
                "np,np->n", low[:, j + 1:, j],
                jnp.stack(xs[j + 1:], axis=-1),
            )
        xs[j] = s / diag[j]
    return jnp.stack(xs, axis=-1)


@jax.named_scope("solve")
def _solve(a, b, cnt, yty, lam, implicit, k, dtype):
    if implicit:
        a = a + yty[None] + lam * jnp.eye(k, dtype=dtype)[None]
    else:
        # MLlib-style weighted-λ regularization: λ · n_u · I
        reg = lam * jnp.maximum(cnt, 1.0)
        a = a + reg[:, None, None] * jnp.eye(k, dtype=dtype)[None]
    if jax.default_backend() == "cpu":
        # LAPACK's batched Cholesky is the fast path on CPU; the
        # unrolled variant exists for TPU (keeps the CPU-vs-TPU
        # benchmark honest: each backend runs its best formulation)
        chol = jnp.linalg.cholesky(a)
        x = jax.scipy.linalg.cho_solve((chol, True), b[..., None])[..., 0]
    else:
        x = _chol_solve_batched(a, b)
    return jnp.where(jnp.isfinite(x), x, 0.0)


def _assemble_and_solve(
    y, slab_arrays, heavy_groups, n_heavy_slots,
    implicit, alpha, lam, compute=None, gather_layout="kminor",
):
    """Shared one-direction solve body: slab stats → heavy scatter-add →
    batched normal-equation solve. Used by both the replicated
    (GSPMD-constrained) and model-sharded (shard_map) paths — the only
    difference between them is where ``y`` comes from and how the solved
    stats rows are reassembled into factor layout.

    ``heavy_groups`` is a sequence of ``(idx, weights, valid, owner)``
    sub-row slab groups (possibly several — build_bucketed caps slab
    size to bound the factor-gather temp).

    Memory shape: each slab group's ``[R_g, k, k]`` Gramians are solved
    IMMEDIATELY and only the ``[R_g, k]`` factor rows survive to the
    final concatenation — the full ``[n_stat_rows, k, k]`` stats array
    never materializes. At 1M+ entity rows that array alone is >4 GB
    (plus the epoch loop's copies), which OOMed a 16 GB chip at the
    Criteo-magnitude workload; bounding peak HBM by the slab cap
    instead makes row count a host-memory concern only. Heavy sub-rows
    are the one scatter-add: their owner slots sit AFTER all regular
    rows in the stats layout (build_bucketed appends them; plan_shards
    keeps the same device-local shape), so they accumulate into a
    small ``[n_heavy_slots, k, k]`` buffer solved last.
    """
    k = y.shape[1]
    dtype = y.dtype
    if compute is not None:
        # cast ONCE, before any gather: every slab's [R, W, k] gather
        # temp (and its read traffic) is then low-precision. Stats
        # always ACCUMULATE in f32 — y may already arrive cast (the
        # sharded path casts before its all-gather), so the accumulator
        # dtype must not be inferred from it.
        dtype = jnp.float32
        y = y.astype(compute)
    with jax.named_scope("gramian"):
        yty = (
            jnp.einsum("ik,im->km", y, y, preferred_element_type=dtype)
            if implicit
            else None
        )
    n_regular = 0
    parts_x = []
    for (idx, weights, valid) in slab_arrays:
        a, b, cnt = _slab_stats(
            y, idx, weights, valid, implicit, alpha, dtype, compute,
            gather_layout,
        )
        parts_x.append(_solve(a, b, cnt, yty, lam, implicit, k, dtype))
        n_regular += idx.shape[0]
    if n_heavy_slots:
        ha = jnp.zeros((n_heavy_slots, k, k), dtype)
        hb = jnp.zeros((n_heavy_slots, k), dtype)
        hcnt = jnp.zeros((n_heavy_slots,), dtype)
        for (idx, weights, valid, owner) in heavy_groups:
            ga, gb, gcnt = _slab_stats(
                y, idx, weights, valid, implicit, alpha, dtype, compute,
                gather_layout,
            )
            # owners are absolute stats positions; rebase into the
            # heavy-only buffer. Phantom sub-rows carry owner 0 with
            # all-zero weights/valid — clip keeps their (zero)
            # contribution in range instead of wrapping negatively.
            local = jnp.clip(
                jnp.asarray(owner) - n_regular, 0, n_heavy_slots - 1
            )
            # few sub-rows (head of the power law): small scatter-add
            ha = ha.at[local].add(ga)
            hb = hb.at[local].add(gb)
            hcnt = hcnt.at[local].add(gcnt)
        parts_x.append(
            _solve(ha, hb, hcnt, yty, lam, implicit, k, dtype)
        )
    return jnp.concatenate(parts_x, axis=0)


def make_bucketed_solver(
    ctx: ComputeContext,
    packed: Bucketed,
    implicit: bool,
    alpha: float,
    compute_dtype: str | None = None,
):
    """Build the one-direction solver body for a fixed geometry.

    Returned fn (NOT jitted — compose under an outer jit):
    ``(y [I,k] replicated, slab_arrays, lam) → x [n_rows_padded, k]``.
    Slabs arrive row-sharded over the data axis, so each device computes
    its rows' stats and solves locally; the trailing ``inv_perm`` gather
    (replicated output constraint) is the one all-gather per call.
    """
    inv_perm = packed.inv_perm
    n_heavy_slots = (
        packed.n_stat_rows
        - sum(s.idx.shape[0] for s in packed.slabs)
    )
    heavy_owners = packed.heavy_owner_pos
    replicated = ctx.replicated
    compute = _resolve_compute(compute_dtype)
    gather_layout = _resolve_gather_layout()

    def solve(y, slab_arrays, heavy_arrays, lam):
        heavy_groups = [
            (idx, wt, vd, owner)
            for (idx, wt, vd), owner in zip(heavy_arrays, heavy_owners)
        ]
        x_stats = _assemble_and_solve(
            y, slab_arrays, heavy_groups, n_heavy_slots,
            implicit, alpha, lam, compute, gather_layout,
        )
        x = jnp.take(x_stats, jnp.asarray(inv_perm), axis=0)
        return jax.lax.with_sharding_constraint(x, replicated)

    return solve


def _slab_tree(slabs: Sequence[Slab]) -> list[dict]:
    """Slabs as a named pytree — the leaf paths (``slabs/0/idx``) are
    what the partition-rule regexes match against."""
    return [
        {"idx": s.idx, "weights": s.weights, "valid": s.valid}
        for s in slabs
    ]


def _slab_tuples(tree: list[dict]) -> tuple:
    return tuple((d["idx"], d["weights"], d["valid"]) for d in tree)


def _device_slabs(ctx: ComputeContext, packed: Bucketed):
    """Stage the replicated-factor geometry per the ALS rule table:
    slab rows split over ``data``, everything else replicated."""
    placed = partition.shard_pytree(
        ctx,
        partition.ALS_REPLICATED_RULES,
        {
            "slabs": _slab_tree(packed.slabs),
            "heavy": _slab_tree(packed.heavy),
        },
    )
    return _slab_tuples(placed["slabs"]), _slab_tuples(placed["heavy"])


def make_solve_side(
    ctx: ComputeContext,
    packed: Bucketed,
    implicit: bool,
    alpha: float,
    compute_dtype: str | None = None,
):
    """Jitted single-direction solver over a pre-staged geometry.

    ``(y, slab_arrays, heavy_arrays, lam) → x`` — used by the profiling
    path and the benchmark; :func:`make_train_step` fuses both
    directions and whole epochs for the production path.
    """
    body = make_bucketed_solver(ctx, packed, implicit, alpha, compute_dtype)
    return jax.jit(body)


def make_train_step(
    ctx: ComputeContext,
    user_packed: Bucketed,
    item_packed: Bucketed,
    implicit: bool,
    alpha: float,
    compute_dtype: str | None = None,
):
    """Fused multi-epoch trainer: one dispatch runs ``n_iters`` epochs.

    Returned fn: ``(x, y, u_slabs, u_heavy, i_slabs, i_heavy, lam,
    n_iters) → (x, y)`` with ``n_iters`` static. Epochs chain on-device
    through a ``fori_loop``, amortizing host↔device dispatch latency
    across the whole run.
    """
    solve_u = make_bucketed_solver(
        ctx, user_packed, implicit, alpha, compute_dtype
    )
    solve_i = make_bucketed_solver(
        ctx, item_packed, implicit, alpha, compute_dtype
    )

    # donate the factor carries: XLA reuses their HBM for the epoch
    # chain's outputs instead of double-buffering both matrices (at
    # 1M rows × rank 64 f32 that is ~256 MB per side back). Callers
    # rebind (`x, y = step(x, y, n)`), which the donation lint rule
    # enforces. CPU has no donation support and would warn per compile.
    donate = (0, 1) if jax.default_backend() != "cpu" else ()

    @partial(
        jax.jit, static_argnames=("n_iters",), donate_argnums=donate
    )
    def run(x, y, u_slabs, u_heavy, i_slabs, i_heavy, lam, n_iters):
        def body(_, carry):
            _x, _y = carry
            _x = solve_u(_y, u_slabs, u_heavy, lam)
            _y = solve_i(_x, i_slabs, i_heavy, lam)
            return (_x, _y)

        return jax.lax.fori_loop(0, n_iters, body, (x, y))

    return run


# --------------------------------------------------------------------------
# Model-sharded training (factor matrices sharded over MODEL_AXIS)
# --------------------------------------------------------------------------
#
# The reference blocks the user/item factor RDDs across the cluster
# (examples/scala-parallel-recommendation/custom-query/src/main/scala/
# ALSModel.scala:10-12; MLlib ALS blocks by user/item). The TPU-native
# equivalent: factor matrices live sharded over the ``model`` mesh axis
# (persistent HBM per device drops model_parallelism×), stats rows are
# split over ALL devices (data×model — every chip solves normal
# equations), and the only collectives per half-iteration are two
# all-gathers: the opposite side's factor slices (needed for the slab
# gather) and the solved stats rows (resharded back to factor layout).
# An all-gather of the factor slices beats a psum of partial Gramians
# here: it moves I·k floats instead of R·k² and doesn't duplicate the
# Gramian einsum per model shard.


@dataclasses.dataclass
class ShardPlan:
    """Device-major layout for one solve direction under shard_map.

    ``shard_map`` sees each slab row-split over the combined
    (data, model) axes, so the concatenated stats layout becomes
    device-major: device ``i`` holds rows ``[i*c_local, (i+1)*c_local)``
    of the all-gathered stats. ``inv_perm_dm`` re-expresses
    :attr:`Bucketed.inv_perm` in that layout. Heavy sub-rows are
    regrouped so every sub-row's owner slot lives on the same device
    (``heavy_owner_local`` is a device-local stats position), which
    keeps the heavy scatter-add device-local.
    """

    heavy: Slab | None                    # regrouped per-shard heavy slab
    heavy_owner_local: np.ndarray | None  # [rows] int32 — local stats pos
    inv_perm_dm: np.ndarray               # [n_rows_padded] int32
    c_local: int                          # stats rows per device
    n_heavy_slots_local: int              # heavy stat slots per device
    n_shards: int


def plan_shards(packed: Bucketed, n_shards: int) -> ShardPlan:
    """Host-side layout planning for the model-sharded solver."""
    rbs = [s.idx.shape[0] for s in packed.slabs]
    per = []
    for rb in rbs:
        if rb % n_shards:
            raise ValueError(
                "slab rows not divisible by n_shards; "
                "build_bucketed with row_multiple=n_shards"
            )
        per.append(rb // n_shards)
    c_slab = int(sum(per))
    n_slab_rows = int(sum(rbs))
    slab_ends = np.cumsum(rbs)
    offsets_global = np.concatenate([[0], slab_ends[:-1]])
    local_off = np.concatenate([[0], np.cumsum(per)[:-1]]).astype(np.int64)
    per_arr = np.asarray(per, np.int64)

    heavy_out = None
    owner_local = None
    h_slots_per = 0
    slot_local: dict[int, tuple[int, int]] = {}
    heavy = None
    if packed.heavy:
        # regrouping is by owner anyway: merge the slot-capped groups
        # back into one host-side slab first
        heavy = Slab(
            idx=np.concatenate([h.idx for h in packed.heavy]),
            weights=np.concatenate([h.weights for h in packed.heavy]),
            valid=np.concatenate([h.valid for h in packed.heavy]),
        )
        owner_all = np.concatenate(packed.heavy_owner_pos)
    if heavy is not None:
        real = heavy.valid.any(axis=1)
        real_rows = np.nonzero(real)[0]
        owners_glob = owner_all[real_rows].astype(np.int64)
        slots, slot_counts = np.unique(owners_glob, return_counts=True)
        # greedy balance: heaviest slot first onto the lightest shard
        shard_sub = np.zeros(n_shards, np.int64)
        shard_slots: list[list[int]] = [[] for _ in range(n_shards)]
        for t in np.argsort(-slot_counts):
            i = int(np.argmin(shard_sub))
            shard_sub[i] += slot_counts[t]
            shard_slots[i].append(int(slots[t]))
        h_slots_per = max(len(s) for s in shard_slots)
        rb_h_per = int(shard_sub.max())
        width = heavy.idx.shape[1]
        h_idx = np.zeros((n_shards * rb_h_per, width), np.int32)
        h_wt = np.zeros((n_shards * rb_h_per, width), np.float32)
        h_vd = np.zeros((n_shards * rb_h_per, width), np.float32)
        owner_local = np.zeros(n_shards * rb_h_per, np.int32)
        for i in range(n_shards):
            fill = 0
            for t_local, slot in enumerate(shard_slots[i]):
                slot_local[slot] = (i, t_local)
                rows_sel = real_rows[owners_glob == slot]
                n = len(rows_sel)
                dst = i * rb_h_per + fill
                h_idx[dst:dst + n] = heavy.idx[rows_sel]
                h_wt[dst:dst + n] = heavy.weights[rows_sel]
                h_vd[dst:dst + n] = heavy.valid[rows_sel]
                owner_local[dst:dst + n] = c_slab + t_local
                fill += n
        heavy_out = Slab(idx=h_idx, weights=h_wt, valid=h_vd)
    c_local = c_slab + h_slots_per

    inv = packed.inv_perm.astype(np.int64)
    inv_dm = np.zeros_like(inv)
    is_reg = inv < n_slab_rows
    pos = inv[is_reg]
    slab_of = np.searchsorted(slab_ends, pos, side="right")
    j = pos - offsets_global[slab_of]
    shard = j // per_arr[slab_of]
    local = local_off[slab_of] + (j % per_arr[slab_of])
    inv_dm[is_reg] = shard * c_local + local
    for e in np.nonzero(~is_reg)[0]:
        i, t_local = slot_local[int(inv[e])]
        inv_dm[e] = i * c_local + c_slab + t_local
    return ShardPlan(
        heavy=heavy_out,
        heavy_owner_local=owner_local,
        inv_perm_dm=inv_dm.astype(np.int32),
        c_local=c_local,
        n_heavy_slots_local=h_slots_per,
        n_shards=n_shards,
    )


@dataclasses.dataclass
class ShardedSide:
    """Device-staged arrays for one solve direction (sharded mode)."""

    slabs: tuple            # ((idx, weights, valid), ...) — P((data,model))
    heavy: tuple            # () or (idx, weights, valid, owner_local)
    inv: jax.Array          # [n_rows_padded] int32 — P(model)
    n_heavy_slots_local: int


def stage_sharded(
    ctx: ComputeContext, packed: Bucketed, plan: ShardPlan
) -> ShardedSide:
    """Stage one direction's sharded geometry per the ALS rule table
    (``partition.ALS_SHARDED_RULES``): slab rows split over the combined
    (data, model) axes, the heavy owner map with its slab, the
    device-major permutation over ``model``. Rule→axis validation runs
    here (at staging), mirroring the static sharding-spec lint."""
    tree: dict = {"slabs": _slab_tree(packed.slabs)}
    if plan.heavy is not None:
        tree["heavy"] = {
            "idx": plan.heavy.idx,
            "weights": plan.heavy.weights,
            "valid": plan.heavy.valid,
            "owner": plan.heavy_owner_local,
        }
    tree["inv_perm"] = plan.inv_perm_dm
    placed = partition.shard_pytree(
        ctx, partition.ALS_SHARDED_RULES, tree
    )
    heavy: tuple = ()
    if plan.heavy is not None:
        h = placed["heavy"]
        heavy = (h["idx"], h["weights"], h["valid"], h["owner"])
    return ShardedSide(
        slabs=_slab_tuples(placed["slabs"]),
        heavy=heavy,
        inv=placed["inv_perm"],
        n_heavy_slots_local=plan.n_heavy_slots_local,
    )


@jax.named_scope("all_gather")
def _all_gather_factors(loc, compute):
    """The opposite side's factor rows from every model shard, cast to
    the compute dtype BEFORE the collective (half the bytes on the
    wire)."""
    return lax.all_gather(
        loc.astype(compute) if compute is not None else loc,
        MODEL_AXIS, axis=0, tiled=True,
    )


def _sharded_half(
    y_full, side_slabs, side_heavy, inv_local, n_heavy_local,
    implicit, alpha, lam, compute=None, gather_layout="kminor",
):
    """One solve direction, written per-device (shard_map body).

    ``y_full`` is the all-gathered opposite factors; slab rows are this
    device's share of the (data×model)-split stats rows. Returns this
    device's model-shard rows of the new factor matrix. Heavy owner
    slots are device-local stats positions by construction (ShardPlan),
    so the scatter-add needs no collective.
    """
    heavy_groups = [side_heavy] if side_heavy else []
    x_stats = _assemble_and_solve(
        y_full, side_slabs, heavy_groups, n_heavy_local,
        implicit, alpha, lam, compute, gather_layout,
    )
    # device-major reassembly: model (minor) then data (major) matches
    # the P((data, model)) row split of the slabs
    with jax.named_scope("all_gather"):
        xs = lax.all_gather(x_stats, MODEL_AXIS, axis=0, tiled=True)
        xs = lax.all_gather(xs, DATA_AXIS, axis=0, tiled=True)
    return jnp.take(xs, inv_local, axis=0)


def _sharded_specs(side: ShardedSide):
    rows = P((DATA_AXIS, MODEL_AXIS), None)
    slab_specs = tuple((rows, rows, rows) for _ in side.slabs)
    heavy_specs: tuple = ()
    if side.heavy:
        heavy_specs = (rows, rows, rows, P((DATA_AXIS, MODEL_AXIS)))
    return slab_specs, heavy_specs


def make_sharded_train_step(
    ctx: ComputeContext,
    u_side: ShardedSide,
    i_side: ShardedSide,
    implicit: bool,
    alpha: float,
    compute_dtype: str | None = None,
):
    """Fused multi-epoch trainer with model-sharded factor matrices.

    Returned fn: ``(x, y, lam, n_iters) → (x, y)`` where ``x``/``y``
    carry sharding ``P(model)`` — each device holds a
    ``1/model_parallelism`` row slice persistently.
    """
    mesh = ctx.mesh
    u_slab_specs, u_heavy_specs = _sharded_specs(u_side)
    i_slab_specs, i_heavy_specs = _sharded_specs(i_side)
    u_nh = u_side.n_heavy_slots_local
    i_nh = i_side.n_heavy_slots_local
    compute = _resolve_compute(compute_dtype)
    gather_layout = _resolve_gather_layout()

    # the factor in/out contract comes from the SAME rule table that
    # staged the geometry: each carry is a true NamedSharding over
    # P(model) — inputs are pinned with a sharding constraint (a
    # mis-sharded caller reshards once instead of silently replicating
    # through the whole epoch chain) and outputs are pinned via
    # out_shardings so the solve→scatter layout survives the jit edge
    factor_sharding = NamedSharding(
        mesh,
        partition.match_partition_rule(
            partition.ALS_SHARDED_RULES, "user_factors"
        ),
    )

    # donate the sharded factor carries like the replicated path: each
    # device's P(model) row slice is reused in place across the fused
    # epoch chain. CPU backends have no donation support.
    donate = (0, 1) if jax.default_backend() != "cpu" else ()

    @partial(
        jax.jit,
        static_argnames=("n_iters",),
        donate_argnums=donate,
        out_shardings=(factor_sharding, factor_sharding),
    )
    def _run(x, y, u_slabs_a, u_heavy_a, u_inv_a,
             i_slabs_a, i_heavy_a, i_inv_a, lam, n_iters):
        x = lax.with_sharding_constraint(x, factor_sharding)
        y = lax.with_sharding_constraint(y, factor_sharding)
        def body(x_loc, y_loc, u_slabs, u_heavy, u_inv,
                 i_slabs, i_heavy, i_inv, lam_):
            def it(_, carry):
                xl, yl = carry
                y_full = _all_gather_factors(yl, compute)
                xl = _sharded_half(
                    y_full, u_slabs, u_heavy, u_inv, u_nh,
                    implicit, alpha, lam_, compute, gather_layout,
                )
                x_full = _all_gather_factors(xl, compute)
                yl = _sharded_half(
                    x_full, i_slabs, i_heavy, i_inv, i_nh,
                    implicit, alpha, lam_, compute, gather_layout,
                )
                return xl, yl

            return lax.fori_loop(0, n_iters, it, (x_loc, y_loc))

        f = shard_map(
            body,
            mesh=mesh,
            in_specs=(
                P(MODEL_AXIS, None), P(MODEL_AXIS, None),
                u_slab_specs, u_heavy_specs, P(MODEL_AXIS),
                i_slab_specs, i_heavy_specs, P(MODEL_AXIS),
                P(),
            ),
            out_specs=(P(MODEL_AXIS, None), P(MODEL_AXIS, None)),
        )
        return f(
            x, y, u_slabs_a, u_heavy_a, u_inv_a,
            i_slabs_a, i_heavy_a, i_inv_a, lam,
        )

    def run(x, y, lam, n_iters):
        # the staged side arrays enter as jit ARGUMENTS, not closure
        # captures: jit may not close over arrays spanning another
        # process's devices, and multi-host meshes are the point here
        return _run(
            x, y, u_side.slabs, u_side.heavy, u_side.inv,
            i_side.slabs, i_side.heavy, i_side.inv, lam,
            n_iters=n_iters,
        )

    return run


def make_sharded_half_step(
    ctx: ComputeContext, side: ShardedSide, implicit: bool, alpha: float,
    compute_dtype: str | None = None,
):
    """Single-direction sharded solve: ``(y, lam) → x`` (both P(model))."""
    mesh = ctx.mesh
    slab_specs, heavy_specs = _sharded_specs(side)
    nh = side.n_heavy_slots_local
    compute = _resolve_compute(compute_dtype)
    gather_layout = _resolve_gather_layout()
    factor_sharding = NamedSharding(
        mesh,
        partition.match_partition_rule(
            partition.ALS_SHARDED_RULES, "user_factors"
        ),
    )

    @partial(jax.jit, out_shardings=factor_sharding)
    def _solve(y, slabs_a, heavy_a, inv_a, lam):
        y = lax.with_sharding_constraint(y, factor_sharding)
        def body(y_loc, slabs, heavy, inv, lam_):
            y_full = _all_gather_factors(y_loc, compute)
            return _sharded_half(
                y_full, slabs, heavy, inv, nh, implicit, alpha, lam_,
                compute, gather_layout,
            )

        f = shard_map(
            body,
            mesh=mesh,
            in_specs=(
                P(MODEL_AXIS, None), slab_specs, heavy_specs,
                P(MODEL_AXIS), P(),
            ),
            out_specs=P(MODEL_AXIS, None),
        )
        return f(y, slabs_a, heavy_a, inv_a, lam)

    def solve_once(y, lam):
        # side arrays as jit arguments, not closure captures (multi-
        # host meshes forbid closing over non-addressable arrays)
        return _solve(y, side.slabs, side.heavy, side.inv, lam)

    return solve_once


def check_factor_sharding(
    ctx: ComputeContext,
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    n_users: int,
    n_items: int,
    rank: int = 8,
    block_len: int = 8,
) -> None:
    """Validation probe: run one sharded training step and assert the
    factor matrices are genuinely split over MODEL_AXIS — each device
    holds exactly a ``1/model_parallelism`` row slice (not a replicated
    copy). Used by the test suite and the driver's multichip dryrun.
    """
    n_dev = ctx.n_devices
    up = build_bucketed(rows, cols, vals, n_users, block_len=block_len,
                        row_multiple=n_dev)
    ip = build_bucketed(cols, rows, vals, n_items, block_len=block_len,
                        row_multiple=n_dev)
    u_side = stage_sharded(ctx, up, plan_shards(up, n_dev))
    i_side = stage_sharded(ctx, ip, plan_shards(ip, n_dev))
    run = make_sharded_train_step(ctx, u_side, i_side, True, 1.0)
    place = ctx.sharding(MODEL_AXIS)
    x = jax.device_put(
        np.zeros((up.n_rows_padded, rank), np.float32), place
    )
    y = jax.device_put(
        np.ones((ip.n_rows_padded, rank), np.float32), place
    )
    x, y = run(x, y, jnp.float32(0.1), n_iters=1)
    m_par = max(ctx.model_parallelism, 1)
    for arr, n_pad in ((x, up.n_rows_padded), (y, ip.n_rows_padded)):
        shard_rows = {s.data.shape[0] for s in arr.addressable_shards}
        if shard_rows != {n_pad // m_par}:
            raise AssertionError(
                f"factors not model-sharded: shard rows {shard_rows}, "
                f"expected {{{n_pad // m_par}}}"
            )


# --------------------------------------------------------------------------
# Training loop
# --------------------------------------------------------------------------


@dataclasses.dataclass
class ALSFactors:
    """Trained factor matrices.

    Host layout (default): unpadded numpy, ``[n_users, k]`` /
    ``[n_items, k]``. Device layout (``train_als(...,
    return_layout="device")``): the PADDED, device-resident (possibly
    model-sharded) ``jax.Array`` carries exactly as the fused epoch
    chain left them — the unbroken train→serve path; ``n_users`` /
    ``n_items`` give the real row counts, rows past them are exact-zero
    phantoms (asserted centrally before return).
    """

    user_factors: np.ndarray | jax.Array
    item_factors: np.ndarray | jax.Array
    n_users: int = 0
    n_items: int = 0


def _train_chaos_sleep_s() -> float:
    """Training-side chaos knob (mirrors the serving tier's
    ``PIO_CHAOS``): ``PIO_TRAIN_CHAOS=epoch_sleep:<seconds>`` stretches
    each epoch dispatch so preemption/kill-mid-train rehearsals
    (scripts/trainer_smoke.py) get a deterministic window to land in.
    Unset/garbage → 0 (no chaos in production paths)."""
    raw = os.environ.get("PIO_TRAIN_CHAOS", "").strip()
    for part in raw.split(";"):
        key, _, value = part.partition(":")
        if key.strip() == "epoch_sleep":
            try:
                return max(0.0, float(value))
            except ValueError:
                return 0.0
    return 0.0


def train_als(
    ctx: ComputeContext,
    user_ids: np.ndarray,
    item_ids: np.ndarray,
    values: np.ndarray,
    n_users: int,
    n_items: int,
    rank: int = 32,
    iterations: int = 10,
    reg: float = 0.01,
    alpha: float = 1.0,
    implicit: bool = True,
    seed: int = 13,
    block_len: int = 64,
    row_chunk: int = 1024,
    s_max: int = 16,
    max_slab_slots: int = 0,
    compute_dtype: str | None = None,
    dtype=jnp.float32,
    timer=None,
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 0,
    resume: bool = False,
    factor_sharding: str = "auto",
    return_layout: str = "host",
) -> ALSFactors:
    """Alternate user/item normal-equation solves on the mesh.

    Epochs run fused on-device (``checkpoint_every``-sized dispatch
    chunks when checkpointing, the whole run otherwise); passing a
    ``timer`` (:class:`~predictionio_tpu.utils.profiling.StepTimer`)
    switches to per-half-iteration dispatch so each solve direction is
    timed separately. Mid-training checkpoint/resume (SURVEY.md §5 —
    the reference only persists final models): with ``checkpoint_dir``
    + ``checkpoint_every`` the factor state is written every N
    iterations (atomic npz) and ``resume=True`` continues from the
    latest checkpoint after a restart. ``row_chunk`` is retained for
    call compatibility (the bucketed layout needs no chunked scan).

    ``compute_dtype`` ("bfloat16") runs the factor gather + Gramian
    einsums in bf16 — half the HBM traffic of the bandwidth-bound stage
    and double MXU rate; accumulation and the Cholesky solve stay f32
    (also settable via ``PIO_ALS_COMPUTE_DTYPE``).

    ``factor_sharding`` selects the factor-matrix layout: "replicated"
    keeps both factor matrices replicated per device (1D data meshes);
    "sharded" stores them split over ``MODEL_AXIS`` with stats rows
    split over all devices (the TPU-native equivalent of the
    reference's cluster-blocked factor RDDs, ALSModel.scala:10-12);
    "auto" picks "sharded" whenever the mesh has a model axis > 1.

    ``return_layout`` selects the output form: "host" (default)
    fetches unpadded numpy matrices; "device" returns the PADDED
    device-resident carries exactly as trained — model-sharded factors
    flow unbroken into serving (``Algorithm.stage_model`` /
    ``similarity.stage_factors`` pass resident arrays through), so one
    engine instance can serve a catalog that never fits a single
    chip's HBM. Both layouts assert the phantom-row invariant (padded
    rows solve to exact zeros) before returning.
    """
    del row_chunk
    if factor_sharding not in ("auto", "sharded", "replicated"):
        raise ValueError(
            f"factor_sharding must be 'auto', 'sharded' or 'replicated', "
            f"got {factor_sharding!r}"
        )
    if return_layout not in ("host", "device"):
        raise ValueError(
            f"return_layout must be 'host' or 'device', "
            f"got {return_layout!r}"
        )
    if return_layout == "device" and jax.process_count() > 1:
        raise NotImplementedError(
            "return_layout='device' is single-process only (other "
            "hosts' shards are not addressable here); use the default "
            "host layout on multi-host meshes"
        )
    sharded = factor_sharding == "sharded" or (
        factor_sharding == "auto" and ctx.model_parallelism > 1
    )
    row_multiple = ctx.n_devices if sharded else ctx.data_parallelism

    user_packed = build_bucketed(
        user_ids, item_ids, values, n_users,
        block_len=block_len, row_multiple=row_multiple, s_max=s_max,
        max_slab_slots=max_slab_slots,
    )
    item_packed = build_bucketed(
        item_ids, user_ids, values, n_items,
        block_len=block_len, row_multiple=row_multiple, s_max=s_max,
        max_slab_slots=max_slab_slots,
    )

    # init at the logical item count (mesh-size independent), zero padding
    # rows so phantom items contribute nothing to YtY
    key = jax.random.PRNGKey(seed)
    init = np.asarray(
        jax.random.normal(key, (n_items, rank), dtype)
    ) * (1.0 / math.sqrt(rank))
    start_iteration = 0
    ckpt_path = (
        os.path.join(checkpoint_dir, "als_checkpoint.npz")
        if checkpoint_dir
        else None
    )
    resumed_user_factors = None
    if resume and ckpt_path and os.path.exists(ckpt_path):
        try:
            with np.load(ckpt_path) as ckpt:
                if (
                    ckpt["item_factors"].shape == (n_items, rank)
                    and ckpt["user_factors"].shape == (n_users, rank)
                    and int(ckpt["iteration"]) <= iterations
                ):
                    init = ckpt["item_factors"]
                    start_iteration = int(ckpt["iteration"])
                    resumed_user_factors = ckpt["user_factors"]
                    logger.info(
                        "resuming ALS from checkpoint at iteration %d",
                        start_iteration,
                    )
        except Exception as e:  # noqa: BLE001 - damaged ckpt = cold start
            # a truncated/corrupt checkpoint (np.load raises BadZipFile,
            # not OSError) must degrade to a from-scratch train, never
            # crash-loop the resuming trainer
            logger.warning(
                "checkpoint %s unreadable (%s); training from scratch",
                ckpt_path, e,
            )
            start_iteration = 0
            resumed_user_factors = None
    if resume and ckpt_path and jax.process_count() > 1:
        # Checkpoints are written by rank 0 only; with a host-local
        # checkpoint_dir the other ranks see no file. Divergent resume
        # state means divergent collective schedules (deadlock), so
        # rank 0's view is broadcast and is authoritative — ranks that
        # found a stale local file discard it.
        from jax.experimental import multihost_utils as _mhu

        state = _mhu.broadcast_one_to_all(
            np.array(
                [int(resumed_user_factors is not None), start_iteration],
                np.int32,
            )
        )
        if int(state[0]):
            base = np.asarray(init).dtype
            have = resumed_user_factors is not None
            init = _mhu.broadcast_one_to_all(
                np.asarray(init, base)
                if have
                else np.zeros((n_items, rank), base)
            )
            resumed_user_factors = _mhu.broadcast_one_to_all(
                np.asarray(resumed_user_factors, base)
                if have
                else np.zeros((n_users, rank), base)
            )
            start_iteration = int(state[1])
        else:
            if resumed_user_factors is not None:
                # this rank loaded a stale local file rank 0 never saw:
                # back to the (seed-deterministic) cold init
                init = np.asarray(
                    jax.random.normal(key, (n_items, rank), dtype)
                ) * (1.0 / math.sqrt(rank))
            start_iteration = 0
            resumed_user_factors = None
    item_factors = np.zeros(
        (item_packed.n_rows_padded, rank), np.asarray(init).dtype
    )
    item_factors[:n_items] = init
    # factor placement comes from the same rule table that stages the
    # geometry and pins the train step's in/out specs — one source of
    # layout truth per mode (docs/parallelism.md partition-rule table)
    rules = partition.als_partition_rules(sharded)
    partition.validate_rules(rules, ctx.mesh)
    factor_place = NamedSharding(
        ctx.mesh, partition.match_partition_rule(rules, "item_factors")
    )
    item_factors = jax.device_put(item_factors, factor_place)
    user_factors = jax.device_put(
        np.zeros((user_packed.n_rows_padded, rank), np.asarray(init).dtype),
        factor_place,
    )
    lam = jnp.asarray(reg, dtype)

    multiprocess = sharded and jax.process_count() > 1
    gather = (
        jax.jit(lambda a: a, out_shardings=ctx.replicated)
        if multiprocess
        else None
    )

    def fetch(arr) -> np.ndarray:
        """Host copy of a (possibly model-sharded) global factor array.
        On a multi-process mesh some model shards live on other hosts'
        devices and are not addressable here; a jitted identity with
        replicated out_shardings inserts the all-gather first (the
        ``multihost_utils.process_allgather`` pattern), after which
        every process holds the full matrix. The jitted identity is
        hoisted so repeated fetches (checkpoints) hit the compile
        cache. Collective: every process must call it."""
        if gather is not None:
            arr = gather(arr)
        return np.asarray(arr)

    # jit is lazy, so constructing the half-step solvers up front costs
    # nothing unless they are actually called (timer / edge paths)
    if sharded:
        u_side = stage_sharded(
            ctx, user_packed, plan_shards(user_packed, ctx.n_devices)
        )
        i_side = stage_sharded(
            ctx, item_packed, plan_shards(item_packed, ctx.n_devices)
        )
        solve_u_half = make_sharded_half_step(
            ctx, u_side, implicit, alpha, compute_dtype
        )
        solve_i_half = make_sharded_half_step(
            ctx, i_side, implicit, alpha, compute_dtype
        )
        _run = make_sharded_train_step(
            ctx, u_side, i_side, implicit, alpha, compute_dtype
        )

        def step(x, y, n):
            return _run(x, y, lam, n_iters=n)
    else:
        u_slabs, u_heavy = _device_slabs(ctx, user_packed)
        i_slabs, i_heavy = _device_slabs(ctx, item_packed)
        _su = make_solve_side(ctx, user_packed, implicit, alpha, compute_dtype)
        _si = make_solve_side(ctx, item_packed, implicit, alpha, compute_dtype)

        def solve_u_half(y, lam_):
            return _su(y, u_slabs, u_heavy, lam_)

        def solve_i_half(x, lam_):
            return _si(x, i_slabs, i_heavy, lam_)

        _run = make_train_step(
            ctx, user_packed, item_packed, implicit, alpha, compute_dtype
        )

        def step(x, y, n):
            return _run(
                x, y, u_slabs, u_heavy, i_slabs, i_heavy, lam, n_iters=n
            )

    ran_any = False
    chaos_sleep = _train_chaos_sleep_s()
    if timer is not None:
        # profiling mode: dispatch each half-iteration separately
        for it in range(start_iteration, iterations):
            if chaos_sleep:
                time.sleep(chaos_sleep)
            with timer.step("als/user_solve", sync_value=None):
                user_factors = solve_u_half(item_factors, lam)
                profiling.sync(user_factors)
            with timer.step("als/item_solve", sync_value=None):
                item_factors = solve_i_half(user_factors, lam)
                profiling.sync(item_factors)
            ran_any = True
            _maybe_checkpoint(
                ckpt_path, checkpoint_every, it + 1, iterations,
                user_factors, item_factors, n_users, n_items,
                gather=gather,
            )
    else:
        checkpointing = bool(ckpt_path) and checkpoint_every > 0
        chunk = (
            checkpoint_every
            if checkpointing
            else max(iterations - start_iteration, 1)
        )
        it = start_iteration
        while it < iterations:
            # align chunk boundaries to absolute multiples of
            # checkpoint_every so resuming from a foreign iteration
            # count still checkpoints on schedule; without
            # checkpointing a resume runs as one fused dispatch
            if checkpointing:
                n = min(chunk - it % chunk, iterations - it)
            else:
                n = min(chunk, iterations - it)
            if chaos_sleep:
                time.sleep(chaos_sleep)
            user_factors, item_factors = step(user_factors, item_factors, n)
            it += n
            ran_any = True
            _maybe_checkpoint(
                ckpt_path, checkpoint_every, it, iterations,
                user_factors, item_factors, n_users, n_items,
                gather=gather,
            )

    if not ran_any:
        # loop never ran (iterations == 0, or resume at full count):
        # use the checkpointed user factors if any, else solve once
        if resumed_user_factors is not None:
            if return_layout == "device":
                # the device-layout contract (padded, device-resident,
                # factor-rule placement) holds on the resume-complete
                # path too — pad the checkpointed host factors back to
                # the mesh shape and commit them like the cold init
                padded_u = np.zeros(
                    (user_packed.n_rows_padded, rank),
                    np.asarray(resumed_user_factors).dtype,
                )
                padded_u[:n_users] = resumed_user_factors[:n_users]
                return ALSFactors(
                    user_factors=jax.device_put(padded_u, factor_place),
                    item_factors=item_factors,
                    n_users=n_users,
                    n_items=n_items,
                )
            item_full = fetch(item_factors)
            assert_phantom_rows_zero(item_full, n_items, "item factors")
            return ALSFactors(
                user_factors=resumed_user_factors[:n_users],
                item_factors=item_full[:n_items],
                n_users=n_users,
                n_items=n_items,
            )
        user_factors = solve_u_half(item_factors, lam)
    if return_layout == "device":
        # the phantom-row invariant still holds on-device: fetch ONLY
        # the padded tails (cheap — at most row_multiple-1 rows/side)
        assert_phantom_rows_zero(
            jax.device_get(user_factors[n_users:]), 0, "user factors"
        )
        assert_phantom_rows_zero(
            jax.device_get(item_factors[n_items:]), 0, "item factors"
        )
        return ALSFactors(
            user_factors=user_factors,
            item_factors=item_factors,
            n_users=n_users,
            n_items=n_items,
        )
    user_full = fetch(user_factors)
    item_full = fetch(item_factors)
    assert_phantom_rows_zero(user_full, n_users, "user factors")
    assert_phantom_rows_zero(item_full, n_items, "item factors")
    return ALSFactors(
        user_factors=user_full[:n_users],
        item_factors=item_full[:n_items],
        n_users=n_users,
        n_items=n_items,
    )


def _maybe_checkpoint(
    ckpt_path, checkpoint_every, iteration, total,
    user_factors, item_factors, n_users, n_items,
    gather=None,
) -> None:
    if (
        ckpt_path
        and checkpoint_every > 0
        and iteration % checkpoint_every == 0
        and iteration < total
    ):
        # gather() is the collective — every process runs it — but the
        # device→host copy and the write are rank-0-only: N hosts
        # racing os.replace on one shared-fs path would corrupt the
        # checkpoint, and non-writers materializing hundreds of MB of
        # host factors per checkpoint is pure waste
        if gather is not None:
            item_factors = gather(item_factors)
            user_factors = gather(user_factors)
        if jax.process_index() == 0:
            # the checkpoint is part of the training trace timeline AND
            # the telemetry registry, so `pio-tpu status --metrics-url`
            # on a trainer shows how many restore points it has banked
            from predictionio_tpu.obs import get_registry, tracing

            with tracing.span(
                "als/checkpoint", iteration=iteration, total=total
            ):
                _write_checkpoint(
                    ckpt_path,
                    iteration=iteration,
                    item_factors=np.asarray(item_factors)[:n_items],
                    user_factors=np.asarray(user_factors)[:n_users],
                )
            get_registry().counter(
                "pio_train_checkpoints_total",
                "Mid-training factor checkpoints written (atomic npz; "
                "resume picks up the latest after a crash)",
            ).inc()


def _write_checkpoint(path: str, **arrays) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp.npz"  # .npz suffix keeps np.savez from renaming
    np.savez(tmp, **arrays)
    # fsync before the rename: a restore point that evaporates on power
    # loss is not a restore point (same discipline as the model store's
    # atomic_write_bytes)
    with open(tmp, "rb") as f:
        os.fsync(f.fileno())
    os.replace(tmp, path)


def checkpoint_path(checkpoint_dir: str) -> str:
    """The checkpoint file :func:`train_als` writes/resumes under a
    given ``checkpoint_dir`` — shared so supervisors (the continuous
    trainer) can observe resume state without duplicating the name."""
    return os.path.join(checkpoint_dir, "als_checkpoint.npz")


def peek_checkpoint_iteration(checkpoint_dir: str | None) -> int:
    """Iteration recorded in the latest checkpoint (0 = none/unreadable)
    — what a ``resume=True`` run will continue from. Used by the
    continuous trainer to record crash-resume provenance."""
    if not checkpoint_dir:
        return 0
    path = checkpoint_path(checkpoint_dir)
    try:
        with np.load(path) as ckpt:
            return int(ckpt["iteration"])
    except Exception:  # noqa: BLE001 - np.load raises BadZipFile on a
        # truncated npz (not OSError); "0 = none/unreadable" is the
        # contract, never a crash-looping supervisor tick
        return 0


# --------------------------------------------------------------------------
# Incremental fold-in (continuous training)
# --------------------------------------------------------------------------


def fold_in_users(
    item_factors: np.ndarray,
    user_rows: np.ndarray,
    item_cols: np.ndarray,
    values: np.ndarray,
    n_new_users: int,
    reg: float = 0.01,
    alpha: float = 1.0,
    implicit: bool = True,
) -> np.ndarray:
    """Solve factors for NEW users against a FIXED item matrix.

    The continuous-training fast path (ROADMAP "continuous training"):
    a cold-start user needs one ``k×k`` normal-equation solve — exactly
    one ALS half-iteration restricted to their rows — not a full
    retrain. Same math as :func:`_slab_stats` + :func:`_solve`
    (implicit: ``A = YtY + Σ αw·y·yᵀ + λI``, ``b = Σ (1+αw)·y``;
    explicit: ``A = Σ y·yᵀ + λ·n·I``, ``b = Σ r·y``), run on host
    numpy — fold-ins touch a handful of rows, far below device
    dispatch overhead. ``user_rows`` index the new users ``[0,
    n_new_users)``; ``item_cols`` index into ``item_factors``. Users
    with no in-range interactions (all their items unseen) get zero
    factors. Non-finite solves degrade to zeros, never NaN factors.

    Symmetric item fold-in is the same call with roles swapped.
    """
    y = np.asarray(item_factors, np.float32)
    k = y.shape[1]
    out = np.zeros((n_new_users, k), np.float32)
    rows = np.asarray(user_rows, np.int64)
    cols = np.asarray(item_cols, np.int64)
    vals = np.asarray(values, np.float32)
    keep = (cols >= 0) & (cols < len(y)) & (rows >= 0) & (
        rows < n_new_users
    )
    rows, cols, vals = rows[keep], cols[keep], vals[keep]
    if len(rows) == 0:
        return out
    yty = y.T @ y if implicit else None
    eye = np.eye(k, dtype=np.float32)
    for u in np.unique(rows):
        sel = rows == u
        yu = y[cols[sel]]                       # [n_u, k]
        w = vals[sel]
        if implicit:
            a = yty + (yu * (alpha * w)[:, None]).T @ yu + reg * eye
            b = ((1.0 + alpha * w)[:, None] * yu).sum(axis=0)
        else:
            a = yu.T @ yu + reg * max(len(w), 1) * eye
            b = (w[:, None] * yu).sum(axis=0)
        try:
            x = np.linalg.solve(a, b)
        except np.linalg.LinAlgError:
            continue
        if np.all(np.isfinite(x)):
            out[int(u)] = x
    return out
