"""Bytes and operations of the sparse scoring step as the algorithm needs
them for one batch: the table rows of the batch's distinct (query, bucket)
pairs, read at the table's tiled width; the pair operands in; the
[queries, C] scores out. The same whatever implements the step (a gather
and a segment sum, or a kernel that never writes the gathered rows out).
The step is bound by the memory: its operations are two a gathered entry."""

from __future__ import annotations

import math

#: lanes of a TPU vector register: a [d, C] f32 table's rows lie in
#: multiples of them
LANES = 128


def tiled_classes(n_classes: int) -> int:
    return LANES * math.ceil(n_classes / LANES)


def score_bytes(queries: float, pairs: float, slots: float, n_classes: int) -> float:
    """Gathered rows (``pairs`` of them) and the prior at the tiled width,
    the ``slots`` of (query, bucket, count) int32 operands, the f32 scores
    of the ``queries``."""
    row = 4.0 * tiled_classes(n_classes)
    return (pairs + 1.0 + queries) * row + 12.0 * slots


def score_ops(pairs: float, n_classes: int) -> float:
    """A multiply and an add for each gathered entry."""
    return 2.0 * pairs * n_classes
