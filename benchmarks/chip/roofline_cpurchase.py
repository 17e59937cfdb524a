"""Bytes and operations of the cart-scoring step as the algorithm needs
them for one batch: the table rows of the batch's distinct (cart, item)
pairs, read at the table's padded width, and their pair operands in; the
[queries, items] summed scores out. The same whatever implements the step
(a gather and a segment sum, or a kernel that never writes the gathered
rows out); padding pairs are waste, not work. The step is bound by the
memory: its operations are two a gathered entry."""

from __future__ import annotations

import math

#: lanes of a TPU vector register, and the widest panel of the table
#: (`ops/cooccurrence.layout`, written again here)
LANES, PANEL_LANES = 128, 195 * 128


def padded_items(n_items: int) -> int:
    """Lanes of a table row: the fewest panels of at most PANEL_LANES
    lanes, of one width a multiple of LANES."""
    lanes = max(1, math.ceil(n_items / LANES))
    panels = math.ceil(lanes / (PANEL_LANES // LANES))
    return panels * LANES * math.ceil(lanes / panels)


def score_bytes(queries: float, items: float, n_items: int) -> float:
    """Gathered rows (``items`` of them) and the f32 scores of the
    ``queries`` at the padded width, and the (cart, item) int32 operands."""
    return (items + queries) * 4.0 * padded_items(n_items) + 8.0 * items


def score_ops(items: float, n_items: int) -> float:
    """A scale and an add for each gathered entry."""
    return 2.0 * items * padded_items(n_items)
