"""Operations and bytes of the masked top-k step as the algorithm needs
them: scoring a batch against every item, with the rules' per-item data
read once and the batch's compact operands in. The same whatever
implements the step (an XLA program that writes the score matrix out and a
kernel that never does are held to the same count)."""

from __future__ import annotations


def masked_topk_ops(batch: float, n_items: int, rank: int) -> float:
    """Multiply-adds of scoring ``batch`` queries against every item; the
    rules' comparisons are not counted."""
    return 2.0 * batch * n_items * rank


def masked_topk_bytes(
    batch: float, n_items: int, rank: int, num: int, list_entries: float,
    categories_per_item: int = 1,
) -> float:
    """The f32 item table, the category ids (int32) and the unavailable
    bitmap (a bit an item) read once; the operands in (the packed lists'
    entries, and per query its user row, branch, recent views, category
    and list offset); the [batch, num] scores and indices out."""
    per_item = 4.0 * rank + 4.0 * categories_per_item + 1.0 / 8.0
    per_query = 4.0 * rank + 4.0 * (1 + 16 + 1 + 1) + 1.0
    return (
        n_items * per_item + 4.0 * list_entries + batch * per_query
        + batch * num * 8.0
    )
