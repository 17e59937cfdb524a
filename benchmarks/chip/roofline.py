"""Operations and bytes of the top-k step as the algorithm needs them, the
table of peaks, and the shares computed from them. The same whatever
implements the step."""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The chip's peaks; a device that is not in the table is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def topk_ops(batch: float, n_items: int, rank: int) -> float:
    """Multiply-adds of scoring ``batch`` users against every item."""
    return 2.0 * batch * n_items * rank


def topk_bytes(
    batch: float, n_items: int, rank: int, num: int,
    item_bytes_per_row: float, user_bytes_per_row: float,
) -> float:
    """The item table read once at its stored width, the gathered user
    rows, and the [batch, num] scores and indices written."""
    return (
        n_items * item_bytes_per_row
        + batch * user_bytes_per_row
        + batch * num * 8.0
    )


def table_row_bytes(rank: int, table_format: str) -> float:
    """Bytes of one stored row: f32, or int8 with one f32 scale."""
    return {"f32": 4.0 * rank, "int8": rank + 4.0}[table_format]


def roofline_seconds(ops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of operations over
    the bf16 peak (int8 tables are dequantized before the product) and
    bytes over the memory bandwidth."""
    return max(ops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])


def share_percent(least_seconds: float, measured_seconds: float) -> float | None:
    """A share of a roofline or a peak; nothing where nothing was measured."""
    if measured_seconds <= 0 or least_seconds <= 0:
        return None
    return 100.0 * least_seconds / measured_seconds
