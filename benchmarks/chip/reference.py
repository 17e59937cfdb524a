"""The plain reference of the serve cells, and the comparison that decides
`correct`. numpy only: nothing of the program, nothing the program made.

A query {"user": u, "num": n} against a tenant with user table U and item
table V answers the n items with the largest U[u] . V[j], with those dot
products as scores. The reference computes every score in float64 from the
seeded float32 tables and compares what the server sent:

- ``score_rms``: root mean square of (served score - reference score of the
  served item), over all served (item, score) pairs of the sample, as a
  share of the root mean square of the reference's own top scores. Steady
  from seed to seed; it is the number that separates a lower precision.
- ``rank_gap_rms``: root mean square, over the same pairs, of the gap by
  which the reference score of the item served at rank r lies below the
  reference's r-th best score, as a share of that query's best score. An
  item from a wrong tenant or user, or an altered one, has a gap of about
  1, so one such answer in a sample of 5,000 pairs reads 0.014 alone.
- ``bad_answers``: sampled answers that are not ``num`` distinct known
  items with finite, non-increasing scores. The limit is 0.

The control (`control_answers`) is this reference put in the program's
place with both tables taken through a lower precision, the step below
the one a configuration states.
"""

from __future__ import annotations

import math

import numpy as np


PRECISIONS = ("bf16", "fp8", "int8", "int4")


def quantize_rows(x: np.ndarray, precision: str) -> np.ndarray:
    """``x`` as float32 after a round trip through ``precision``: a cast
    for bf16 and fp8 (e4m3), symmetric per-row quantization with a float32
    scale for int8 and int4."""
    x = np.asarray(x, np.float32)
    if precision in ("bf16", "fp8"):
        import ml_dtypes

        kind = {"bf16": ml_dtypes.bfloat16, "fp8": ml_dtypes.float8_e4m3fn}
        return x.astype(kind[precision]).astype(np.float32)
    top = float(2 ** (int(precision[3:]) - 1) - 1)
    amax = np.max(np.abs(x), axis=1, keepdims=True)
    scale = np.where(amax > 0, amax / top, 1.0).astype(np.float32)
    return (np.clip(np.rint(x / scale), -top, top) * scale).astype(np.float32)


def reference_scores(user_rows: np.ndarray, items: np.ndarray) -> np.ndarray:
    """[Q, I] float64 scores of every item for each user row."""
    return np.asarray(user_rows, np.float64) @ np.asarray(items, np.float64).T


def top_k(scores: np.ndarray, num: int):
    """(indices, scores) of the ``num`` largest per row, best first."""
    part = np.argpartition(scores, -num, axis=1)[:, -num:]
    part_scores = np.take_along_axis(scores, part, axis=1)
    order = np.argsort(-part_scores, axis=1, kind="stable")
    idx = np.take_along_axis(part, order, axis=1)
    return idx, np.take_along_axis(scores, idx, axis=1)


def control_answers(users, items, user_idx, num: int, precision: str):
    """The control's answers: top ``num`` of the quantized tables' scores,
    as ``[(item indices, scores)]`` per query."""
    scores = reference_scores(
        quantize_rows(users[user_idx], precision),
        quantize_rows(items, precision),
    )
    idx, top = top_k(scores, num)
    return [(idx[q], top[q]) for q in range(len(user_idx))]


class Comparison:
    """Accumulates the numbers over a sample of answers."""

    def __init__(self, num: int):
        self.num = num
        self.sq_err = 0.0
        self.sq_ref = 0.0
        self.pairs = 0
        self.sq_gap = 0.0
        self.bad_answers = 0
        self.answers = 0

    def add(self, users, items, user_idx, answers) -> None:
        """``answers[q]`` is ``(item indices, scores)`` as served for the
        query of user ``user_idx[q]``, or None where the answer is
        malformed."""
        scores = reference_scores(users[np.asarray(user_idx)], items)
        _, best = top_k(scores, self.num)
        for q, answer in enumerate(answers):
            self.answers += 1
            if answer is None:
                self.bad_answers += 1
                continue
            idx, served = (np.asarray(a) for a in answer)
            if (
                len(idx) != self.num
                or len(set(idx.tolist())) != self.num
                or idx.min() < 0 or idx.max() >= scores.shape[1]
                or not np.all(np.isfinite(served))
                or np.any(np.diff(served) > 0)
            ):
                self.bad_answers += 1
                continue
            ref = scores[q, idx]
            self.sq_err += float(np.sum((served - ref) ** 2))
            self.sq_ref += float(np.sum(best[q] ** 2))
            self.pairs += self.num
            gap = np.maximum(best[q] - ref, 0.0) / best[q, 0]
            self.sq_gap += float(np.sum(gap ** 2))

    def numbers(self) -> dict[str, float]:
        if not self.pairs:
            # nothing to compare is a failure, and stays valid JSON
            return {
                "score_rms": 1e30, "rank_gap_rms": 1e30,
                "bad_answers": float(self.bad_answers),
            }
        return {
            "score_rms": math.sqrt(self.sq_err / self.sq_ref),
            "rank_gap_rms": math.sqrt(self.sq_gap / self.pairs),
            "bad_answers": float(self.bad_answers),
        }


def judge(numbers: dict[str, float], limits: dict[str, float]):
    """``(correct, {name: [number, limit]})``: every limit has its number,
    and no number passes its limit."""
    compared = {k: [numbers[k], limits[k]] for k in limits}
    correct = all(
        math.isfinite(v) and v <= lim for v, lim in compared.values()
    )
    return correct, compared


def parse_answer(prediction, num: int):
    """A served prediction as ``(item indices, scores)``; None if it is not
    a list of ``num`` {"item": "i<j>", "score": s}."""
    try:
        rows = prediction["itemScores"]
        if len(rows) != num:
            return None
        idx = [int(r["item"][1:]) for r in rows if r["item"][0] == "i"]
        if len(idx) != num:
            return None
        return np.asarray(idx), np.asarray(
            [float(r["score"]) for r in rows], np.float64
        )
    except (KeyError, TypeError, ValueError, IndexError):
        return None
