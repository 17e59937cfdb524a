"""The plain reference of the complementary-purchase cell, and the
comparison that decides `correct`. numpy only, float64: nothing of the
program, nothing the program made.

A query {"items": cart, "num": n} against pair counts ``C`` over ``N``
baskets answers the n items j outside the cart with the largest positive
``sum over i in the cart of rule(i, j)``: ``rule`` is lift ``C[i,j] N /
(C[i,i] C[j,j])`` or confidence ``C[i,j] / C[i,i]``, and 0 where
``C[i,j]`` is under the support floor or j is i. :func:`pair_counts`
counts ``C`` from baskets, one basket at a time (the CPU tests' train
comparison); :class:`SeededTable` redraws the seeded model's rows from the
seed (`modelstore_cpurchase` draws the same integers on the device).

The comparison, over a sample of the answers the timed path produced
(scores relative, so ``tolerance`` is a share of a score):

- ``score_rel_max``: the largest |served - reference| / reference over
  every served item whose reference score is positive.
- ``item_mismatch``: served items whose reference score is not positive,
  or lies more than ``tolerance`` below the reference's n-th best.
- ``rank_mismatch``: served items at rank r whose reference score lies
  more than ``tolerance`` below the reference's r-th best.
- ``cart_item_returned``: served items that are in the cart.
- ``short_answers``: answers with fewer items than the reference has
  positive scores, up to n.
- ``bad_answers``: answers that are not at most n distinct known items
  with finite, positive, non-increasing scores.

The controls put this reference in the program's place with one fault:
the rule rows and the sums rounded to bfloat16 (``bf16``); each item's
20 best complements merged, the template's earlier serving
(``truncated``); the support floor left out (``no_support_floor``).
"""

from __future__ import annotations

import math

import numpy as np

from reference import judge  # noqa: F401 - one judge for every cell

CONTROLS = ("bf16", "truncated", "no_support_floor")
#: complements kept per item by the ``truncated`` control (the earlier
#: template's default ``top_k``)
TRUNCATED_TOP_K = 20
#: rows redrawn at a time
ROW_BLOCK = 256


def pair_counts(baskets, n_items: int) -> np.ndarray:
    """float64 [n, n]: ``C[i, j]`` the baskets holding both i and j."""
    counts = np.zeros((n_items, n_items))
    for basket in baskets:
        items = np.unique(np.asarray(basket, np.int64))
        counts[np.ix_(items, items)] += 1.0
    return counts


def rule_rows(rows, items, diag, n_baskets: int, metric: str,
              min_support: float) -> np.ndarray:
    """The rule scores ``items[r] -> j`` of the count rows ``rows``
    [len(items), n] (float64), ``diag`` every item's basket count."""
    rows = np.asarray(rows, np.float64)
    items = np.asarray(items, np.int64)
    rule = rows / diag[items][:, None]
    if metric == "lift":
        rule = rule * n_baskets / diag[None, :]
    keep = rows >= min_support
    keep[np.arange(len(items)), items] = False
    return np.where(keep, rule, 0.0)


def cart_scores(rules: dict, cart, n_items: int) -> np.ndarray:
    """A cart's score of every item: the sum of its distinct items' rule
    rows (``rules[i]``), the cart's own items at -inf."""
    items = sorted({int(i) for i in cart})
    scores = np.zeros(n_items)
    for i in items:
        scores += rules[i]
    scores[items] = -np.inf
    return scores


def answer_of(scores: np.ndarray, num: int):
    """``(items, scores)`` of the positive scores, at most ``num``, best
    first (ties by the lower item)."""
    order = np.argsort(-scores, kind="stable")[:num]
    order = order[scores[order] > 0]
    return order, scores[order]


# -- the seeded model, redrawn -------------------------------------------------


def popularity(n_items: int, n_rows: int, exponent: float) -> np.ndarray:
    weights = 1.0 / np.arange(1, n_items + 1, dtype=np.float64) ** exponent
    return np.maximum(1, np.rint(n_rows * weights / weights.sum())).astype(np.int64)


def _mix(x: np.ndarray) -> np.ndarray:
    """lowbias32 over uint32 arrays (numpy's uint32 products wrap)."""
    x = np.array(x, np.uint32, ndmin=1)
    x ^= x >> np.uint32(16)
    x *= np.uint32(0x7FEB352D)
    x ^= x >> np.uint32(15)
    x *= np.uint32(0x846CA68B)
    x ^= x >> np.uint32(16)
    return x


class SeededTable:
    """The seeded model's counts, any rows of them on demand."""

    def __init__(self, seed: int, config: dict):
        self.seed = seed
        self.n_items = config["n_items"]
        self.n_baskets = config["n_baskets"]
        self.diag = popularity(
            self.n_items, config["n_order_rows"], config["item_zipf_exponent"]
        )
        self.diag32 = self.diag.astype(np.uint32)
        self.u = np.maximum(
            1, np.rint(32.0 * self.diag / math.sqrt(self.n_baskets))
        ).astype(np.uint32)
        s0, s1 = (seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF
        k = np.arange(self.n_items, dtype=np.uint32)
        self.low_half = _mix(_mix(k ^ _mix(s0)[0]) + np.uint32(s1))
        self.high_half = _mix(k)

    def rows(self, items) -> np.ndarray:
        """float64 [len(items), n_items] counts: for i != j, ``min(c_i,
        c_j, (((u_i u_j) >> 6) m >> 10) << e)``, m and e from the hash
        of (seed, min(i, j), max(i, j)); ``c_i`` on the diagonal. Every
        step fits 32 bits (u < 2^16, m < 64), as on the device; the hash's
        two halves are tables over the items, indexed by lo and hi."""
        i = np.asarray(items, np.int64)[:, None]
        j = np.arange(self.n_items, dtype=np.int64)[None, :]
        lo, hi = np.minimum(i, j), np.maximum(i, j)
        h = _mix(self.low_half[lo] ^ self.high_half[hi])
        m = h & np.uint32(63)
        b = (h >> np.uint32(6)) & np.uint32(7)
        e = np.where(b == 0, 3, np.where(b == 1, 2, np.where(b < 4, 1, 0))).astype(np.uint32)
        t = (self.u[lo] * self.u[hi]) >> np.uint32(6)
        v = ((t * m) >> np.uint32(10)) << e
        v = np.minimum(v, np.minimum(self.diag32[lo], self.diag32[hi]))
        return np.where(lo == hi, self.diag32[lo], v).astype(np.float64)

    def rules(self, items, metric: str, min_support: float) -> dict:
        """``{item: rule row}`` of distinct ``items``, ``ROW_BLOCK`` rows
        drawn at a time."""
        items = sorted({int(i) for i in items})
        out = {}
        for at in range(0, len(items), ROW_BLOCK):
            block = items[at:at + ROW_BLOCK]
            rows = rule_rows(
                self.rows(block), block, self.diag.astype(np.float64),
                self.n_baskets, metric, min_support,
            )
            out.update(zip(block, rows))
        return out


def _bf16(x: np.ndarray) -> np.ndarray:
    import ml_dtypes

    return x.astype(np.float32).astype(ml_dtypes.bfloat16).astype(np.float64)


def _truncated(rule: np.ndarray) -> np.ndarray:
    """A rule row with only its ``TRUNCATED_TOP_K`` best kept."""
    keep = np.argsort(-rule, kind="stable")[:TRUNCATED_TOP_K]
    out = np.zeros_like(rule)
    out[keep] = rule[keep]
    return out


def reference_answers(table: SeededTable, carts, num: int, metric: str,
                      min_support: float, control: str | None = None):
    """``(scores [len(carts), n] of the reference, answers)``: the answers
    are the reference's own, or with ``control`` the control's."""
    floor = 1 if control == "no_support_floor" else min_support
    rules = table.rules([i for cart in carts for i in cart], metric, min_support)
    faulty = rules
    if control == "no_support_floor":
        faulty = table.rules([i for cart in carts for i in cart], metric, floor)
    elif control == "bf16":
        faulty = {i: _bf16(r) for i, r in rules.items()}
    elif control == "truncated":
        faulty = {i: _truncated(r) for i, r in rules.items()}
    elif control is not None:
        raise ValueError(f"no control {control!r}")
    scores = np.stack([cart_scores(rules, c, table.n_items) for c in carts])
    answers = []
    for cart in carts:
        got = cart_scores(faulty, cart, table.n_items)
        if control == "bf16":
            got = _bf16(got)
        answers.append(answer_of(got, num))
    return scores, answers


def parse_answer(prediction, n_items: int):
    """A served prediction as ``(items, scores)`` in its order; None if it
    is not a list of distinct known items ``p<i>`` with finite, positive,
    non-increasing scores."""
    try:
        rows = prediction["itemScores"]
        names = [r["item"] for r in rows]
        if any(not isinstance(n, str) or n[:1] != "p" for n in names):
            return None
        items = np.asarray([int(n[1:]) for n in names], np.int64)
        scores = np.asarray([float(r["score"]) for r in rows], np.float64)
    except (KeyError, TypeError, ValueError, AttributeError, IndexError):
        return None
    if len(items) and (
        items.min() < 0 or items.max() >= n_items
        or len(set(items.tolist())) != len(items)
        or not np.all(np.isfinite(scores)) or np.any(scores <= 0)
        or np.any(np.diff(scores) > 0)
    ):
        return None
    return items, scores


class Comparison:
    """Accumulates the numbers over a sample of answers."""

    def __init__(self, num: int, tolerance: float):
        self.num = num
        self.tolerance = tolerance
        self.score_rel_max = 0.0
        self.item_mismatch = 0
        self.rank_mismatch = 0
        self.cart_item_returned = 0
        self.short_answers = 0
        self.bad_answers = 0
        self.compared = 0

    def add(self, scores: np.ndarray, carts, answers) -> None:
        """``scores``: the reference's [n, n_items] (cart items -inf);
        ``answers[q]``: the served ``(items, scores)`` of cart q, or None
        where malformed."""
        for q, answer in enumerate(answers):
            if answer is None or len(answer[0]) > self.num:
                self.bad_answers += 1
                continue
            items, served = answer
            _, best = answer_of(scores[q], self.num)
            if len(items) < len(best):
                self.short_answers += 1
            self.compared += 1
            cart = {int(i) for i in carts[q]}
            in_cart = np.array([int(i) in cart for i in items], bool)
            self.cart_item_returned += int(in_cart.sum())
            ref = np.where(in_cart, 0.0, scores[q, items])
            positive = ref > 0
            if positive.any():
                rel = np.abs(served[positive] - ref[positive]) / ref[positive]
                self.score_rel_max = max(self.score_rel_max, float(rel.max()))
            floor = best[-1] * (1.0 - self.tolerance) if len(best) else np.inf
            self.item_mismatch += int(np.sum(~positive | (ref < floor)))
            ranks = min(len(items), len(best))
            self.rank_mismatch += int(np.sum(
                ref[:ranks] < best[:ranks] * (1.0 - self.tolerance)
            ))

    def numbers(self) -> dict[str, float]:
        return {
            # nothing compared is a failure, and stays valid JSON
            "score_rel_max": self.score_rel_max if self.compared else 1e30,
            "item_mismatch": float(self.item_mismatch),
            "rank_mismatch": float(self.rank_mismatch),
            "cart_item_returned": float(self.cart_item_returned),
            "short_answers": float(self.short_answers),
            "bad_answers": float(self.bad_answers),
        }
