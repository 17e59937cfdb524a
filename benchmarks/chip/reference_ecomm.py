"""The plain reference of the e-commerce cell, and the comparison that
decides its `correct`. numpy and float64 only: nothing of the program,
nothing the program made, never through the program's event store. It is
given the seeded arrays the runner built the tenants and wrote the events
from (`Shop`).

Semantics, after the reference template's ``ECommAlgorithm.scala``
(train-with-rate-event variant). ``U``, ``V``: a tenant's user and item
factors; ``cat(i)``: the item's category; ``seen(u)``: the items of the
user's ``view`` and ``buy`` events; ``unavail``: the items of the latest
``$set`` of ``constraint/unavailableItems``.

- Candidates of query q from user u: items not in seen(u), unavail or
  q.blackList; on q.whiteList if it has one; of one of q.categories if it
  names any.
- Known user: s_i = U[u] . V[i]; the top ``num`` of the candidates with
  s_i > 0.
- Unknown user with recent views R (the items of their latest 10 ``view``
  events that the model knows): s_i = sum over r in R of cos(V[r], V[i]);
  same candidates, same s_i > 0.
- Unknown user with none: s_i = popularity[i] over the candidates.
- Fewer candidates than ``num``: a shorter answer.

Departures from the Scala, each also the program's: one category an item
(the data set's shape; the program takes lists); popularity is a seeded
array, where upstream counts ``buy`` events at train time; a query's
``categories`` that the model does not know match nothing (upstream the
same); ties go to the lower item index (upstream leaves them to the
priority queue).

What is compared, with limits in the configuration's file:

- ``score_rms``: root mean square of (served score - reference score of
  the served item), each query's scores taken as shares of that query's
  best reference score (the three branches' scores differ by six orders of
  magnitude), over the root mean square of the reference's own top scores
  taken the same way. It separates a lower precision.
- ``rank_gap_rms``: as `reference.py`: how far the reference score of the
  item served at rank r lies below the reference's r-th best candidate, as
  a share of the query's best.
- ``rule_violations``: served items that are seen, unavailable,
  blacklisted, off the whiteList, outside the categories, or that the
  reference scores at or below 0 on a branch that keeps positive scores.
- ``short_answers``: answers with fewer items than the reference gives
  (at most ``num``).
  Both leave out what rounding decides: an item whose reference score lies
  within `ZERO_BAND` of 0, as a share of the query's best, is neither a
  violation when served nor owed when not.
- ``bad_answers``: answers that are not at most ``num`` distinct known
  items with finite, non-increasing scores.
- ``stale_answers``: the runner's probe after the window (an item still
  served after the write that rules it out has returned).

The controls put this reference in the program's place: with both tables
through fp8 (`reference.quantize_rows`), which must fail ``score_rms`` and
``rank_gap_rms``; and as the algorithm this template had before its rules
moved in front of the top-k (`filter_after_top64`: the best 64 of all
items, the rules over those on the host), which must read
``short_answers`` above 0.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from reference import judge, quantize_rows  # noqa: F401 - judge is the runner's

RECENT_VIEWS = 10
#: a reference score within this share of the query's best score of 0 is
#: rounding's to decide (bf16 operands: 2^-8 an operand)
ZERO_BAND = 0.02


@dataclasses.dataclass
class Shop:
    """One tenant as the runner seeded it."""

    users: np.ndarray        # [U, k] f32
    items: np.ndarray        # [I, k] f32
    category: np.ndarray     # [I] int: the item's category number
    popularity: np.ndarray   # [I]
    unavailable: np.ndarray  # [I] bool
    seen: dict               # user id -> int array of the item rows seen
    views: dict              # unknown user's id -> item rows, newest first
    wide: tuple | None = None  # `all_scores`' float64 copy of the table


def user_index(user: str, n_users: int) -> int:
    """The row of user id ``u<i>``; -1 for an id the model does not know."""
    if user[:1] == "u" and user[1:].isdigit() and int(user[1:]) < n_users:
        return int(user[1:])
    return -1


def item_rows(ids, n_items: int) -> np.ndarray:
    rows = [int(x[1:]) for x in ids if x[:1] == "i" and x[1:].isdigit()]
    return np.asarray([r for r in rows if r < n_items], np.int64)


def category_number(name: str) -> int:
    return int(name[1:]) if name[:1] == "c" and name[1:].isdigit() else -1


def query_vector(shop: Shop, query: dict):
    """``(branch, [k] float64 vector or None)`` of a query."""
    u = user_index(str(query.get("user", "")), len(shop.users))
    if u >= 0:
        return "known", shop.users[u].astype(np.float64)
    rows = np.asarray(shop.views.get(str(query.get("user", "")), ())[:RECENT_VIEWS], np.int64)
    if len(rows):
        v = shop.items[rows].astype(np.float64)
        norm = np.linalg.norm(v, axis=1, keepdims=True)
        return "similar", np.where(norm > 0, v / np.where(norm > 0, norm, 1), 0).sum(0)
    return "popular", None


def candidates(shop: Shop, query: dict) -> np.ndarray:
    """[I] bool: the items the rules leave."""
    n_items = len(shop.items)
    ok = ~shop.unavailable
    seen = shop.seen.get(str(query.get("user", "")))
    if seen is not None:
        ok[seen] = False
    ok[item_rows(query.get("blackList") or (), n_items)] = False
    if query.get("whiteList"):
        white = np.zeros(n_items, bool)
        white[item_rows(query["whiteList"], n_items)] = True
        ok &= white
    if query.get("categories"):
        wanted = [category_number(c) for c in query["categories"]]
        ok &= np.isin(shop.category, [c for c in wanted if c >= 0])
    return ok


def all_scores(shop: Shop, queries, items=None, users=None) -> tuple[list, np.ndarray]:
    """``(branches, [Q, I] float64 scores)``, no rule applied. ``items``
    and ``users`` stand in for the shop's tables in a control."""
    base = shop if users is None and items is None else dataclasses.replace(
        shop, items=shop.items if items is None else items,
        users=shop.users if users is None else users, wide=None,
    )
    if base.wide is None:  # the table in float64 and its 1/norms, once
        items64 = np.asarray(base.items, np.float64)
        inv = np.linalg.norm(items64, axis=1)
        base.wide = items64, np.where(inv > 0, 1.0 / np.where(inv > 0, inv, 1.0), 0.0)
    items64, inv = base.wide
    found = [query_vector(base, q) for q in queries]
    vectors = np.stack([
        v if v is not None else np.zeros(items64.shape[1]) for _b, v in found
    ])
    scores = vectors @ items64.T
    for q, (branch, _v) in enumerate(found):
        if branch == "similar":
            scores[q] *= inv
        elif branch == "popular":
            scores[q] = shop.popularity
    return [b for b, _v in found], scores


def ranked(scores: np.ndarray, ok: np.ndarray, num: int):
    """(rows, scores) of the ``num`` best of ``scores`` where ``ok``, best
    first, ties to the lower row."""
    rows = np.flatnonzero(ok)
    if len(rows) > num:
        rows = rows[np.argpartition(-scores[rows], num - 1)[:num]]
        # what ties with the last kept score, by row
        edge = scores[rows].min()
        tied = np.flatnonzero(ok & (scores == edge))
        rows = np.union1d(rows[scores[rows] > edge], tied)
    order = np.lexsort((rows, -scores[rows]))[:num]
    return rows[order], scores[rows][order]


def reference_answers(shop: Shop, queries, num: int, items=None, users=None):
    """The reference's own answers: ``[(rows, scores)]`` per query."""
    branches, scores = all_scores(shop, queries, items, users)
    out = []
    for q, query in enumerate(queries):
        ok = candidates(shop, query)
        if branches[q] != "popular":
            ok &= scores[q] > 0
        out.append(ranked(scores[q], ok, min(num, int(query.get("num", num)))))
    return out


def filter_after_top64(shop: Shop, queries, num: int):
    """The second control: this template's algorithm before its rules
    moved in front of the top-k. Known user: the best 64 of ALL items by
    score, then the rules on those, then ``num``; unknown user: the 40
    most popular, the same way."""
    branches, scores = all_scores(shop, queries)
    everything = np.ones(len(shop.items), bool)
    out = []
    for q, query in enumerate(queries):
        if user_index(str(query.get("user", "")), len(shop.users)) >= 0:
            rows, top = ranked(scores[q], everything, 64)
        else:
            rows, _ = ranked(shop.popularity.astype(np.float64), everything, 4 * num)
            top = shop.popularity[rows].astype(np.float64)
        keep = candidates(shop, query)[rows]
        out.append((rows[keep][:num], top[keep][:num]))
    return out


def parse_answer(prediction, num: int, n_items: int):
    """A served prediction as ``(item rows, scores)``; None if it is not a
    list of at most ``num`` {"item": "i<j>", "score": s} of distinct known
    items with finite, non-increasing scores."""
    try:
        pairs = prediction["itemScores"]
        rows = np.asarray([int(p["item"][1:]) for p in pairs if p["item"][0] == "i"], np.int64)
        served = np.asarray([float(p["score"]) for p in pairs], np.float64)
    except (KeyError, TypeError, ValueError, IndexError):
        return None
    if (
        len(rows) != len(pairs) or len(pairs) > num
        or len(set(rows.tolist())) != len(rows)
        or (len(rows) and (rows.min() < 0 or rows.max() >= n_items))
        or not np.all(np.isfinite(served)) or np.any(np.diff(served) > 0)
    ):
        return None
    return rows, served


class Comparison:
    """Accumulates the numbers over a sample of answers."""

    def __init__(self, num: int):
        self.num = num
        self.sq_err = self.sq_ref = self.sq_gap = 0.0
        self.pairs = self.answers = 0
        self.bad_answers = self.rule_violations = self.short_answers = 0
        self.by_branch = {"known": 0, "similar": 0, "popular": 0}

    def add(self, shop: Shop, queries, answers) -> None:
        """``answers[q]`` is ``(item rows, scores)`` as served for
        ``queries[q]``, or None where the answer is malformed."""
        branches, scores = all_scores(shop, queries)
        for q, (query, answer) in enumerate(zip(queries, answers)):
            self.answers += 1
            self.by_branch[branches[q]] += 1
            if answer is None:
                self.bad_answers += 1
                continue
            rows, served = (np.asarray(a) for a in answer)
            num = min(self.num, int(query.get("num", self.num)))
            ok = candidates(shop, query)
            positive = branches[q] != "popular"
            _, best = ranked(scores[q], ok & (scores[q] > 0) if positive else ok, num)
            scale = abs(best[0]) if len(best) else 1.0
            band = ZERO_BAND * scale if positive else 0.0
            ref = scores[q, rows]
            self.rule_violations += int(np.sum(~ok[rows]))
            if positive:
                self.rule_violations += int(np.sum(ref <= -band))
                owed = min(num, int(np.sum(ok & (scores[q] > band))))
            else:
                owed = min(num, int(ok.sum()))
            self.short_answers += len(rows) < owed
            n = min(len(rows), len(best))
            if not n:
                continue
            self.sq_err += float(np.sum(((served - ref) / scale) ** 2))
            self.sq_ref += float(np.sum((best / scale) ** 2))
            self.pairs += n
            gap = np.maximum(best[:n] - ref[:n], 0.0) / scale
            self.sq_gap += float(np.sum(gap ** 2))

    def numbers(self) -> dict[str, float]:
        counts = {
            "bad_answers": float(self.bad_answers),
            "rule_violations": float(self.rule_violations),
            "short_answers": float(self.short_answers),
        }
        if not self.pairs:
            # nothing to compare is a failure, and stays valid JSON
            return {"score_rms": 1e30, "rank_gap_rms": 1e30, **counts}
        return {
            "score_rms": math.sqrt(self.sq_err / self.sq_ref),
            "rank_gap_rms": math.sqrt(self.sq_gap / self.pairs),
            **counts,
        }


def control_answers(shop: Shop, queries, num: int, control: str):
    """The control's answers: `filter_after_top64`, or the reference over
    both tables taken through the precision ``control`` names."""
    if control == "filter_after_top64":
        return filter_after_top64(shop, queries, num)
    return reference_answers(
        shop, queries, num,
        items=quantize_rows(shop.items, control),
        users=quantize_rows(shop.users, control),
    )
