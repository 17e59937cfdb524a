"""The plain reference of the similar-product cell, and the comparison
that decides its `correct`. numpy and float64 only: nothing of the program,
nothing the program made. It is given the seeded arrays the runner built
the tenants from (`Shop`).

Semantics, after the reference template's ``multi`` variant
(``ALSAlgorithm.scala``, ``LikeAlgorithm.scala``, ``Serving.scala``). For a
query ``{items, num, categories?, whiteList?, blackList?}`` and each
algorithm a with item table V_a:

1. Q = the query's items that the model knows, each once; if none is
   left, or every known one has a zero row in V_a, the algorithm's answer
   is empty.
2. score_a(j) = sum over i in Q of cos(V_a[i], V_a[j]), over every item
   of Q whatever its length (a zero row has no direction and adds 0).
3. j is a candidate iff j is not in ``items``, not in ``blackList``, in
   ``whiteList`` if one is given, in one of ``categories`` if any is
   given, and score_a(j) > 0.
4. L_a = the ``num`` best candidates by score_a, fewer if fewer exist.
5. Combine: if ``num`` is 1 the lists are taken as they are; otherwise
   each L_a's scores are standardized with that list's own mean and sample
   standard deviation (z = 0 for every item of a list whose deviation is
   0, one-item lists included); the z of one item are summed over the
   lists that hold it; the ``num`` largest sums are the answer, ties by
   the order the lists gave.

Departures from the Scala are listed in the configuration's file
(`departures`), each also the program's.

What is compared, with limits in the configuration's file. A list's ten
best of 4.16 M cosines lie within about a hundredth of each other, so
bfloat16 operands reorder them near the list's end and which items a
served list held cannot be read off the reference's list. z is affine in
score_a, so the reference gives any item the z it would have in L_a: the
list's own mean and deviation applied to the item's reference score. A
served item's reference score is the sum of those z over the lists that
hold it. Where rounding decides whether a list holds it (a candidate whose
score lies within `EDGE_DEVIATIONS` of the list's own deviations of its
last score; bfloat16 operands move a score by about a tenth of one at 4.16
M items), both memberships are tried and the one nearest the served score
is taken, but an item that was served was held by some list: where no list
holds it for sure, one of those that may hold it is counted.

- ``score_rms``: root mean square of (served combined score - reference
  combined score of the served item) over the root mean square of the
  reference's own combined scores.
- ``rank_gap_rms``: as `reference.py`: how far the reference combined score
  of the item served at rank r lies below the reference's r-th best
  combined score, root mean square; in units of z (one deviation of a
  list) where the query standardizes, as a share of the query's best
  where ``num`` is 1.
- ``rule_violations``: served items that are among the query's items, on
  the blackList, off the whiteList, outside the categories, or that no
  list could hold (in no L_a and within the band of no list's edge).
- ``short_answers``: answers shorter than the reference's answer, an item
  whose score lies within `ZERO_BAND` of 0 in every list not owed.
- ``bad_answers``: answers that are not at most ``num`` distinct known
  items with finite, non-increasing scores.

The controls put this reference in the program's place: with every table
through fp8 (`reference.quantize_rows`), which must fail ``score_rms`` and
``rank_gap_rms``; and as the algorithm the template had before its rules
moved in front of the top-k (`filter_after_top`: cosine to the mean of the
query items' raw vectors, the best ``num + len(items)`` rounded up to a
power of two, the filters over those on the host, raw scores summed),
which must read ``short_answers`` above 0. `COMBINE_FAULTS` plants a fault
in step 5 alone (the lists are the reference's own), to show that the
comparison holds the combine and not only the lists.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from reference import judge, quantize_rows  # noqa: F401 - judge is the runner's

#: a reference score within this share of the list's best score of 0 is
#: rounding's to decide (bf16 operands: 2^-8 an operand)
ZERO_BAND = 0.02
#: a candidate whose reference score lies within this many of the list's
#: own sample deviations of the list's last score may be inside the served
#: list or outside it
EDGE_DEVIATIONS = 0.5
#: the same where the list's deviation is 0 (a list of one, `num` 1
#: included), as a share of its best score: one step of bfloat16
EDGE_SHARE = 2.0 ** -8


@dataclasses.dataclass
class Shop:
    """One tenant as the runner seeded it."""

    tables: list             # per algorithm: [I, k] f32 item factors
    category: np.ndarray     # [I] int: the item's category number
    wide: list | None = None  # `unit_rows`' float64 copies


def item_rows(ids, n_items: int) -> np.ndarray:
    """The rows of the ids ``i<j>`` the model knows, each once, in order."""
    rows = [int(x[1:]) for x in ids if x[:1] == "i" and x[1:].isdigit()]
    return np.asarray(list(dict.fromkeys(r for r in rows if r < n_items)), np.int64)


def category_number(name: str) -> int:
    return int(name[1:]) if name[:1] == "c" and name[1:].isdigit() else -1


def unit_rows(shop: Shop, tables=None) -> list:
    """Per algorithm the table in float64, every row at length 1, a zero
    row left 0; ``tables`` stand in for the shop's in a control."""
    if tables is None and shop.wide is not None:
        return shop.wide
    out = []
    for table in shop.tables if tables is None else tables:
        wide = np.asarray(table, np.float64)
        norm = np.linalg.norm(wide, axis=1, keepdims=True)
        out.append(np.where(norm > 0, wide / np.where(norm > 0, norm, 1.0), 0.0))
    if tables is None:
        shop.wide = out
    return out


def rules(shop: Shop, query: dict) -> np.ndarray:
    """[I] bool: the items step 3 leaves, before ``score > 0``."""
    n_items = len(shop.category)
    ok = np.ones(n_items, bool)
    ok[item_rows(query.get("items") or (), n_items)] = False
    ok[item_rows(query.get("blackList") or (), n_items)] = False
    if query.get("whiteList"):
        white = np.zeros(n_items, bool)
        white[item_rows(query["whiteList"], n_items)] = True
        ok &= white
    if query.get("categories"):
        wanted = [category_number(c) for c in query["categories"]]
        ok &= np.isin(shop.category, [c for c in wanted if c >= 0])
    return ok


def all_scores(shop: Shop, queries, tables=None) -> np.ndarray:
    """[A, Q, I] float64: step 2 for every algorithm, no rule applied."""
    n_items = len(shop.category)
    out = []
    for unit in unit_rows(shop, tables):
        vectors = np.stack([
            unit[item_rows(q.get("items") or (), n_items)].sum(0) for q in queries
        ])
        out.append(vectors @ unit.T)
    return np.stack(out)


def ranked(scores: np.ndarray, ok: np.ndarray, num: int):
    """(rows, scores) of the ``num`` best of ``scores`` where ``ok``, best
    first, ties to the lower row."""
    rows = np.flatnonzero(ok)
    if len(rows) > num:
        kept = rows[np.argpartition(-scores[rows], num - 1)[:num]]
        edge = scores[kept].min()
        rows = np.union1d(kept[scores[kept] > edge], np.flatnonzero(ok & (scores == edge)))
    order = np.lexsort((rows, -scores[rows]))[:num]
    return rows[order], scores[rows][order]


def spread(scores) -> tuple[float, float]:
    """(mean, sample standard deviation) a list is standardized by; the
    deviation is 0 for a list of one."""
    scores = np.asarray(scores, np.float64)
    if len(scores) < 2:
        return (float(scores.mean()) if len(scores) else 0.0), 0.0
    return float(scores.mean()), float(scores.std(ddof=1))


def z_of(score, mean: float, deviation: float, num: int):
    """Step 5's score of an item in a list of this mean and deviation."""
    if num == 1:
        return score
    return (score - mean) / deviation if deviation > 0 else 0.0 * score


def list_z(a: int, scores, num: int):
    """Step 5's scores of the a-th list's items, in the list's order."""
    return z_of(np.asarray(scores, np.float64), *spread(scores), num)


def combine(lists, num: int, list_z=list_z):
    """Step 5 over ``[(rows, scores)]``, one list an algorithm: (rows,
    combined scores), best first, ties by the order the lists gave.
    ``list_z`` stands in for step 5's own in a control (`COMBINE_FAULTS`)."""
    total: dict[int, float] = {}
    for a, (rows, scores) in enumerate(lists):
        for row, z in zip(rows.tolist(), np.asarray(list_z(a, scores, num)).tolist()):
            total[row] = total.get(row, 0.0) + z
    order = sorted(total.items(), key=lambda kv: kv[1], reverse=True)[:num]
    return (
        np.asarray([r for r, _z in order], np.int64),
        np.asarray([z for _r, z in order], np.float64),
    )


def reference_lists(shop: Shop, queries, num: int, tables=None):
    """Per query ``(num, ok, [L_a as (rows, scores)], scores [A, I])``."""
    scores = all_scores(shop, queries, tables)
    out = []
    for q, query in enumerate(queries):
        n = min(num, int(query.get("num", num)))
        ok = rules(shop, query)
        lists = [ranked(s[q], ok & (s[q] > 0), n) for s in scores]
        out.append((n, ok, lists, scores[:, q]))
    return out


def reference_answers(shop: Shop, queries, num: int, tables=None):
    """The reference's own answers: ``[(rows, combined scores)]``."""
    return [
        combine(lists, n)
        for n, _ok, lists, _s in reference_lists(shop, queries, num, tables)
    ]


def filter_after_top(shop: Shop, queries, num: int):
    """The second control: the template's algorithm before this
    configuration. Per algorithm the cosine to the MEAN of the query items'
    raw vectors, the best ``num + len(items)`` rounded up to a power of two
    of ALL items, the filters on those, then ``num``; the lists' raw scores
    summed per item."""
    n_items = len(shop.category)
    units = unit_rows(shop)
    everything = np.ones(n_items, bool)
    out = []
    for query in queries:
        rows = item_rows(query.get("items") or (), n_items)
        ok = rules(shop, query)
        total: dict[int, float] = {}
        for table, unit in zip(shop.tables, units):
            if not len(rows):
                continue
            mean = np.asarray(table, np.float64)[rows].mean(0)
            norm = np.linalg.norm(mean)
            scores = unit @ (mean / norm if norm > 0 else mean)
            k = min(1 << max(0, num + len(rows) - 1).bit_length(), n_items)
            top, top_scores = ranked(scores, everything, k)
            keep = ok[top]
            for row, score in zip(top[keep][:num].tolist(), top_scores[keep][:num].tolist()):
                total[row] = total.get(row, 0.0) + score
        order = sorted(total.items(), key=lambda kv: kv[1], reverse=True)[:num]
        out.append((
            np.asarray([r for r, _s in order], np.int64),
            np.asarray([s for _r, s in order], np.float64),
        ))
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


def _memberships(row_scores, ok, lists, num: int):
    """Per list, for the items whose scores are ``row_scores`` [A, n]:
    ``(z the item would have there, held for sure, may be held)``."""
    out = []
    for a, (rows, scores) in enumerate(lists):
        mean, deviation = spread(scores)
        z = z_of(row_scores[a], mean, deviation, num)
        if not len(scores):
            out.append((z, np.zeros(len(z), bool), np.zeros(len(z), bool)))
            continue
        if len(scores) >= num:
            edge = scores[-1]
            band = EDGE_DEVIATIONS * deviation if deviation > 0 else EDGE_SHARE * scores[0]
        else:  # a list that is not full holds every candidate: its edge is 0
            edge, band = 0.0, ZERO_BAND * scores[0]
        sure = ok & (row_scores[a] > edge + band)
        # a zero row scores exactly 0, here and in the program: no candidate
        maybe = ok & (row_scores[a] >= edge - band) & (row_scores[a] != 0) & ~sure
        out.append((z, sure, maybe))
    return out


def nearest_combined(served, memberships):
    """The reference's combined score of each served item: the sum of its
    z over the lists that hold it for sure, and over that subset of the
    lists that may hold it which comes nearest the served score; where no
    list holds it for sure the subset is not the empty one (some list held
    an item that was served)."""
    n = len(served)
    base, held = np.zeros(n), np.zeros(n, bool)
    for z, sure, _maybe in memberships:
        base += np.where(sure, z, 0.0)
        held |= sure
    best = np.where(held, base, np.inf)
    for mask in range(1, 1 << len(memberships)):
        chosen = [m for k, m in enumerate(memberships) if mask >> k & 1]
        trial = base + sum(np.where(maybe, z, 0.0) for z, _sure, maybe in chosen)
        valid = held | np.any([maybe for _z, _sure, maybe in chosen], axis=0)
        best = np.where(
            valid & (np.abs(trial - served) < np.abs(best - served)), trial, best
        )
    # an item no list could hold is a rule violation, counted there
    return np.where(np.isfinite(best), best, base)


class Comparison:
    """Accumulates the numbers over a sample of answers."""

    def __init__(self, num: int):
        self.num = num
        self.sq_err = self.sq_ref = self.sq_gap = 0.0
        self.pairs = self.answers = 0
        self.bad_answers = self.rule_violations = self.short_answers = 0

    def add(self, shop: Shop, queries, answers) -> None:
        """``answers[q]`` is ``(item rows, scores)`` as served for
        ``queries[q]``, or None where the answer is malformed."""
        found = reference_lists(shop, queries, self.num)
        for (num, ok, lists, scores), answer in zip(found, answers):
            self.answers += 1
            if answer is None:
                self.bad_answers += 1
                continue
            rows, served = (np.asarray(a) for a in answer)
            _, best = combine(lists, num)
            owed = set()
            for l_rows, l_scores in lists:
                band = ZERO_BAND * l_scores[0] if len(l_scores) else 0.0
                owed.update(l_rows[l_scores > band].tolist())
            self.short_answers += len(rows) < min(num, len(owed))
            if not len(rows):
                continue
            members = _memberships(scores[:, rows], ok[rows], lists, num)
            held = np.zeros(len(rows), bool)
            for _z, sure, maybe in members:
                held |= sure | maybe
            self.rule_violations += int(np.sum(~held))
            ref = nearest_combined(served, members)
            n = min(len(rows), len(best))
            if not n:
                continue
            scale = max(abs(best[0]), 1e-30) if num == 1 else 1.0
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
        if not self.pairs or self.sq_ref <= 0:
            # nothing to compare is a failure, and stays valid JSON
            return {"score_rms": 1e30, "rank_gap_rms": 1e30, **counts}
        return {
            "score_rms": math.sqrt(self.sq_err / self.sq_ref),
            "rank_gap_rms": math.sqrt(self.sq_gap / self.pairs),
            **counts,
        }


def _population_deviation(a: int, scores, num: int):
    mean, _sample = spread(scores)
    population = float(np.std(scores)) if len(scores) else 0.0
    return z_of(np.asarray(scores, np.float64), mean, population, num)


def _raw_last_list(a: int, scores, num: int, last: int = 1):
    return np.asarray(scores, np.float64) if a == last else list_z(a, scores, num)


#: step 5 with one fault planted, by the control's name: the deviation of
#: the population (ddof 0) in place of the sample's, which moves every z
#: by 5.4% at ten items; the second list's scores summed as they are
COMBINE_FAULTS = {
    "population_deviation": _population_deviation,
    "raw_last_list": _raw_last_list,
}


def control_answers(shop: Shop, queries, num: int, control: str):
    """The control's answers: `filter_after_top`, the reference's lists
    through a faulty combine (`COMBINE_FAULTS`), or the reference over
    every table taken through the precision ``control`` names."""
    if control == "filter_after_top":
        return filter_after_top(shop, queries, num)
    if control in COMBINE_FAULTS:
        return [
            combine(lists, n, COMBINE_FAULTS[control])
            for n, _ok, lists, _s in reference_lists(shop, queries, num)
        ]
    return reference_answers(
        shop, queries, num, tables=[quantize_rows(t, control) for t in shop.tables]
    )
