"""The plain reference of the text-classification cell, and the comparison
that decides `correct`. numpy only: nothing of the program, nothing the
program made.

A query {"text": t, "num": n} against the seeded model answers the n
classes with the largest joint log-score ``pi[c] + sum over the tokens of
t of count * theta[bucket, c]``: tokens by Python's own ``re`` (the
template's token pattern), each token's bucket its 64-bit FNV-1a hash,
byte by byte in Python integers, modulo the hashed width d. The model's
entries are redrawn here in float64 from (seed, row, class) for just the
buckets the compared titles touch (:func:`normals`: the arithmetic of
`modelstore_textcls.normals` written again in numpy).

The comparison, over a sample of the answers the timed path produced:

- ``score_abs_max``: the largest |served score - reference score of the
  served class|, in log units, over every served (class, score).
- ``label_mismatch``: answers whose label is not the reference's best
  class, where the reference's score of the served label lies more than
  ``tolerance`` below its best (two classes nearer than that are a tie).
- ``rank_mismatch``: served classes at ranks 2..n that lie more than
  ``tolerance`` below the reference's score at that rank.
- ``short_answers``: answers with fewer than n classes.
- ``bad_answers``: answers that are not a label and n distinct known
  classes with finite, non-increasing scores, the label first.

The controls put this reference in the program's place with one fault:
the table and prior rounded to bfloat16 (``bf16``), or each token hashed
by the 32-bit FNV-1a (``hash_fault``). The third control,
``dense_default`` (the dense product of the template's earlier scoring
at XLA's default precision), runs on the device in the runner.
"""

from __future__ import annotations

import math
import re

import numpy as np

from reference import judge  # noqa: F401 - one judge for every cell

TOKEN = re.compile(r"[a-z0-9']+")
#: (offset basis, prime, modulus) of FNV-1a at 64 and at 32 bits
FNV64 = (14695981039346656037, 1099511628211, 1 << 64)
FNV32 = (2166136261, 16777619, 1 << 32)
CONTROLS = ("bf16", "hash_fault", "dense_default")


def tokens(text: str) -> list[str]:
    return TOKEN.findall(text.lower())


def fnv1a(token: str, kind=FNV64) -> int:
    h, prime, modulus = kind
    for byte in token.encode("utf-8"):
        h = ((h ^ byte) * prime) % modulus
    return h


def bucket_counts(text: str, n_features: int, kind=FNV64) -> dict[int, int]:
    """``{bucket: count}`` of a text's tokens."""
    out: dict[int, int] = {}
    for tok in tokens(text):
        b = fnv1a(tok, kind) % n_features
        out[b] = out.get(b, 0) + 1
    return out


def _mix(x: np.ndarray) -> np.ndarray:
    """lowbias32 over uint32 arrays (numpy's uint32 products wrap)."""
    x = np.array(x, np.uint32, ndmin=1)
    x ^= x >> np.uint32(16)
    x *= np.uint32(0x7FEB352D)
    x ^= x >> np.uint32(15)
    x *= np.uint32(0x846CA68B)
    x ^= x >> np.uint32(16)
    return x


def normals(seed: int, rows, n_classes: int) -> np.ndarray:
    """[len(rows), n_classes] float64 standard normals of (seed, row,
    class): Box-Muller from two 24-bit uniforms, each a lowbias32 hash
    of the seed's two words, the row and the class."""
    s0, s1 = (seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF
    rows = np.asarray(rows, np.int64).astype(np.uint32)
    base = _mix(_mix(rows ^ _mix(s0)[0]) + np.uint32(s1))[:, None]
    cols = 2 * np.arange(n_classes, dtype=np.uint32)
    u1 = _mix(base ^ _mix(cols)[None, :]) >> np.uint32(8)
    u2 = _mix(base ^ _mix(cols + np.uint32(1))[None, :]) >> np.uint32(8)
    a = (u1.astype(np.float64) + 1.0) * 2.0 ** -24
    b = u2.astype(np.float64) * 2.0 ** -24
    return np.sqrt(-2.0 * np.log(a)) * np.cos(2.0 * math.pi * b)


def theta_rows(seed: int, rows, n_features: int, n_classes: int) -> np.ndarray:
    return -math.log(n_features) + normals(seed, rows, n_classes)


def prior(seed: int, n_features: int, n_classes: int) -> np.ndarray:
    z = normals(seed, [n_features], n_classes)[0]
    top = z.max()
    return z - (top + math.log(np.exp(z - top).sum()))


def _rounded(x: np.ndarray, precision: str | None) -> np.ndarray:
    if precision is None:
        return x
    import ml_dtypes

    kind = {"bf16": ml_dtypes.bfloat16}[precision]
    return x.astype(np.float32).astype(kind).astype(np.float64)


def reference_scores(
    seed: int, texts: list[str], n_features: int, n_classes: int,
    kind=FNV64, precision: str | None = None,
) -> np.ndarray:
    """[len(texts), C] float64 joint log-scores; with ``precision`` the
    model's entries rounded through it first."""
    per_text = [bucket_counts(t, n_features, kind) for t in texts]
    rows = sorted({b for counts in per_text for b in counts})
    where = {b: i for i, b in enumerate(rows)}
    table = _rounded(theta_rows(seed, rows, n_features, n_classes), precision)
    scores = np.tile(_rounded(prior(seed, n_features, n_classes), precision),
                     (len(texts), 1))
    for q, counts in enumerate(per_text):
        for b, n in counts.items():
            scores[q] += n * table[where[b]]
    return scores


def top(scores: np.ndarray, num: int):
    """(classes, scores) of the ``num`` best of each row, best first."""
    idx = np.argsort(-scores, axis=1, kind="stable")[:, :num]
    return idx, np.take_along_axis(scores, idx, axis=1)


def answers_of(scores: np.ndarray, num: int) -> list:
    classes, best = top(scores, num)
    return [(classes[q], best[q]) for q in range(len(scores))]


def control_answers(seed, texts, n_features, n_classes, num, control):
    """The host controls' answers, as ``[(classes, scores)]``."""
    if control == "bf16":
        scores = reference_scores(seed, texts, n_features, n_classes, precision="bf16")
    elif control == "hash_fault":
        scores = reference_scores(seed, texts, n_features, n_classes, kind=FNV32)
    else:
        raise ValueError(f"no host control {control!r}")
    return answers_of(scores, num)


def parse_answer(prediction, n_classes: int):
    """A served prediction as ``(classes, scores)`` in its order; None if
    it is not a label and a dict of known classes ``c<i>`` with finite,
    non-increasing scores, the label first."""
    try:
        rows = list(prediction["scores"].items())
        classes = np.asarray([int(name[1:]) for name, _ in rows], np.int64)
        if any(name[0] != "c" for name, _ in rows) or not rows:
            return None
        scores = np.asarray([float(s) for _, s in rows], np.float64)
    except (KeyError, TypeError, ValueError, AttributeError, IndexError):
        return None
    if (
        prediction.get("label") != rows[0][0]
        or classes.min() < 0 or classes.max() >= n_classes
        or not np.all(np.isfinite(scores)) or np.any(np.diff(scores) > 0)
    ):
        return None
    return classes, scores


class Comparison:
    """Accumulates the numbers over a sample of answers."""

    def __init__(self, num: int, tolerance: float):
        self.num = num
        self.tolerance = tolerance
        self.score_abs_max = 0.0
        self.label_mismatch = 0
        self.rank_mismatch = 0
        self.short_answers = 0
        self.bad_answers = 0
        self.compared = 0

    def add(self, scores: np.ndarray, answers) -> None:
        """``scores``: the reference's [n, C]; ``answers[q]``: the served
        ``(classes, scores)`` of query q, or None where malformed."""
        _, best = top(scores, self.num)
        for q, answer in enumerate(answers):
            if answer is None:
                self.bad_answers += 1
                continue
            classes, served = answer
            if len(classes) > self.num:
                self.bad_answers += 1
                continue
            if len(classes) < self.num:
                self.short_answers += 1
                continue
            ref = scores[q, classes]
            self.compared += 1
            self.score_abs_max = max(
                self.score_abs_max, float(np.max(np.abs(served - ref)))
            )
            gap = best[q] - ref
            self.label_mismatch += int(gap[0] > self.tolerance)
            self.rank_mismatch += int(np.sum(gap[1:] > self.tolerance))

    def numbers(self) -> dict[str, float]:
        return {
            # nothing compared is a failure, and stays valid JSON
            "score_abs_max": self.score_abs_max if self.compared else 1e30,
            "label_mismatch": float(self.label_mismatch),
            "rank_mismatch": float(self.rank_mismatch),
            "short_answers": float(self.short_answers),
            "bad_answers": float(self.bad_answers),
        }
