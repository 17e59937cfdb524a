"""The churn cell's comparison: `reference.py`'s arithmetic over answers
whose item ids carry their tenant, with the share of the sample that came
from tenants loaded inside the window, and the two shortfalls that say the
run measured the deployment it names. numpy only: nothing of the program.

What `correct` holds a run to, beside `reference.py`'s ``score_rms``,
``rank_gap_rms`` and ``bad_answers``:

- an answer's items are ``<tenant>.i<j>`` of the tenant that was asked
  (`parse_answer`): ids of another tenant's map are a bad answer;
- ``cold_sample_short``: how many compared queries are missing for a third
  of the sample to come from posts whose tenant was loaded inside the
  window (`cold_shortfall`), so that an answer after a reload is held to
  the reference as an answer before it is;
- ``missing_evictions``: how many evictions the window fell short of the
  configuration's least (`shortfall`): a pool that did not churn is
  another deployment.

`judge` takes upper limits only, so both shortfalls are 0 when met.

Two planted faults (`control_answers`): the tables one precision step down
(``fp8``, as `reference.py` has it), and ``wrong_blob``: the answers of
another tenant's tables under the asked tenant's ids, which is what a
loader that staged the wrong generation would serve after a reload. A third
is planted in the run and not in the answers (`RUN_CONTROLS`):
``kept_generation``, the runner holding on to one generation past its
eviction, which ``over_ledger_gib`` has to read over its limit.
"""

from __future__ import annotations

import math

import numpy as np

import reference
from reference import Comparison, judge  # noqa: F401  (the cell's judge)

CONTROLS = ("fp8", "wrong_blob")
#: the faults a runner plants in the run itself
RUN_CONTROLS = ("kept_generation",)
#: the share of the compared queries that has to come from tenants loaded
#: inside the window
COLD_SHARE = 1.0 / 3.0


def parse_answer(prediction, num: int, tenant: str):
    """A served prediction as ``(item indices, scores)``; None if it is not
    a list of ``num`` {"item": "<tenant>.i<j>", "score": s}."""
    prefix = tenant + ".i"
    try:
        rows = prediction["itemScores"]
        if len(rows) != num:
            return None
        if not all(r["item"].startswith(prefix) for r in rows):
            return None
        return (
            np.asarray([int(r["item"][len(prefix):]) for r in rows]),
            np.asarray([float(r["score"]) for r in rows], np.float64),
        )
    except (KeyError, TypeError, ValueError, AttributeError):
        return None


def control_answers(users, items, user_idx, num, control, other=None):
    """The planted fault's answers for the queries of ``user_idx``:
    ``fp8`` as `reference.control_answers`; ``wrong_blob`` the exact top
    ``num`` of ``other`` (another tenant's ``(users, items)``)."""
    if control == "wrong_blob":
        wrong_users, wrong_items = other
        scores = reference.reference_scores(wrong_users[user_idx], wrong_items)
        idx, top = reference.top_k(scores, num)
        return [(idx[q], top[q]) for q in range(len(user_idx))]
    return reference.control_answers(users, items, user_idx, num, control)


def cold_shortfall(cold_queries: int, queries: int) -> float:
    """Compared queries missing for `COLD_SHARE` of the sample to come
    from tenants loaded inside the window; an empty sample is short."""
    if queries <= 0:
        return 1.0
    return float(max(0, math.ceil(COLD_SHARE * queries) - cold_queries))


def shortfall(least: float, found: float) -> float:
    return float(max(0.0, least - found))
