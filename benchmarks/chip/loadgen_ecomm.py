"""The e-commerce cell's seeded data and its load generator. Standard
library and numpy only, so that it can run in child processes that share
no interpreter lock with the server.

Data, from the seed: the active users and their seen lists (one
population for every tenant), and from (seed, tenant) the category of
every item, the unknown users and their recent views, the unavailable
items. The runner writes the events and builds the tenants
from these; the reference is given the same arrays.

Run as a script it reads one JSON plan on standard input, drives the
server named there in a closed loop (``clients`` threads, each posting
``batch`` queries of one Zipf-chosen tenant to /batch/queries.json and
sending the next post when the reply has come), and prints one JSON
result of the shape `loadgen.run_closed` prints: ``posts`` and ``kept``
(a reservoir of ``(tenant, queries, reply)`` from inside the window).

A query of the mix, from the seed: the user is one of the tenant's active
users (uniform), or with ``unknown_share`` an id the model does not know,
half of those with 1-10 views in the store; 50% carry nothing else, 30%
one category drawn in proportion to its size, 15% a blackList of 1-20
items, 5% a whiteList of 50-200.
"""

from __future__ import annotations

import http.client
import json
import os
import sys
import time

import numpy as np

from loadgen import REQUEST_TIMEOUT_S, _post, _run_threads, zipf_weights

#: unknown users a tenant has of each kind: ``x<j>`` with views, ``n<j>``
#: with none
UNKNOWN_USERS = 256
#: seen lists: log-normal, this mean and sigma, clipped to 1..4096
SEEN_MEAN, SEEN_SIGMA, SEEN_MAX = 93.0, 1.0, 4096
#: ``buy`` among the seen events: 2,015,839 of 91,732,103
BUY_SHARE = 2015839 / 91732103


def _rng(seed: int, tenant: int, what: int):
    return np.random.default_rng([seed, tenant, what])


def category_sizes(n_items: int, n_categories: int) -> np.ndarray:
    """Items per category, Zipf 1.0 over the categories by number
    (category 0 the largest), largest remainders, every category at least
    one item."""
    exact = zipf_weights(n_categories, 1.0) * n_items
    whole = np.maximum(np.floor(exact).astype(np.int64), 1)
    order = np.argsort(exact - np.floor(exact))[::-1]
    short = n_items - int(whole.sum())
    if short >= 0:
        whole[order[:short]] += 1
    else:  # the floors of one took more than there is: the largest give
        whole[: -short] -= 1
    return whole


def item_categories(seed: int, tenant: int, n_items: int, n_categories: int) -> np.ndarray:
    """[I] int32: the category number of every item."""
    sizes = category_sizes(n_items, n_categories)
    return _rng(seed, tenant, 1).permutation(
        np.repeat(np.arange(n_categories, dtype=np.int32), sizes)
    )


def active_users(seed: int, n_users: int, n_active: int) -> np.ndarray:
    """The user rows that have events, the same in every tenant (the
    tenants' shops share one seeded population; factors, categories,
    unknown users and unavailable items are each tenant's own)."""
    return np.sort(np.random.default_rng([seed, 2]).choice(n_users, n_active, replace=False))


def seen_lists(seed: int, n_items: int, n_active: int) -> list[np.ndarray]:
    """Distinct item rows per active user, lengths log-normal."""
    rng = np.random.default_rng([seed, 3])
    mu = np.log(SEEN_MEAN) - SEEN_SIGMA ** 2 / 2
    lengths = np.clip(
        np.rint(rng.lognormal(mu, SEEN_SIGMA, n_active)), 1, min(SEEN_MAX, n_items)
    ).astype(np.int64)
    drawn = rng.integers(0, n_items, int(lengths.sum()))
    return [np.unique(part) for part in np.split(drawn, np.cumsum(lengths)[:-1])]


def unknown_views(seed: int, tenant: int, n_items: int) -> dict[str, np.ndarray]:
    """``x<j>`` -> item rows of their 1-10 views, newest first."""
    rng = _rng(seed, tenant, 4)
    return {
        f"x{j}": rng.integers(0, n_items, int(rng.integers(1, 11)))
        for j in range(UNKNOWN_USERS)
    }


def unavailable_items(seed: int, tenant: int, n_items: int) -> np.ndarray:
    """1% of the catalog, as sorted rows."""
    return np.sort(_rng(seed, tenant, 5).choice(n_items, max(1, n_items // 100), replace=False))


def draw_queries(rng, plan: dict, active: np.ndarray, cat_weights: np.ndarray) -> list[dict]:
    """One post's queries of one tenant."""
    n_items, num = plan["n_items"], plan["num"]
    out = []
    for _ in range(plan["batch"]):
        if rng.random() < plan["unknown_share"]:
            kind = "x" if rng.random() < 0.5 else "n"
            user = f"{kind}{int(rng.integers(UNKNOWN_USERS))}"
        else:
            user = f"u{int(active[rng.integers(len(active))])}"
        query = {"user": user, "num": num}
        shape = rng.random()
        if shape < 0.30:
            query["categories"] = [f"c{int(rng.choice(len(cat_weights), p=cat_weights))}"]
        elif shape < 0.45:
            query["blackList"] = [
                f"i{int(r)}" for r in rng.integers(0, n_items, int(rng.integers(1, 21)))
            ]
        elif shape < 0.50:
            query["whiteList"] = [
                f"i{int(r)}" for r in rng.integers(0, n_items, int(rng.integers(50, 201)))
            ]
        out.append(query)
    return out


def count_ok(data: bytes, expect: int, num: int) -> int:
    """Queries of a batch reply answered with status 200 and an
    ``itemScores`` list of at most ``num``."""
    try:
        slots = json.loads(data)
        return sum(
            1 for s in slots[:expect]
            if s.get("status") == 200
            and isinstance(s["prediction"]["itemScores"], list)
            and len(s["prediction"]["itemScores"]) <= num
        )
    except (ValueError, KeyError, TypeError, AttributeError):
        return 0


def run_closed(plan: dict) -> dict:
    host, port = plan["host"], plan["port"]
    t_window, t_end = plan["t_window"], plan["t_end"]
    batch, num = plan["batch"], plan["num"]
    names = plan["tenants"]
    weights = zipf_weights(len(names), plan["zipf_exponent"])
    cat_weights = zipf_weights(plan["n_categories"], 1.0)
    active = active_users(plan["seed"], plan["n_users"], plan["active_users"])
    results = [[] for _ in range(plan["clients"])]
    kept = [[] for _ in range(plan["clients"])]

    def client(w: int) -> None:
        rng = np.random.default_rng([plan["seed"], plan["proc"], w])
        conn = http.client.HTTPConnection(host, port, timeout=REQUEST_TIMEOUT_S)
        seen = 0
        while time.monotonic() < t_end:
            tenant = int(rng.choice(len(weights), p=weights))
            queries = draw_queries(rng, plan, active, cat_weights)
            body = json.dumps(queries).encode()
            try:
                status, data = _post(
                    conn, f"/batch/queries.json?accessKey={names[tenant]}", body
                )
                ok = count_ok(data, batch, num) if status == 200 else 0
                answered = True
            except (OSError, http.client.HTTPException):
                ok, answered, data = 0, False, b""
                conn.close()
                conn = http.client.HTTPConnection(host, port, timeout=REQUEST_TIMEOUT_S)
            done = time.monotonic()
            results[w].append((done, ok, batch - ok, answered))
            if answered and done >= t_window:
                # reservoir of this client's replies inside the window
                seen += 1
                entry = (tenant, queries, data.decode("utf-8", "replace"))
                if len(kept[w]) < plan["keep"]:
                    kept[w].append(entry)
                elif rng.random() < plan["keep"] / seen:
                    kept[w][int(rng.integers(plan["keep"]))] = entry
        conn.close()

    _run_threads(client, plan["clients"])
    return {
        "posts": [r for rs in results for r in rs],
        "kept": [k for ks in kept for k in ks],
    }


def main() -> int:
    plan = json.loads(sys.stdin.readline())
    if plan.get("cores"):
        os.sched_setaffinity(0, plan["cores"])
    json.dump(run_closed(plan), sys.stdout)
    sys.stdout.write("\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
