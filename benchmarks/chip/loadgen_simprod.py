"""The similar-product cell's seeded data and its load generator. Standard
library and numpy only, so that it can run in child processes that share
no interpreter lock with the server. What `loadgen` and `loadgen_ecomm`
have is used from there (the categories of a tenant's items are
`loadgen_ecomm.item_categories`: the same catalogue as the e-commerce
cell's).

Data, from (seed, tenant): which rows of the ``like`` table are zero
(`like_rows`: at the source 2.9 M ``fav`` events fall on 4.16 M items, so
most items have no ``like`` vector).

Run as a script it reads one JSON plan on standard input, drives the
server named there in a closed loop (``clients`` threads, each posting
``batch`` queries of one Zipf-chosen tenant to /batch/queries.json and
sending the next post when the reply has come), and prints one JSON
result of the shape `loadgen.run_closed` prints: ``posts`` and ``kept``
(a reservoir of ``(tenant, queries, reply)`` from inside the window).

A query of the mix, from the seed. Its items: 50% one item, 35% 2-8, 13%
9-16, 2% 17-50 (uniform inside each range), drawn uniformly from the
tenant's catalogue; ``unknown_item_share`` of the ids are unknown to the
model, and ``unknown_query_share`` of the queries hold unknown ids only.
Its filter: 50% none, 30% one category drawn in proportion to its size,
15% a blackList of 1-20 items, 5% a whiteList of 50-200.
"""

from __future__ import annotations

import http.client
import json
import os
import sys
import time

import numpy as np

from loadgen import REQUEST_TIMEOUT_S, _post, _run_threads, zipf_weights
from loadgen_ecomm import count_ok

#: (share of the queries, fewest items, most items) of a query's basket
BASKETS = ((0.50, 1, 1), (0.35, 2, 8), (0.13, 9, 16), (0.02, 17, 50))


def like_rows(seed: int, tenant: int, n_items: int, coverage: float) -> np.ndarray:
    """[I] bool: the items that have a ``like`` vector, ``coverage`` of
    the catalogue; the other rows of the tenant's ``like`` table are 0."""
    return np.random.default_rng([seed, tenant, 7]).random(n_items) < coverage


def draw_basket(rng, plan: dict) -> list[str]:
    """One query's ``items``."""
    shape, n = rng.random(), 1
    for share, low, high in BASKETS:
        if shape < share:
            n = int(rng.integers(low, high + 1))
            break
        shape -= share
    rows = rng.integers(0, plan["n_items"], n)
    unknown = rng.random(n) < plan["unknown_item_share"]
    if rng.random() < plan["unknown_query_share"]:
        unknown[:] = True
    # an id past the catalogue's last is one the model does not know
    return [
        f"i{int(r) + plan['n_items']}" if u else f"i{int(r)}"
        for r, u in zip(rows, unknown)
    ]


def draw_queries(rng, plan: dict, cat_weights: np.ndarray) -> list[dict]:
    """One post's queries of one tenant."""
    n_items, num = plan["n_items"], plan["num"]
    out = []
    for _ in range(plan["batch"]):
        query = {"items": draw_basket(rng, plan), "num": num}
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


def run_closed(plan: dict) -> dict:
    host, port = plan["host"], plan["port"]
    t_window, t_end = plan["t_window"], plan["t_end"]
    batch, num = plan["batch"], plan["num"]
    names = plan["tenants"]
    weights = zipf_weights(len(names), plan["zipf_exponent"])
    cat_weights = zipf_weights(plan["n_categories"], 1.0)
    results = [[] for _ in range(plan["clients"])]
    kept = [[] for _ in range(plan["clients"])]

    def client(w: int) -> None:
        rng = np.random.default_rng([plan["seed"], plan["proc"], w])
        conn = http.client.HTTPConnection(host, port, timeout=REQUEST_TIMEOUT_S)
        seen = 0
        while time.monotonic() < t_end:
            tenant = int(rng.choice(len(weights), p=weights))
            queries = draw_queries(rng, plan, cat_weights)
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
