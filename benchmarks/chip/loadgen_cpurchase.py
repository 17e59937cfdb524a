"""The complementary-purchase cell's load generator: carts in posts of
``batch`` to /batch/queries.json, closed loop. Standard library and numpy
only, as `loadgen.py` is, whose clock, post and result shapes it keeps:
run as a script it reads one JSON plan on standard input and prints one
JSON result.

A cart: its size log-normal (``cart_items``: median, sigma, clipped to
[min, max]), its items distinct and drawn by popularity, Zipf over the
catalog by rank (item 0 the most popular, as the seeded model has it),
each named as the model's item map names it.
"""

from __future__ import annotations

import http.client
import json
import os
import sys
import time

import numpy as np

from loadgen import REQUEST_TIMEOUT_S, _post, _run_threads, zipf_weights


def item_name(i: int) -> str:
    return f"p{i:05d}"


def cart_sizes(rng, n: int, spec: dict) -> np.ndarray:
    raw = np.exp(np.log(spec["median"]) + spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def draw_carts(rng, cdf: np.ndarray, n: int, spec: dict) -> list[list[int]]:
    """``n`` carts of distinct item indices, each in the order drawn."""
    carts = []
    for size in cart_sizes(rng, n, spec).tolist():
        cart: dict[int, None] = {}
        while len(cart) < size:
            draws = np.searchsorted(cdf, rng.random(2 * size), side="right")
            for item in np.minimum(draws, len(cdf) - 1).tolist():
                cart.setdefault(item)
                if len(cart) == size:
                    break
        carts.append(list(cart))
    return carts


def _count_ok(data: bytes, expect: int) -> int:
    """Queries of a batch reply answered with a list of items (the
    reference judges how many; a cart may have fewer than ``num``
    complements)."""
    try:
        slots = json.loads(data)
        return sum(
            1 for s in slots[:expect]
            if s.get("status") == 200 and isinstance(s["prediction"]["itemScores"], list)
        )
    except (ValueError, KeyError, TypeError, AttributeError):
        return 0


def run_closed(plan: dict) -> dict:
    host, port = plan["host"], plan["port"]
    t_window, t_end = plan["t_window"], plan["t_end"]
    batch, num = plan["batch"], plan["num"]
    cdf = np.cumsum(zipf_weights(plan["n_items"], plan["item_zipf_exponent"]))
    results = [[] for _ in range(plan["clients"])]
    kept = [[] for _ in range(plan["clients"])]

    def client(w: int) -> None:
        rng = np.random.default_rng([plan["seed"], plan["proc"], w])
        conn = http.client.HTTPConnection(host, port, timeout=REQUEST_TIMEOUT_S)
        seen = 0
        while time.monotonic() < t_end:
            carts = draw_carts(rng, cdf, batch, plan["cart_items"])
            body = json.dumps([
                {"items": [item_name(i) for i in cart], "num": num} for cart in carts
            ]).encode()
            try:
                status, data = _post(conn, "/batch/queries.json", body)
                ok = _count_ok(data, batch) if status == 200 else 0
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
                entry = (carts, data.decode("utf-8", "replace"))
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
