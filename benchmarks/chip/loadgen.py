"""The load generator: one general generator that reads a traffic mix's
parameters. Standard library and numpy only, so that it can run in child
processes that share no interpreter lock with the server.

Run as a script it reads one JSON plan on standard input, drives the
server named there, and prints one JSON result. Two loops:

Sending starts at ``t_start``; what is measured lies between ``t_window``
and ``t_end``, and the time before it settles connections and the server's
adaptive controllers at the cell's own load.

- ``closed``: ``clients`` threads; each posts ``batch`` queries of one
  tenant to /batch/queries.json and sends the next post when the reply has
  arrived. Counts every query by the instant its reply came.
- ``open``: single queries to /queries.json on a schedule of exponential
  gaps drawn from the seed (`arrival_schedule`), at ``rate`` per second
  over all generator processes. Each request is timed from the instant it
  was due; how late it was sent is reported beside it.

Tenant of a request: Zipf over the tenants. User: uniform. The clock is
``time.monotonic()``, which all processes of one machine share.
"""

from __future__ import annotations

import gc
import http.client
import json
import os
import sys
import threading
import time

import numpy as np

REQUEST_TIMEOUT_S = 30.0


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) of ``values``, linear between
    ranks; a failed request is in ``values`` as its time-out."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def zipf_weights(n: int, exponent: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** exponent
    return w / w.sum()


def _deal(counts: np.ndarray, n: int) -> np.ndarray:
    """``n`` items shared out in proportion to ``counts`` (largest
    remainders), as the index of each item's owner."""
    exact = counts / counts.sum() * n
    whole = np.floor(exact).astype(int)
    for i in np.argsort(exact - whole)[::-1][: n - whole.sum()]:
        whole[i] += 1
    return np.repeat(np.arange(len(counts)), whole)


def arrival_schedule(
    seed: int, rate: float, seconds: float, tenants: int, exponent: float,
    block_s: float = 1.0,
):
    """``(offsets, tenant of each arrival)`` of an open loop at ``rate``.

    Every seed gets the same work in another order: each block of
    ``block_s`` seconds holds the same set of exponential gaps (the
    distribution's quantiles, scaled to fill the block) and the same Zipf
    share of each tenant, and the seed shuffles both within the block. So
    arrivals cluster as a Poisson stream's do at the scale of a queue's
    memory (tens of ms), while no seed offers a run or a second more
    requests than another: with Poisson counts the tail followed the
    seed's clustering, by 8% between seeds against 3% between two runs of
    one seed (my chip runs, PR 24)."""
    rng = np.random.default_rng([seed, 0x5EED])
    per_block = max(1, int(round(rate * block_s)))
    gaps = -np.log(1.0 - (np.arange(per_block) + 0.5) / per_block)
    gaps *= block_s / gaps.sum()
    owners = _deal(zipf_weights(tenants, exponent), per_block)
    offsets, who = [], []
    early = gaps.min() / 2  # the block's last arrival lies inside it
    for b in range(int(np.ceil(seconds / block_s))):
        offsets.append(b * block_s - early + np.cumsum(rng.permutation(gaps)))
        who.append(rng.permutation(owners))
    offsets, who = np.concatenate(offsets), np.concatenate(who)
    keep = offsets < seconds
    return offsets[keep], who[keep]


def due_latencies_ms(due, done) -> np.ndarray:
    """Latency of each request from the instant it was due."""
    return (np.asarray(done, np.float64) - np.asarray(due, np.float64)) * 1e3


def _post(conn, path: str, body: bytes):
    conn.request("POST", path, body, {"Content-Type": "application/json"})
    resp = conn.getresponse()
    return resp.status, resp.read()


def _count_ok(data: bytes, expect: int, num: int) -> int:
    """Queries of a batch reply that were answered with ``num`` items."""
    try:
        slots = json.loads(data)
        return sum(
            1 for s in slots[:expect]
            if s.get("status") == 200
            and len(s["prediction"]["itemScores"]) == num
        )
    except (ValueError, KeyError, TypeError, AttributeError):
        return 0


def run_closed(plan: dict) -> dict:
    host, port = plan["host"], plan["port"]
    t_window, t_end = plan["t_window"], plan["t_end"]
    batch, num = plan["batch"], plan["num"]
    names = plan["tenants"]
    weights = zipf_weights(len(names), plan["zipf_exponent"])
    results = [[] for _ in range(plan["clients"])]
    kept = [[] for _ in range(plan["clients"])]

    def client(w: int) -> None:
        rng = np.random.default_rng([plan["seed"], plan["proc"], w])
        conn = http.client.HTTPConnection(host, port, timeout=REQUEST_TIMEOUT_S)
        seen = 0
        while time.monotonic() < t_end:
            tenant = int(rng.choice(len(weights), p=weights))
            users = rng.integers(0, plan["n_users"], batch).tolist()
            body = json.dumps(
                [{"user": f"u{u}", "num": num} for u in users]
            ).encode()
            try:
                status, data = _post(
                    conn, f"/batch/queries.json?accessKey={names[tenant]}", body
                )
                ok = _count_ok(data, batch, num) if status == 200 else 0
                answered = True
            except (OSError, http.client.HTTPException):
                ok, answered, data = 0, False, b""
                conn.close()
                conn = http.client.HTTPConnection(
                    host, port, timeout=REQUEST_TIMEOUT_S
                )
            done = time.monotonic()
            results[w].append((done, ok, batch - ok, answered))
            if answered and done >= t_window:
                # reservoir of this client's replies inside the window
                seen += 1
                entry = (tenant, users, data.decode("utf-8", "replace"))
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


def run_open(plan: dict) -> dict:
    host, port = plan["host"], plan["port"]
    t_start = plan["t_start"]
    num = plan["num"]
    offsets, who = arrival_schedule(
        plan["seed"], plan["rate"], plan["t_end"] - t_start,
        len(plan["tenants"]), plan["zipf_exponent"],
    )
    mine = np.arange(plan["proc"], len(offsets), plan["procs"])
    rng = np.random.default_rng([plan["seed"], plan["proc"]])
    tenants = who[mine]
    users = rng.integers(0, plan["n_users"], len(mine))
    bodies = [
        json.dumps({"user": f"u{u}", "num": num}).encode() for u in users
    ]
    in_window = np.flatnonzero(
        offsets[mine] >= plan["t_window"] - t_start
    )
    keep = set(
        rng.choice(
            in_window, min(plan["keep"], len(in_window)), replace=False
        ).tolist()
    )
    rows = [None] * len(mine)
    kept = []
    lock = threading.Lock()
    cursor = [0]

    def client(w: int) -> None:
        conn = http.client.HTTPConnection(host, port, timeout=REQUEST_TIMEOUT_S)
        while True:
            with lock:
                k = cursor[0]
                cursor[0] += 1
            if k >= len(mine):
                break
            due = t_start + float(offsets[mine[k]])
            wait = due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            sent = time.monotonic()
            try:
                status, data = _post(
                    conn,
                    f"/queries.json?accessKey={plan['tenants'][tenants[k]]}",
                    bodies[k],
                )
                answered = True
            except (OSError, http.client.HTTPException):
                status, data, answered = 0, b"", False
                conn.close()
                conn = http.client.HTTPConnection(
                    host, port, timeout=REQUEST_TIMEOUT_S
                )
            done = time.monotonic()
            ok = status == 200 and b"itemScores" in data
            rows[k] = (due, sent, done, ok, answered)
            if k in keep and answered:
                with lock:
                    kept.append((
                        int(tenants[k]), [int(users[k])],
                        data.decode("utf-8", "replace"),
                    ))
        conn.close()

    _run_threads(client, plan["clients"])
    return {"requests": rows, "kept": kept}


def _run_threads(target, n: int) -> None:
    # nothing allocated in set-up is looked at by a collection again
    gc.collect()
    gc.freeze()
    threads = [
        threading.Thread(target=target, args=(w,), daemon=True)
        for w in range(n)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def main() -> int:
    plan = json.loads(sys.stdin.readline())
    if plan.get("cores"):
        os.sched_setaffinity(0, plan["cores"])
    run = {"closed": run_closed, "open": run_open}[plan["loop"]]
    json.dump(run(plan), sys.stdout)
    sys.stdout.write("\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
