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

Two parameters are off unless the traffic file names them, and with both
absent every draw is what it was before they came (a test holds the
digests): ``user_zipf_exponent`` (both loops: a request's user is drawn
Zipf over the tenant's own order of its users, `user_order`) and ``burst``
(open loop only: ``{"period_s": p, "on_share": s}``, each period's
arrivals in its first ``s * p`` seconds, `arrival_schedule`).
"""

from __future__ import annotations

import gc
import http.client
import json
import math
import os
import sys
import threading
import time

import numpy as np

REQUEST_TIMEOUT_S = 30.0
#: the traffic parameters a plan carries only where the traffic file names them
OPTIONAL_PARAMETERS = ("user_zipf_exponent", "burst")


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


def user_order(seed: int, tenant: int, n_users: int) -> tuple[int, int]:
    """``(step, start)`` of a tenant's order of its users by popularity:
    its user of rank ``r`` is ``(start + step * r) % n_users``, a
    permutation of the users (``step`` is coprime to ``n_users``) seeded by
    (seed, tenant). So each tenant has hot users of its own, a hot user is
    no low row number, and no table of a million rows a tenant is kept."""
    rng = np.random.default_rng([seed, 0x25E2, tenant])
    start = int(rng.integers(n_users))
    step = 1
    while n_users > 2 and (step == 1 or math.gcd(step, n_users) != 1):
        step = int(rng.integers(2, n_users))
    return step, start


def user_skew(plan: dict):
    """``(cdf, orders)`` of the users' Zipf where the plan names
    ``user_zipf_exponent``: the cumulated weights of the ranks, and each
    tenant's `user_order` as a row. None where it does not."""
    exponent = plan.get("user_zipf_exponent")
    if exponent is None:
        return None
    n_users = plan["n_users"]
    orders = [
        user_order(plan["seed"], t, n_users) for t in range(len(plan["tenants"]))
    ]
    return np.cumsum(zipf_weights(n_users, exponent)), np.array(orders, np.int64)


def zipf_users(rng, skew, tenants) -> np.ndarray:
    """A user for each entry of ``tenants``: one uniform number each, its
    rank by the cumulated weights, the rank's user in that tenant's order."""
    cdf, orders = skew
    ranks = np.searchsorted(cdf, rng.random(len(tenants)), side="right")
    step, start = orders[np.asarray(tenants)].T
    return (start + step * np.minimum(ranks, len(cdf) - 1)) % len(cdf)


def arrival_schedule(
    seed: int, rate: float, seconds: float, tenants: int, exponent: float,
    block_s: float = 1.0, burst: dict | None = None,
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
    one seed (my chip runs, PR 24).

    With ``burst`` (``{"period_s": p, "on_share": s}``, 0 < s <= 1) a block
    is a period: the same arrivals as a block of ``p`` seconds, scaled into
    its first ``s * p`` seconds, then silence. The mean rate over a period
    stays ``rate``, the rate inside a burst is ``rate / s``."""
    if burst:
        block_s, on_share = float(burst["period_s"]), float(burst["on_share"])
        if not (block_s > 0 and 0 < on_share <= 1):
            raise ValueError(f"burst wants period_s > 0 and 0 < on_share <= 1: {burst}")
    rng = np.random.default_rng([seed, 0x5EED])
    per_block = max(1, int(round(rate * block_s)))
    gaps = -np.log(1.0 - (np.arange(per_block) + 0.5) / per_block)
    gaps *= block_s / gaps.sum()
    if burst:
        gaps *= on_share
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


def draw_post(rng, plan: dict, weights, skew=None):
    """The closed loop's next post, from the client's one generator: its
    tenant, then its users."""
    tenant = int(rng.choice(len(weights), p=weights))
    if skew is None:
        users = rng.integers(0, plan["n_users"], plan["batch"])
    else:
        users = zipf_users(rng, skew, np.full(plan["batch"], tenant))
    return tenant, users.tolist()


def draw_open(plan: dict):
    """What one process of the open loop sends, all drawn before it starts:
    ``(offsets, tenants, users, keep)`` of its share of the schedule, the
    last the set of its requests whose answers it keeps for the check. Its
    generator draws all users at once, then the kept sample."""
    t_start = plan["t_start"]
    offsets, who = arrival_schedule(
        plan["seed"], plan["rate"], plan["t_end"] - t_start,
        len(plan["tenants"]), plan["zipf_exponent"], burst=plan.get("burst"),
    )
    mine = np.arange(plan["proc"], len(offsets), plan["procs"])
    rng = np.random.default_rng([plan["seed"], plan["proc"]])
    tenants = who[mine]
    skew = user_skew(plan)
    if skew is None:
        users = rng.integers(0, plan["n_users"], len(mine))
    else:
        users = zipf_users(rng, skew, tenants)
    in_window = np.flatnonzero(offsets[mine] >= plan["t_window"] - t_start)
    keep = set(
        rng.choice(
            in_window, min(plan["keep"], len(in_window)), replace=False
        ).tolist()
    )
    return offsets[mine], tenants, users, keep


def run_closed(plan: dict) -> dict:
    host, port = plan["host"], plan["port"]
    t_window, t_end = plan["t_window"], plan["t_end"]
    batch, num = plan["batch"], plan["num"]
    names = plan["tenants"]
    weights = zipf_weights(len(names), plan["zipf_exponent"])
    skew = user_skew(plan)
    results = [[] for _ in range(plan["clients"])]
    kept = [[] for _ in range(plan["clients"])]

    def client(w: int) -> None:
        rng = np.random.default_rng([plan["seed"], plan["proc"], w])
        conn = http.client.HTTPConnection(host, port, timeout=REQUEST_TIMEOUT_S)
        seen = 0
        while time.monotonic() < t_end:
            tenant, users = draw_post(rng, plan, weights, skew)
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
    offsets, tenants, users, keep = draw_open(plan)
    bodies = [
        json.dumps({"user": f"u{u}", "num": num}).encode() for u in users
    ]
    rows = [None] * len(offsets)
    kept = []
    lock = threading.Lock()
    cursor = [0]

    def client(w: int) -> None:
        conn = http.client.HTTPConnection(host, port, timeout=REQUEST_TIMEOUT_S)
        while True:
            with lock:
                k = cursor[0]
                cursor[0] += 1
            if k >= len(offsets):
                break
            due = t_start + float(offsets[k])
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
