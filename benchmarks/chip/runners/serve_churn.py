"""Runner of the churn cell: an in-process multi-tenant `EngineServer` of the
`recommendation` template whose tenants are more than its model pool holds,
served over real HTTP on localhost and driven by `loadgen.py` as the other
batch cells are.

One run: publish every tenant's model into the `memory` model store as the
bytes a train would publish (`modelstore_churn`), hand the server its
tenants in an order shuffled by the seed and let its own preload fill the
pool, settle until LRU holds its steady content, measure the window while
the pool evicts and reloads under the traffic, read the device's peak
memory, close the server, and only then hold a sample of the window's own
answers against the reference: a third of it or more from posts whose
tenant was loaded inside the window (`reference_churn`). The registry is
read at both ends of every window, traced or not: the misses by tenant say
which tenants those are, and the evictions, leaked threads and the pool's
budget are among the numbers `correct` is decided on. What `serve_http`
and `serve_ecomm` have is used from there. ``--control fp8`` and
``wrong_blob`` plant their fault in the answers that are compared;
``--control kept_generation`` plants it in the run: a generation kept past
its eviction, which the run's own ``over_ledger_gib`` has to show.
"""

from __future__ import annotations

import gc
import json
import os
import shutil
import tempfile
import time

import numpy as np

import layer_metrics
import modelstore
import modelstore_churn
import reference_churn
import roofline
import trace_reduce
from runners import serve_http
from runners.serve_ecomm import stage_means
from runners.serve_http import (
    _sleep_until, cell_metrics, collect, generator_parameters, send_plans,
    split_cores, start_generators, window_numbers,
)

ENGINE_ID = "chipbench"
#: what this runner reads of a configuration's file, beside the general keys
CONFIG_KEYS = (
    "n_users", "n_items", "rank", "tenants", "num", "zipf_exponent",
    "quantize", "table_format", "jit_names", "min_evictions",
    "pool_budget_bytes",
)
#: the handler's stages between the two instants `pio_http_request_seconds`
#: is taken over (`pool.wait` among them: only a missed request has one)
REQUEST_STAGES = (
    "http.admit", "engine.decode", "pool.wait", "engine.submit",
    "engine.await", "engine.serve",
)
#: the stages nested in `pool.load`
LOAD_STAGES = (
    "pool.read", "pool.deserialize", "pool.promote", "pool.warmup",
    "pool.batchers",
)

def build_server(config: dict, storage, tenants, devices, own_pool=False):
    """The server as `pio-tpu deploy` builds it, over the published
    tenants; the pool's budget is deploy's default unless the
    configuration states one (``pool_budget_bytes``: the tiny size of a
    rehearsal, or a cut named under ``assumed``). With ``own_pool`` the
    pool of the default budget is built here and handed over, as the
    server would build it: the caller can then pin a tenant itself."""
    from predictionio_tpu.models.recommendation import recommendation_engine
    from predictionio_tpu.obs.registry import MetricRegistry
    from predictionio_tpu.parallel.mesh import ComputeContext
    from predictionio_tpu.serving.engine_server import EngineServer
    from predictionio_tpu.serving.modelpool import ModelPool

    registry = MetricRegistry()
    pool = None
    if config["pool_budget_bytes"] or own_pool:
        pool = ModelPool(config["pool_budget_bytes"], registry=registry)
    server = EngineServer(
        recommendation_engine(),
        modelstore.engine_params(config["rank"]),
        engine_id=ENGINE_ID,
        storage=storage,
        ctx=ComputeContext.create(batch="chipbench", devices=devices),
        tenants=tenants,
        quantize=config["quantize"] or "",
        registry=registry,
        pool=pool,
        **config["server"],
    )
    http = server.serve(host="127.0.0.1", port=0)
    http.start()
    return server, http, registry, pool


def watch_window(times, registry, traffic, trace_dir: str | None) -> dict:
    """The registry at both ends of the window, traced or not; a traced
    run is `serve_http.watch_window`'s, which takes them too."""
    if trace_dir:
        return serve_http.watch_window(times, registry, traffic, trace_dir)
    _sleep_until(times["t_window"])
    before = registry.to_dict()
    _sleep_until(times["t_end"])
    return {"before": before, "after": registry.to_dict()}


def _total(snapshot: dict, family: str, field: str = "value") -> float:
    return sum(
        float(s.get(field) or 0.0)
        for s in layer_metrics.samples(snapshot, family, {})
    )


def cold_tenants(seen: dict) -> set[int]:
    """The tenants (by index) that were loaded inside the window: those
    whose `pio_pool_misses_total` grew over it."""
    def by_tenant(snapshot):
        return {
            s["labels"]["tenant"]: float(s.get("value") or 0.0)
            for s in layer_metrics.samples(snapshot, "pio_pool_misses_total", {})
        }

    before, after = by_tenant(seen["before"]), by_tenant(seen["after"])
    return {
        int(name[1:]) for name, value in after.items()
        if value > before.get(name, 0.0)
    }


def pool_numbers(seen: dict, built: dict) -> dict:
    """What the pool did: over set-up (until the server was built), over
    the settle phase, and over the window."""
    def count(snapshot, family):
        return int(_total(snapshot, family))

    def loads(snapshot):
        found = layer_metrics.samples(
            snapshot, "pio_stage_seconds", {"stage": "pool.load"}
        )
        return sum(int(s.get("count") or 0) for s in found)

    before, after = seen["before"], seen["after"]
    misses = "pio_pool_misses_total"
    evictions = "pio_pool_evictions_total"
    return {
        "budget_bytes": int(_total(after, "pio_pool_budget_bytes")),
        "loads_in_setup": loads(built),
        "evictions_in_setup": count(built, evictions),
        "loads_in_settle": loads(before) - loads(built),
        "evictions_in_settle": count(before, evictions) - count(built, evictions),
        "loads": loads(after) - loads(before),
        "evictions": count(after, evictions) - count(before, evictions),
        "misses": count(after, misses) - count(before, misses),
        "lookups": count(after, misses) - count(before, misses)
        + count(after, "pio_pool_hits_total") - count(before, "pio_pool_hits_total"),
        "resident_at_end": count(after, "pio_pool_tenants_resident"),
        "resident_bytes_at_end": int(_total(after, "pio_pool_resident_bytes")),
        "leaked_threads": count(after, "pio_batcher_leaked_threads_total")
        - count(before, "pio_batcher_leaked_threads_total"),
        **wait_numbers(seen),
    }


def wait_numbers(seen: dict) -> dict:
    """What a miss cost over the window, traced or not: the mean of a
    load and of a missed post's wait in ms, the seconds the posts spent
    waiting and the seconds and count of the requests they are part of (all
    clients together: what is left of a request is a hit's path), and the
    waits counted by the bucket of `pio_stage_seconds` they fell in (its
    upper bound in seconds: a wait past one load's time stood behind other
    loads)."""
    def stage(field, name="pool.wait"):
        return layer_metrics.delta(
            seen, "pio_stage_seconds", {"stage": name}, field
        )

    def buckets(snapshot):
        found = layer_metrics.samples(
            snapshot, "pio_stage_seconds", {"stage": "pool.wait"}
        )
        return next(iter(found), {}).get("buckets", {})

    def requests(field):
        return layer_metrics.delta(
            seen, "pio_http_request_seconds", {"service": "engine"}, field
        )

    before, after = buckets(seen["before"]), buckets(seen["after"])
    waits, loads = stage("count"), stage("count", "pool.load")
    return {
        "load_ms": 1e3 * stage("sum", "pool.load") / loads if loads else None,
        "wait_ms": 1e3 * stage("sum") / waits if waits else None,
        "wait_s": stage("sum"),
        "request_s": requests("sum"),
        "requests": int(requests("count")),
        "waits_by_bucket_s": {
            le: int(n - before.get(le, 0)) for le, n in after.items()
            if n - before.get(le, 0)
        },
    }


def keep_generation(pool, tenants, built: dict):
    """The planted fault of ``--control kept_generation``: a reference to
    one staged generation (through its batchers' closures, its tables on
    the device) that outlives its eviction, as a closure or a cycle in the
    server would keep it. The least popular tenant the preload staged is
    taken, the surest to be evicted; the run's own ``over_ledger_gib`` then
    has to read over its limit. Returns ``(tenant, generation)``."""
    staged = {
        s["labels"]["tenant"]
        for s in layer_metrics.samples(built, "pio_pool_misses_total", {})
    }
    tenant = max(t for t in tenants if t in staged)
    with pool.pin(tenant, None) as generation:
        return tenant, generation


def kept_answers(results, config):
    """``[(tenant, [(user, answer or None)])]`` of every kept reply, in
    an order that no run's timing moves."""
    kept = sorted(
        (k for r in results for k in r["kept"]), key=lambda k: (k[0], k[1])
    )
    out = []
    for tenant, users, text in kept:
        try:
            body = json.loads(text)
        except ValueError:
            body = None
        slots = body if isinstance(body, list) else []
        slots = slots + [None] * (len(users) - len(slots))
        name = modelstore.tenant_name(tenant)
        out.append((tenant, list(zip(users, [
            reference_churn.parse_answer(
                (s or {}).get("prediction"), config["num"], name
            )
            for s in slots[:len(users)]
        ]))))
    return out


def sample_answers(kept, traffic, seed, cold: set[int]) -> dict:
    """``{tenant: [(user, answer)]}``: a sample, drawn from the seed, of
    the answers the window itself produced. First the posts of tenants
    loaded inside the window, over at most half of ``check_tenants``
    tenants and up to half of ``check_queries``; then what is left, the
    other tenants' posts first, up to the whole of both."""
    rng = np.random.default_rng([seed, 0xC4EC])
    order = [kept[i] for i in rng.permutation(len(kept))]
    cold_posts = [k for k in order if k[0] in cold]
    sample: dict[int, list] = {}
    taken: set[int] = set()
    n = 0
    for posts, tenants_cap, queries_cap in (
        (cold_posts, traffic["check_tenants"] // 2, traffic["check_queries"] // 2),
        ([k for k in order if k[0] not in cold] + cold_posts,
         traffic["check_tenants"], traffic["check_queries"]),
    ):
        for post in posts:
            tenant, pairs = post
            if n >= queries_cap:
                break
            if id(post) in taken or (
                tenant not in sample and len(sample) >= tenants_cap
            ):
                continue
            taken.add(id(post))
            sample.setdefault(tenant, []).extend(pairs)
            n += len(pairs)
    return sample


def compare(sample, config, seed, cold, control=None) -> dict[str, float]:
    """The reference over the sample, tenant by tenant and 64 queries at a
    time; with ``control`` a planted fault's answers stand in for the
    served ones (``wrong_blob``: in the tenants loaded inside the window)."""
    def tables(tenant):
        return modelstore.host_factors(
            seed, tenant, config["n_users"], config["n_items"], config["rank"]
        )

    comparison = reference_churn.Comparison(config["num"])
    for tenant, pairs in sorted(sample.items()):
        users, items = tables(tenant)
        planted = control and (control != "wrong_blob" or tenant in cold)
        other = (
            tables((tenant + 1) % config["tenants"])
            if planted and control == "wrong_blob" else None
        )
        for at in range(0, len(pairs), 64):
            idx = [p[0] for p in pairs[at:at + 64]]
            answers = [p[1] for p in pairs[at:at + 64]]
            if planted:
                answers = reference_churn.control_answers(
                    users, items, np.asarray(idx), config["num"], control, other
                )
            comparison.add(users, items, idx, answers)
    return comparison.numbers()


def cover(sums: dict, parts, whole: float | None) -> float | None:
    """The share of ``whole`` (seconds over the window) that the seconds
    of the stages ``parts`` add up to."""
    if not whole:
        return None
    return sum(sums.get(p, 0.0) for p in parts) / whole


def stage_sums(gathered: dict) -> dict[str, float]:
    """Seconds of every stage over the window (`pio_stage_seconds`)."""
    return {
        s["labels"]["stage"]: layer_metrics.delta(
            gathered, "pio_stage_seconds", {"stage": s["labels"]["stage"]}, "sum"
        )
        for s in layer_metrics.samples(gathered["after"], "pio_stage_seconds", {})
    }


def run(cell, bench, config, traffic, args, t_process_start, device) -> dict:
    """One run of the cell; returns the result line as a dictionary."""
    from predictionio_tpu.obs import tracing

    if not hasattr(tracing, "POOL_LOAD"):
        raise SystemExit(
            "this program's model pool has no `pool.load` stage (a cold load "
            "is timed by nothing, and the preload stages every tenant): the "
            "cell cannot run on it"
        )
    import jax

    from predictionio_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    generator_parameters(traffic)  # a mix the generator cannot follow ends here
    server_cores, generator_cores = split_cores(traffic["generator_cores"])
    if server_cores:
        os.sched_setaffinity(0, server_cores)
    procs = start_generators(traffic)
    server = http = pool = None
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if args.trace else None
    phases = {"start_s": time.monotonic() - t_process_start}
    try:
        storage, tenants, published = modelstore_churn.publish_tenants(
            ENGINE_ID, args.seed, config["tenants"],
            config["n_users"], config["n_items"], config["rank"],
        )
        phases["published_s"] = time.monotonic() - t_process_start
        keep = args.control == "kept_generation"
        server, http, registry, pool = build_server(
            config, storage, tenants, jax.devices()[:cell["chips"]], keep
        )
        built = registry.to_dict()
        kept = keep_generation(pool, tenants, built) if keep else None
        # nothing allocated in set-up is looked at by a collection again
        gc.collect()
        gc.freeze()
        phases["server_s"] = time.monotonic() - t_process_start
        times = send_plans(
            procs, traffic, config, http.port, args.seed, args.seconds,
            generator_cores,
        )
        seen = watch_window(times, registry, traffic, trace_dir)
        results = collect(procs)
        events = trace_reduce.load_events(trace_dir) if trace_dir else []
        stats = jax.devices()[0].memory_stats() or {}
        memory_peak = int(stats.get("peak_bytes_in_use", 0))
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        if http is not None:
            http.shutdown()
        if server is not None:
            server.close()
        if pool is not None:
            pool.close()
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    # the program's state goes before the reference runs
    del server, http, pool, storage
    gc.unfreeze()
    gc.collect()
    # what is still on the device now was kept by something else than the
    # server and its pool: nothing in a sound run, a tenant's tables under
    # `--control kept_generation`
    alive_after_close = sum(a.nbytes for a in jax.live_arrays())
    kept_tenant = kept[0] if kept else None
    del kept

    load = window_numbers(traffic, times, results, args.seconds)
    load["setup_s"] = times["t_window"] - t_process_start
    lo, hi = times["t_window"], times["t_end"]
    failed_posts = sum(
        1 for r in results for p in r["posts"] if lo <= p[0] <= hi and p[2]
    )
    cold = cold_tenants(seen)
    pooled = pool_numbers(seen, built)
    sample = sample_answers(kept_answers(results, config), traffic, args.seed, cold)
    queries = sum(len(v) for v in sample.values())
    cold_queries = sum(len(v) for t, v in sample.items() if t in cold)
    t_reference = time.monotonic()
    numbers = compare(sample, config, args.seed, cold)
    reference_s = time.monotonic() - t_reference
    over_ledger_gib = (memory_peak - pooled["budget_bytes"]) / 2**30
    numbers.update(
        unanswered=float(load["unanswered"]),
        failed_posts=float(failed_posts),
        cold_sample_short=reference_churn.cold_shortfall(cold_queries, queries),
        missing_evictions=reference_churn.shortfall(
            config["min_evictions"], pooled["evictions"]
        ),
        leaked_threads=float(pooled["leaked_threads"]),
        over_ledger_gib=over_ledger_gib,
    )
    correct, compared = reference_churn.judge(numbers, config["limits"])
    device = {**device, "memory_peak_bytes": memory_peak}
    result = {
        "correct": correct,
        "attempted": load["attempted"], "failed": load["failed"],
    }
    if args.trace:
        trace = trace_reduce.reduce(
            events, ["jit_" + n for n in config["jit_names"]]
        )
        gathered = {
            "before": seen["before"], "after": seen["after"], "trace": trace,
            "traced_queries": seen["traced_queries"], "load": load,
            "config": config, "traffic": traffic, "built": built,
            "memory_peak_bytes": memory_peak,
            "peak": roofline.peaks(device["kind"]) if trace else None,
        }
        wanted = cell_metrics(bench, "per_layer", cell["name"])
        values = {m["name"]: layer_metrics.read(m["name"], gathered) for m in wanted}
        means, sums = stage_means(gathered), stage_sums(gathered)
        posts = layer_metrics.delta(
            gathered, "pio_http_request_seconds",
            {"service": "engine", "route": traffic["route"]}, "sum",
        )
        result["breakdown"] = {
            "stage_ms": means,
            # seconds of the handler's stages over the seconds of the
            # requests, and of the load's nested stages over the loads'
            "handler_cover": cover(sums, REQUEST_STAGES, posts),
            "load_cover": cover(sums, LOAD_STAGES, sums.get("pool.load")),
        }
        if trace:
            device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
            result["breakdown"].update(
                device_ops=trace["device_ops"], idle_gaps=trace["idle_gaps"],
            )
    else:
        wanted = cell_metrics(bench, "end_to_end", cell["name"])
        values = {m["name"]: load.get(m["name"]) for m in wanted}
    result["metrics"] = {
        m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
        for m in wanted if values[m["name"]] is not None
    }
    result["device"] = device
    result["pool"] = {
        **pooled, "published_bytes": published, "cold_tenants": len(cold),
        "alive_after_close_bytes": alive_after_close,
    }
    result["sampled"] = {
        "tenants": len(sample), "queries": queries,
        "cold_tenants": sum(1 for t in sample if t in cold),
        "cold_queries": cold_queries, "reference_s": reference_s,
    }
    result["phases"] = phases
    if args.control == "kept_generation":
        # the fault was in the run itself: `correct` above is its verdict
        result["control"] = {
            "kept_tenant": kept_tenant,
            "evictions_of_kept": int(sum(
                float(s.get("value") or 0.0) for s in layer_metrics.samples(
                    seen["after"], "pio_pool_evictions_total",
                    {"tenant": kept_tenant},
                )
            )),
        }
    elif args.control:
        result["control"] = compare(sample, config, args.seed, cold, args.control)
    result["compared"] = compared
    return result
