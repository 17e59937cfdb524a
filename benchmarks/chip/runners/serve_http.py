"""Runner of the serve cells: an in-process multi-tenant `EngineServer` on
`memory` storage, served over real HTTP on localhost and driven by
generator processes that share no interpreter lock with it.

One run: publish the seeded tenants, let the server's own loader stage and
warm them, settle at the cell's own load, measure the window, read the
device's peak memory, close the server, and only then hold a sample of the
window's own answers against the plain reference.
"""

from __future__ import annotations

import gc
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

import layer_metrics
import loadgen
import modelstore
import reference
import roofline
import trace_reduce

ENGINE_ID = "chipbench"
#: what this runner reads of a configuration's file, beside the general keys
#: every configuration has: `build_server`, `send_plans`, the roofline readers
CONFIG_KEYS = (
    "n_users", "n_items", "rank", "tenants", "num", "zipf_exponent",
    "quantize", "table_format", "jit_names",
)
_LOADGEN = os.path.join(os.path.dirname(os.path.abspath(loadgen.__file__)), "loadgen.py")
#: children answer within the request time-out after the window closes
_COLLECT_TIMEOUT_S = loadgen.REQUEST_TIMEOUT_S + 60.0


def split_cores(n_generator: int):
    """(server cores, generator cores): the generator gets the last cores
    of this process's set where there are enough, else nobody is pinned."""
    cores = sorted(os.sched_getaffinity(0))
    if n_generator <= 0 or len(cores) < 2 * n_generator + 2:
        return None, None
    return cores[:-n_generator], cores[-n_generator:]


def build_server(config: dict, seed: int, devices):
    """The server as `pio-tpu deploy` builds it, over the seeded tenants."""
    from predictionio_tpu.models.recommendation import recommendation_engine
    from predictionio_tpu.obs.registry import MetricRegistry
    from predictionio_tpu.parallel.mesh import ComputeContext
    from predictionio_tpu.serving.engine_server import EngineServer

    storage, tenants = modelstore.publish_tenants(
        ENGINE_ID, seed, config["tenants"],
        config["n_users"], config["n_items"], config["rank"],
    )
    registry = MetricRegistry()
    server = EngineServer(
        recommendation_engine(),
        modelstore.engine_params(config["rank"]),
        engine_id=ENGINE_ID,
        storage=storage,
        ctx=ComputeContext.create(batch="chipbench", devices=devices),
        tenants=tenants,
        quantize=config["quantize"] or "",
        registry=registry,
        **config["server"],
    )
    http = server.serve(host="127.0.0.1", port=0)
    http.start()
    return server, http, registry


def start_generators(traffic: dict):
    """The generator processes, importing while the server builds; each
    waits on its standard input for the plan."""
    return [
        subprocess.Popen(
            [sys.executable, _LOADGEN],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        )
        for _ in range(traffic["procs"])
    ]


def generator_parameters(traffic: dict) -> dict:
    """The generator's optional parameters that the traffic file names
    (`loadgen.OPTIONAL_PARAMETERS`); one the loop cannot follow ends the
    run with a message."""
    if traffic["loop"] == "closed" and "burst" in traffic:
        raise SystemExit(
            "traffic names 'burst' on a closed loop: a client sends when its "
            "reply has come, so there is no schedule to burst; open loop only"
        )
    return {k: traffic[k] for k in loadgen.OPTIONAL_PARAMETERS if k in traffic}


def send_plans(procs, traffic, config, port, seed, seconds, cores) -> dict:
    t_start = time.monotonic() + 0.3
    times = {
        "t_start": t_start,
        "t_window": t_start + traffic["settle_s"],
        "t_end": t_start + traffic["settle_s"] + seconds,
    }
    for i, proc in enumerate(procs):
        plan = {
            **times, "host": "127.0.0.1", "port": port,
            "loop": traffic["loop"], "seed": seed,
            "proc": i, "procs": len(procs),
            "clients": traffic["clients"] // len(procs),
            "tenants": [
                modelstore.tenant_name(t) for t in range(config["tenants"])
            ],
            "zipf_exponent": config["zipf_exponent"],
            "n_users": config["n_users"], "num": config["num"],
            "batch": traffic.get("batch", 1),
            "rate": traffic.get("rate", 0.0),
            "keep": traffic["keep"], "cores": cores,
            **generator_parameters(traffic),
        }
        proc.stdin.write(json.dumps(plan) + "\n")
        proc.stdin.close()
        proc.stdin = None  # so that communicate() leaves it alone
    return times


def collect(procs) -> list[dict]:
    out = []
    for proc in procs:
        try:
            text, _ = proc.communicate(timeout=_COLLECT_TIMEOUT_S)
            out.append(json.loads(text))
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
    return out


def _sleep_until(t: float) -> None:
    wait = t - time.monotonic()
    if wait > 0:
        time.sleep(wait)


def watch_window(times, registry, traffic, trace_dir: str | None) -> dict:
    """What the server's process does during the window: nothing, or in a
    traced run the registry at both ends and the profiler over a slice of
    steady traffic in the middle, written under ``trace_dir``. The trace is
    read once the window's requests are all in (`run`): reading it here
    held the interpreter lock for 0.4 s of the window it measures."""
    _sleep_until(times["t_window"])
    if not trace_dir:
        _sleep_until(times["t_end"])
        return {}
    import jax

    seen = {"before": registry.to_dict()}
    trace_s = min(traffic["trace_s"], (times["t_end"] - times["t_window"]) / 2)
    _sleep_until((times["t_window"] + times["t_end"] - trace_s) / 2)
    # Python's own tracer multiplies the host's work; the device lines and
    # the window's span need none of it
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    q0 = registry.to_dict()
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_EVENT):
        time.sleep(trace_s)
    q1 = registry.to_dict()
    jax.profiler.stop_trace()
    seen["traced_queries"] = layer_metrics.delta(
        {"before": q0, "after": q1}, "pio_batch_occupancy", {}, "sum"
    )
    _sleep_until(times["t_end"])
    seen["after"] = registry.to_dict()
    return seen


def window_numbers(traffic, times, results, seconds) -> dict:
    """The generator's numbers over the window, all requests of it."""
    lo, hi = times["t_window"], times["t_end"]
    if traffic["loop"] == "closed":
        posts = [p for r in results for p in r["posts"] if lo <= p[0] <= hi]
        ok = sum(p[1] for p in posts)
        bad = sum(p[2] for p in posts)
        return {
            "attempted": ok + bad, "failed": bad,
            "unanswered": sum(1 for p in posts if not p[3]),
            "queries_per_s": ok / seconds,
        }
    rows = [q for r in results for q in r["requests"] if q and lo <= q[0] < hi]
    due = np.array([q[0] for q in rows])
    done = np.array([
        q[2] if q[3] else q[0] + loadgen.REQUEST_TIMEOUT_S for q in rows
    ])
    lat = loadgen.due_latencies_ms(due, done)
    late = loadgen.due_latencies_ms(due, [q[1] for q in rows])
    return {
        "attempted": len(rows),
        "failed": sum(1 for q in rows if not q[3]),
        "unanswered": sum(1 for q in rows if not q[4]),
        "query_p50_ms": loadgen.percentile(lat, 50),
        "query_p90_ms": loadgen.percentile(lat, 90),
        "query_p95_ms": loadgen.percentile(lat, 95),
        "query_p99_ms": loadgen.percentile(lat, 99),
        "generator_late_p99_ms": loadgen.percentile(late, 99),
    }


def sample_answers(results, traffic, config, seed) -> dict:
    """``{tenant: [(user index, answer or None)]}``: a sample, drawn from
    the seed, of the answers the window itself produced, over at most
    ``check_tenants`` tenants and ``check_queries`` queries."""
    kept = sorted(
        (k for r in results for k in r["kept"]), key=lambda k: (k[0], k[1])
    )
    rng = np.random.default_rng([seed, 0xC4EC])
    sample: dict[int, list] = {}
    n = 0
    for i in rng.permutation(len(kept)):
        tenant, users, text = kept[i]
        if tenant not in sample and len(sample) >= traffic["check_tenants"]:
            continue
        try:
            body = json.loads(text)
        except ValueError:
            body = None
        if traffic["loop"] == "closed":
            slots = body if isinstance(body, list) else []
            slots = slots + [None] * (len(users) - len(slots))
            answers = [
                reference.parse_answer((s or {}).get("prediction"), config["num"])
                for s in slots[:len(users)]
            ]
        else:
            answers = [reference.parse_answer(body, config["num"])]
        sample.setdefault(tenant, []).extend(zip(users, answers))
        n += len(users)
        if n >= traffic["check_queries"]:
            break
    return sample


def compare(sample, config, seed, control=None) -> dict[str, float]:
    """The reference over the sample, tenant by tenant and 64 queries at a
    time; with ``control`` (a precision of `reference.quantize_rows`) the
    control's answers stand in for the served ones."""
    comparison = reference.Comparison(config["num"])
    for tenant, pairs in sorted(sample.items()):
        users, items = modelstore.host_factors(
            seed, tenant, config["n_users"], config["n_items"], config["rank"]
        )
        for at in range(0, len(pairs), 64):
            idx = [p[0] for p in pairs[at:at + 64]]
            answers = [p[1] for p in pairs[at:at + 64]]
            if control:
                answers = reference.control_answers(
                    users, items, idx, config["num"], control
                )
            comparison.add(users, items, idx, answers)
    return comparison.numbers()


def evictions(registry) -> float:
    found = layer_metrics.samples(registry.to_dict(), "pio_pool_evictions_total", {})
    return sum(float(s.get("value") or 0.0) for s in found)


def cell_metrics(bench, kind: str, cell_name: str) -> list[dict]:
    """The metrics of ``kind`` that this cell reports."""
    return [
        m for m in bench[kind] if cell_name in m.get("workloads", [cell_name])
    ]


def run(cell, bench, config, traffic, args, t_process_start, device) -> dict:
    """One run of one cell; returns the result line as a dictionary."""
    import jax

    from predictionio_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    generator_parameters(traffic)  # a mix the generator cannot follow ends here
    server_cores, generator_cores = split_cores(traffic["generator_cores"])
    if server_cores:
        os.sched_setaffinity(0, server_cores)
    procs = start_generators(traffic)
    server = http = None
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if args.trace else None
    phases = {"start_s": time.monotonic() - t_process_start}
    try:
        server, http, registry = build_server(
            config, args.seed, jax.devices()[:cell["chips"]]
        )
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
        evicted = evictions(registry)
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        if http is not None:
            http.shutdown()
        if server is not None:
            server.close()
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    # the program's state goes before the reference runs
    del server, http
    gc.collect()

    load = window_numbers(traffic, times, results, args.seconds)
    load["setup_s"] = times["t_window"] - t_process_start
    sample = sample_answers(results, traffic, config, args.seed)
    t_reference = time.monotonic()
    numbers = compare(sample, config, args.seed)
    reference_s = time.monotonic() - t_reference
    numbers["unanswered"] = float(load["unanswered"])
    numbers["evictions"] = evicted
    correct, compared = reference.judge(numbers, config["limits"])
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
            "config": config, "traffic": traffic,
            "memory_peak_bytes": memory_peak,
            "peak": roofline.peaks(device["kind"]) if trace else None,
        }
        wanted = cell_metrics(bench, "per_layer", cell["name"])
        values = {m["name"]: layer_metrics.read(m["name"], gathered) for m in wanted}
        if trace:
            device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
            result["breakdown"] = {
                "device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"],
            }
    else:
        wanted = cell_metrics(bench, "end_to_end", cell["name"])
        values = {m["name"]: load.get(m["name"]) for m in wanted}
    result["metrics"] = {
        m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
        for m in wanted if values[m["name"]] is not None
    }
    result["device"] = device
    result["sampled"] = {
        "tenants": len(sample), "queries": sum(len(v) for v in sample.values()),
        "reference_s": reference_s,
    }
    result["phases"] = phases
    if args.control:
        result["control"] = compare(sample, config, args.seed, args.control)
    result["compared"] = compared
    return result
