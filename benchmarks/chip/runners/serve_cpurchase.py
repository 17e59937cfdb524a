"""Runner of the complementary-purchase cell: one single-tenant
`EngineServer` as `pio-tpu deploy` builds it without ``--tenant`` (one
engine instance, no pool), on `memory` storage, served over real HTTP on
localhost and driven by generator processes (`loadgen_cpurchase.py`)
that post carts.

One run: publish the seeded model, let the server load, stage and warm
it, settle at the cell's own load, measure the window, read the device's
peak memory, close the server, and only then hold a sample of the
window's own answers against the plain reference (`reference_cpurchase`).
``--control`` reads one or more controls (comma-separated, of
`reference_cpurchase.CONTROLS`) on the same sample.
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
import loadgen_cpurchase
import modelstore_cpurchase
import reference_cpurchase
import roofline
import trace_reduce
from runners import serve_http, serve_textcls

#: what this runner reads of a configuration's file, beside the general keys
CONFIG_KEYS = (
    "n_items", "n_baskets", "n_order_rows", "item_zipf_exponent", "cart_items",
    "num", "metric", "min_support", "tie_tolerance", "jit_names",
)
#: the named scope of the scoring step, as the program names it
SCORE_SCOPE = "cpurchase_score"
_LOADGEN = os.path.join(
    os.path.dirname(os.path.abspath(loadgen_cpurchase.__file__)), "loadgen_cpurchase.py"
)


def check_program() -> None:
    """A program that does not serve carts from the pair counts on the
    device (the template's earlier per-item top-k lists) ends the run
    before anything is built."""
    try:
        from predictionio_tpu.ops import cooccurrence
    except ImportError:
        cooccurrence = None
    if not hasattr(cooccurrence, "basket_rule_scores"):
        raise SystemExit(
            "the program has no cart-scoring step over the pair counts "
            "(ops/cooccurrence.basket_rule_scores): it cannot serve this table"
        )


def build_server(config: dict, seed: int, devices):
    """The server as single-tenant `pio-tpu deploy` builds it."""
    from predictionio_tpu.models.complementarypurchase import complementarypurchase_engine
    from predictionio_tpu.obs.registry import MetricRegistry
    from predictionio_tpu.parallel.mesh import ComputeContext
    from predictionio_tpu.serving.engine_server import EngineServer

    storage = modelstore_cpurchase.publish(seed, config)
    registry = MetricRegistry()
    server = EngineServer(
        complementarypurchase_engine(),
        modelstore_cpurchase.engine_params(config["metric"], config["min_support"]),
        engine_id=modelstore_cpurchase.ENGINE_ID,
        storage=storage,
        ctx=ComputeContext.create(batch="chipbench", devices=devices),
        registry=registry,
        **config["server"],
    )
    http = server.serve(host="127.0.0.1", port=0)
    http.start()
    return server, http, registry


def start_generators(traffic: dict):
    return [
        subprocess.Popen(
            [sys.executable, _LOADGEN],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        )
        for _ in range(traffic["procs"])
    ]


def send_plans(procs, traffic, config, port, seed, seconds, cores) -> dict:
    t_start = time.monotonic() + 0.3
    times = {
        "t_start": t_start,
        "t_window": t_start + traffic["settle_s"],
        "t_end": t_start + traffic["settle_s"] + seconds,
    }
    for i, proc in enumerate(procs):
        plan = {
            **times, "host": "127.0.0.1", "port": port, "seed": seed,
            "proc": i, "procs": len(procs),
            "clients": traffic["clients"] // len(procs),
            "batch": traffic["batch"], "num": config["num"],
            "n_items": config["n_items"],
            "item_zipf_exponent": config["item_zipf_exponent"],
            "cart_items": config["cart_items"],
            "keep": traffic["keep"], "cores": cores,
        }
        proc.stdin.write(json.dumps(plan) + "\n")
        proc.stdin.close()
        proc.stdin = None  # so that communicate() leaves it alone
    return times


def sample_answers(results, traffic, config, seed) -> list:
    """``[(cart, served answer or None)]``: a sample, drawn from the seed,
    of the answers the window itself produced, ``check_queries`` of them
    in whole posts."""
    kept = sorted(
        (k for r in results for k in r["kept"]), key=lambda k: (k[0], k[1])
    )
    rng = np.random.default_rng([seed, 0xC0CA])
    sample: list = []
    for i in rng.permutation(len(kept)):
        carts, text = kept[i]
        try:
            slots = json.loads(text)
        except ValueError:
            slots = []
        slots = (slots if isinstance(slots, list) else []) + [None] * len(carts)
        for cart, slot in zip(carts, slots):
            answer = reference_cpurchase.parse_answer(
                (slot or {}).get("prediction"), config["n_items"]
            )
            sample.append((cart, answer))
        if len(sample) >= traffic["check_queries"]:
            break
    return sample[: traffic["check_queries"]]


def compare(sample, config, seed, control=None) -> dict:
    """The reference over the sample, 64 carts at a time; with
    ``control`` the control's answers stand in for the served ones."""
    table = reference_cpurchase.SeededTable(seed, config)
    comparison = reference_cpurchase.Comparison(config["num"], config["tie_tolerance"])
    for at in range(0, len(sample), 64):
        carts = [c for c, _ in sample[at:at + 64]]
        scores, answers = reference_cpurchase.reference_answers(
            table, carts, config["num"], config["metric"], config["min_support"],
            control,
        )
        got = answers if control else [a for _, a in sample[at:at + 64]]
        comparison.add(scores, carts, got)
    return comparison.numbers()


def run(cell, bench, config, traffic, args, t_process_start, device) -> dict:
    """One run of the cell; returns the result line as a dictionary."""
    check_program()
    controls = [c for c in args.control.split(",") if c]
    for name in controls:
        if name not in reference_cpurchase.CONTROLS:
            raise SystemExit(f"no control {name!r}: {reference_cpurchase.CONTROLS}")
    import jax

    from predictionio_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    server_cores, generator_cores = serve_http.split_cores(traffic["generator_cores"])
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
        seen = serve_http.watch_window(times, registry, traffic, trace_dir)
        results = serve_http.collect(procs)
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
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    # the program's state goes before the reference runs
    del server, http
    gc.collect()

    load = serve_http.window_numbers(traffic, times, results, args.seconds)
    load["setup_s"] = times["t_window"] - t_process_start
    sample = sample_answers(results, traffic, config, args.seed)
    t_reference = time.monotonic()
    numbers = compare(sample, config, args.seed)
    reference_s = time.monotonic() - t_reference
    numbers["unanswered"] = float(load["unanswered"])
    numbers["failed"] = float(load["failed"])
    correct, compared = reference_cpurchase.judge(numbers, config["limits"])
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
            "scope_s": serve_textcls.scope_seconds(events, SCORE_SCOPE),
            "peak": roofline.peaks(device["kind"]) if trace else None,
        }
        wanted = serve_http.cell_metrics(bench, "per_layer", cell["name"])
        values = {m["name"]: layer_metrics.read(m["name"], gathered) for m in wanted}
        if trace:
            device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
            result["breakdown"] = {
                "device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"],
            }
    else:
        wanted = serve_http.cell_metrics(bench, "end_to_end", cell["name"])
        values = {m["name"]: load.get(m["name"]) for m in wanted}
    result["metrics"] = {
        m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
        for m in wanted if values[m["name"]] is not None
    }
    result["device"] = device
    result["sampled"] = {
        "queries": len(sample), "reference_s": reference_s,
        "full_answers": sum(
            1 for _, a in sample if a is not None and len(a[0]) == config["num"]
        ),
    }
    result["phases"] = phases
    if controls:
        result["control"] = {
            name: compare(sample, config, args.seed, name) for name in controls
        }
    result["compared"] = compared
    return result
