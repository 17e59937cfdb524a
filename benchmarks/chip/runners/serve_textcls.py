"""Runner of the text-classification cell: one single-tenant `EngineServer`
as `pio-tpu deploy` builds it without ``--tenant`` (one engine instance,
no pool), on `memory` storage, served over real HTTP on localhost and
driven by generator processes (`loadgen_textcls.py`) that post product
titles.

One run: publish the seeded model, let the server load, stage and warm
it, settle at the cell's own load, measure the window, read the device's
peak memory, close the server, and only then hold a sample of the
window's own answers against the plain reference (`reference_textcls`).
``--control`` reads one or more controls (comma-separated, of
`reference_textcls.CONTROLS`) on the same sample.
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
import loadgen_textcls
import modelstore_textcls
import reference_textcls
import roofline
import trace_reduce
from runners import serve_http

#: what this runner reads of a configuration's file, beside the general keys
CONFIG_KEYS = (
    "n_classes", "n_features", "num", "vocabulary", "word_zipf_exponent",
    "title_tokens", "tie_tolerance", "jit_names", "dense_control_features",
)
#: the named scope of the scoring step, as the program names it
SCORE_SCOPE = "textcls_score"
_LOADGEN = os.path.join(
    os.path.dirname(os.path.abspath(loadgen_textcls.__file__)), "loadgen_textcls.py"
)


def check_program() -> None:
    """A program without the sparse step (the template's dense form) ends
    the run before anything is built."""
    from predictionio_tpu.ops import naive_bayes

    if not hasattr(naive_bayes, "sparse_log_scores"):
        raise SystemExit(
            "the program has no sparse naive-Bayes step "
            "(ops/naive_bayes.sparse_log_scores): it cannot hold this table"
        )


def build_server(config: dict, seed: int, devices):
    """The server as single-tenant `pio-tpu deploy` builds it."""
    from predictionio_tpu.models.textclassification import textclassification_engine
    from predictionio_tpu.obs.registry import MetricRegistry
    from predictionio_tpu.parallel.mesh import ComputeContext
    from predictionio_tpu.serving.engine_server import EngineServer

    storage = modelstore_textcls.publish(seed, config["n_features"], config["n_classes"])
    registry = MetricRegistry()
    server = EngineServer(
        textclassification_engine(),
        modelstore_textcls.engine_params(config["n_features"]),
        engine_id=modelstore_textcls.ENGINE_ID,
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
            "vocabulary": config["vocabulary"],
            "word_zipf_exponent": config["word_zipf_exponent"],
            "title_tokens": config["title_tokens"],
            "keep": traffic["keep"], "cores": cores,
        }
        proc.stdin.write(json.dumps(plan) + "\n")
        proc.stdin.close()
        proc.stdin = None  # so that communicate() leaves it alone
    return times


def sample_answers(results, traffic, config, seed) -> list:
    """``[(title, served answer or None)]``: a sample, drawn from the seed,
    of the answers the window itself produced, ``check_queries`` of them
    in whole posts."""
    kept = sorted(
        (k for r in results for k in r["kept"]), key=lambda k: (k[0], k[1])
    )
    rng = np.random.default_rng([seed, 0x7E47C])
    sample: list = []
    for i in rng.permutation(len(kept)):
        titles, text = kept[i]
        try:
            slots = json.loads(text)
        except ValueError:
            slots = []
        slots = (slots if isinstance(slots, list) else []) + [None] * len(titles)
        for title, slot in zip(titles, slots):
            answer = reference_textcls.parse_answer(
                (slot or {}).get("prediction"), config["n_classes"]
            )
            sample.append((title, answer))
        if len(sample) >= traffic["check_queries"]:
            break
    return sample[: traffic["check_queries"]]


def compare(sample, config, seed, answers=None, n_features=None) -> dict:
    """The reference over the sample, 64 titles at a time; ``answers``
    (one per sampled title) stand in for the served ones, and
    ``n_features`` for the configuration's width, for a control."""
    comparison = reference_textcls.Comparison(config["num"], config["tie_tolerance"])
    width = n_features or config["n_features"]
    for at in range(0, len(sample), 64):
        texts = [t for t, _ in sample[at:at + 64]]
        got = [a for _, a in sample[at:at + 64]] if answers is None else answers[at:at + 64]
        scores = reference_textcls.reference_scores(seed, texts, width, config["n_classes"])
        comparison.add(scores, got)
    return comparison.numbers()


def dense_default_answers(sample, config, seed):
    """The template's earlier scoring, ``x @ theta.T`` over dense hashed
    rows at XLA's default precision, on the device, at the reduced width
    ``dense_control_features`` so that [64, d] rows and a [C, d] table
    fit: ``(answers, width)``."""
    import jax

    from predictionio_tpu.models.textclassification import hash_counts, tokenize
    from predictionio_tpu.ops import naive_bayes as nb

    width, n_classes = config["dense_control_features"], config["n_classes"]
    pi, theta_t = modelstore_textcls.device_model(seed, width, n_classes)
    model = nb.MultinomialNBModel(pi=pi, theta=theta_t.T)
    answers = []
    for at in range(0, len(sample), 64):
        x = np.stack([hash_counts(tokenize(t), width) for t, _ in sample[at:at + 64]])
        scores = np.asarray(jax.device_get(nb.log_scores(model, x)), np.float64)
        answers.extend(reference_textcls.answers_of(scores, config["num"]))
    del model, pi, theta_t
    return answers, width


def read_controls(names, sample, config, seed) -> dict:
    out = {}
    for name in names:
        if name == "dense_default":
            answers, width = dense_default_answers(sample, config, seed)
        else:
            width = config["n_features"]
            answers = reference_textcls.control_answers(
                seed, [t for t, _ in sample], width, config["n_classes"],
                config["num"], name,
            )
        out[name] = compare(sample, config, seed, answers, width)
    return out


def scope_seconds(events, scope: str) -> float:
    """Device time of the operations under ``scope`` inside the traced
    window, averaged over the device planes."""
    window = [e for e in events if e[2] == trace_reduce.WINDOW_EVENT]
    ops = [
        e for e in events
        if e[0].startswith("/device:") and e[1] == trace_reduce.OPS_LINE
        and (e[5] == scope or e[5].startswith(scope + "/"))
    ]
    if not window or not ops:
        return 0.0
    lo, hi = window[0][3], window[0][3] + window[0][4]
    planes = {e[0] for e in events if e[0].startswith("/device:")}
    total = sum(max(0, min(e[3] + e[4], hi) - max(e[3], lo)) for e in ops)
    return total / 1e9 / len(planes)


def run(cell, bench, config, traffic, args, t_process_start, device) -> dict:
    """One run of the cell; returns the result line as a dictionary."""
    check_program()
    controls = [c for c in args.control.split(",") if c]
    for name in controls:
        if name not in reference_textcls.CONTROLS:
            raise SystemExit(f"no control {name!r}: {reference_textcls.CONTROLS}")
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
    correct, compared = reference_textcls.judge(numbers, config["limits"])
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
            "scope_s": scope_seconds(events, SCORE_SCOPE),
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
    result["sampled"] = {"queries": len(sample), "reference_s": reference_s}
    result["phases"] = phases
    if controls:
        result["control"] = read_controls(controls, sample, config, args.seed)
    result["compared"] = compared
    return result
