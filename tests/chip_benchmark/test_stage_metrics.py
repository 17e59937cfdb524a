"""The per-layer metrics that read the program's stages and counters: their
files and manifest entries, a post of 64 through the benchmark's own tiny
server (one observation of each handler stage, one of each batcher stage
a batch), and CPU rehearsals that report every one of them."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import urllib.request

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHIP = os.path.join(ROOT, "benchmarks", "chip")
sys.path[:0] = [CHIP, ROOT, os.path.dirname(os.path.abspath(__file__))]

import layer_metrics  # noqa: E402
import manifest_rules as rules  # noqa: E402
import modelstore  # noqa: E402
import run as chip_run  # noqa: E402

ACCEPTED_PER_LAYER = rules.load_accepted(ROOT)["per_layer"]

STAGE_METRICS = {
    "decode_ms": "engine.decode", "respond_ms": "http.respond",
    "submit_ms": "engine.submit", "await_ms": "engine.await",
    "backpressure_ms": "batch.backpressure", "serve_ms": "engine.serve",
    "device_get_ms": "predict.device_get",
    "materialize_ms": "predict.materialize",
}
COUNTER_METRICS = ("host_cpu_share", "compiles_in_window")
#: PR 27's: the window PR 26 read by hand; `launch_idle_share` needs a device
#: plane in the trace and is read on recorded traces in test_harness.py
WINDOW_METRICS = {"window_ms": ("ms", "program_span"), "window_waited_share": ("%", "program_counter")}
HANDLER_STAGES = (
    "http.read", "http.admit", "engine.decode", "engine.submit",
    "engine.await", "engine.serve", "http.respond", "http.encode",
    "http.write",
)
BATCHER_STAGES = (
    "batch.window", "batch.backpressure", "predict.prep", "predict.enqueue",
    "predict.device_get", "predict.materialize", "batch.settle",
)


def _new_metrics(prefix):
    return [f"{prefix}.{base}" for base in (*STAGE_METRICS, *COUNTER_METRICS)]


@pytest.mark.parametrize("base,stage", sorted(STAGE_METRICS.items()))
def test_stage_metric_reads_its_stage_of_the_one_family(manifest, base, stage):
    from predictionio_tpu.obs import tracing

    bench, _root = manifest

    with open(os.path.join(CHIP, "metrics", base + ".json")) as f:
        spec = json.load(f)
    assert stage in tracing.STAGES
    assert spec["reader"] == "histogram_mean" and spec["scale"] == 1000.0
    assert spec["families"] == ["pio_stage_seconds"]
    assert spec["labels"] == {"stage": stage}
    for prefix, moves in (("single", "query_p50_ms"), ("batch", "queries_per_s")):
        entry = rules.entry(bench, "per_layer", f"{prefix}.{base}")
        assert entry["moves"] == moves and entry["unit"] == "ms"
        assert entry["source"] == "program_span" and entry["better"] == "lower"


def test_counter_metrics_on_hand_made_runs_and_on_a_program_without_them():
    def snapshot(cpu, clock, compiles, seconds):
        return {
            "pio_process_cpu_seconds_total": {"samples": [{"labels": {}, "value": cpu}]},
            "pio_process_clock_seconds_total": {"samples": [{"labels": {}, "value": clock}]},
            "pio_xla_compiles_total": {"samples": [
                {"labels": {"cache": "hit"}, "value": compiles[0]},
                {"labels": {"cache": "miss"}, "value": compiles[1]},
            ]},
            "pio_xla_compile_seconds": {"samples": [
                {"labels": {"cache": "hit"}, "count": compiles[0], "sum": seconds[0]},
                {"labels": {"cache": "miss"}, "count": compiles[1], "sum": seconds[1]},
            ]},
        }

    run = {
        "before": snapshot(10.0, 100.0, (3, 40), (0.5, 1.5)),
        "after": snapshot(40.0, 140.0, (3, 41), (0.5, 1.75)),
        "traffic": {},
    }
    assert layer_metrics.read("batch.host_cpu_share", run) == pytest.approx(75.0)
    assert layer_metrics.read("single.compiles_in_window", run) == 1
    assert layer_metrics.read("setup_compile_s", run) == pytest.approx(2.25)
    # the parent commit's program has none of the families: nothing to
    # read, and no reader raises
    bare = {"before": {}, "after": {}, "traffic": {}}
    for name in ("single.host_cpu_share", "batch.compiles_in_window",
                 "setup_compile_s", "batch.decode_ms"):
        assert layer_metrics.read(name, bare) is None


def test_new_entries_only_follow_the_accepted_ones(manifest):
    """The accepted names, in their order, are a prefix of the list; what a
    later PR appends after them is free (the grown copy shows it, and
    test_harness.py an insertion failing)."""
    bench, root = manifest
    rules.check_accepted_prefix(bench, root, rules.load_accepted(root))
    names = [m["name"] for m in bench["per_layer"]]
    # PR 24's 23, PR 25's 21 with setup_compile_s their last, PR 27's six,
    # then PR 28's 17 (test_control_reference_ecomm.py) and PR 32's four
    assert ACCEPTED_PER_LAYER[23:44] == [
        *_new_metrics("single"), *_new_metrics("batch"), "setup_compile_s",
    ]
    assert ACCEPTED_PER_LAYER[44:50] == [
        f"{prefix}.{base}" for prefix in ("single", "batch")
        for base in (*WINDOW_METRICS, "launch_idle_share")
    ]
    assert ACCEPTED_PER_LAYER[67:70] == [
        f"{prefix}.launch_calls_share" for prefix in ("single", "batch", "ecomm")
    ]
    # and the tail that PR 32's check found too noisy to judge end to end
    assert ACCEPTED_PER_LAYER[70:] == ["single.query_p90_ms"]
    assert len(ACCEPTED_PER_LAYER) == 71 <= len(names)


def test_launch_calls_share_and_its_entries(manifest):
    """PR 32's three, as `PERF.md` specified them from PR 29 on: one reader
    file, the accepted `counter_share` over the batches dispatched."""
    bench, _root = manifest
    with open(os.path.join(CHIP, "metrics", "launch_calls_share.json")) as f:
        spec = json.load(f)
    assert {k: v for k, v in spec.items() if k != "what"} == {
        "reader": "counter_share", "over": "pio_batches_total",
        "families": ["pio_device_launch_calls_total"],
    }
    cells = {
        "single": ("query_p50_ms", ["serve-pool-single"]),
        "batch": ("queries_per_s", ["serve-pool-batch", "serve-pool-int8-batch"]),
        "ecomm": ("queries_per_s", ["serve-ecomm-batch"]),
    }
    for prefix, (moves, workloads) in cells.items():
        entry = rules.entry(bench, "per_layer", f"{prefix}.launch_calls_share")
        assert (entry["moves"], entry["workloads"]) == (moves, workloads)
        assert (entry["unit"], entry["better"]) == ("%", "lower")
        assert (entry["layer"], entry["source"]) == ("predict", "program_counter")

    def snapshot(batches, calls):
        return {
            "pio_batches_total": {"samples": [
                {"labels": {"batcher": "a"}, "value": batches - 10},
                {"labels": {"batcher": "b"}, "value": 10},
            ]},
            "pio_device_launch_calls_total": {"samples": [{"labels": {}, "value": calls}]},
        }

    run = {"before": snapshot(100, 300), "after": snapshot(1100, 1301), "traffic": {}}
    assert layer_metrics.read("ecomm.launch_calls_share", run) == pytest.approx(100.1)
    bare = {"before": {}, "after": {}, "traffic": {}}
    assert layer_metrics.read("single.launch_calls_share", bare) is None


def test_the_tail_is_read_per_layer_and_judged_nowhere(manifest):
    """PR 32's fourth: the driver's check read `query_p90_ms` spread by 10%
    and 25% of its median between runs of one code, so no bound the
    contract allows can judge it. It left `end_to_end`; the same number,
    all requests of the window, stands on every traced line as
    `single.query_p90_ms` beside p95 and p99, and what moved it moves the
    median."""
    bench, _root = manifest
    assert "query_p90_ms" not in [m["name"] for m in bench["end_to_end"]]
    assert all(m["moves"] != "query_p90_ms" for m in bench["per_layer"])
    assert rules.reports(bench, "end_to_end", "serve-pool-single") == ["setup_s", "query_p50_ms"]
    entry = rules.entry(bench, "per_layer", "single.query_p90_ms")
    sibling = rules.entry(bench, "per_layer", "single.query_p95_ms")
    assert {k: v for k, v in entry.items() if k != "name"} == {
        k: v for k, v in sibling.items() if k != "name"
    }
    assert (entry["moves"], entry["workloads"]) == ("query_p50_ms", ["serve-pool-single"])
    with open(os.path.join(CHIP, "metrics", "query_p90_ms.json")) as f:
        spec = json.load(f)
    assert (spec["reader"], spec["key"]) == ("load_number", "query_p90_ms")
    run = {"load": {"query_p90_ms": 8.25, "query_p50_ms": 4.5}}
    assert layer_metrics.read("single.query_p90_ms", run) == 8.25
    assert layer_metrics.read("single.query_p90_ms", {"load": {}}) is None


@pytest.mark.parametrize("base", [*WINDOW_METRICS, "launch_idle_share"])
def test_window_and_launch_metrics_and_their_entries(manifest, base):
    from predictionio_tpu.obs import tracing

    bench, _root = manifest

    with open(os.path.join(CHIP, "metrics", base + ".json")) as f:
        spec = json.load(f)
    unit, source = WINDOW_METRICS.get(base, ("%", "device_trace"))
    layer = "predict" if base == "launch_idle_share" else "micro-batcher"
    for prefix, moves in (("single", "query_p50_ms"), ("batch", "queries_per_s")):
        entry = rules.entry(bench, "per_layer", f"{prefix}.{base}")
        sibling = rules.entry(bench, "per_layer", f"{prefix}.queue_wait_ms")
        assert (entry["moves"], entry["workloads"]) == (moves, sibling["workloads"])
        assert (entry["unit"], entry["source"], entry["layer"]) == (unit, source, layer)
        assert entry["better"] == "lower"

    def snapshot(batches, waited, count, total):
        return {
            "pio_batches_total": {"samples": [
                {"labels": {"batcher": "a"}, "value": batches - 10},
                {"labels": {"batcher": "b"}, "value": 10},
            ]},
            "pio_batch_windows_waited_total": {"samples": [
                {"labels": {"batcher": "a"}, "value": waited},
            ]},
            "pio_stage_seconds": {"samples": [
                {"labels": {"stage": tracing.BATCH_WINDOW}, "count": count, "sum": total},
                {"labels": {"stage": tracing.BATCH_SETTLE}, "count": count, "sum": 9.0},
            ]},
        }

    run = {
        "before": snapshot(100, 50, 100, 0.25), "after": snapshot(1100, 55, 1100, 0.27),
        "traffic": {}, "trace": {},
    }
    want = {"window_ms": 0.02, "window_waited_share": 0.5, "launch_idle_share": None}
    assert layer_metrics.read(f"single.{base}", run) == pytest.approx(want[base])
    if base == "launch_idle_share":
        assert spec == {**spec, "reader": "idle_state_share", "state": "launch"}
        run["trace"] = {"window_s": 4.0, "idle_gaps": [["launch", 1.5], ["no_request", 0.5]]}
        assert layer_metrics.read(f"batch.{base}", run) == pytest.approx(37.5)
    # a program older than the stage and the counter: nothing, never a 0
    assert layer_metrics.read(f"batch.{base}", {"before": {}, "after": {}, "traffic": {}, "trace": {}}) is None


def _stage_counts(registry):
    samples = registry.to_dict()["pio_stage_seconds"]["samples"]
    return {s["labels"]["stage"]: s["count"] for s in samples}


def test_a_post_of_64_is_one_observation_a_handler_stage_and_one_a_batch():
    import jax

    from runners import serve_http

    _bench, _cell, config, _traffic = chip_run.load_cell("serve-pool-batch", True)
    server, http, registry = serve_http.build_server(config, 5, jax.devices()[:1])
    try:
        tenant = modelstore.tenant_name(0)
        body = json.dumps(
            [{"user": str(u), "num": config["num"]} for u in range(64)]
        ).encode()

        def post():
            request = urllib.request.Request(
                f"http://127.0.0.1:{http.port}/batch/queries.json?accessKey={tenant}",
                data=body, method="POST",
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(request, timeout=60) as response:
                return json.loads(response.read())

        post()  # the tenant is resident and warm after this
        before = _stage_counts(registry)
        batches_before = layer_metrics.delta(
            {"before": {}, "after": registry.to_dict()},
            "pio_batches_total", {}, "value",
        )
        answers = post()
        # the handler's last stage closes after the reply has left
        deadline = time.monotonic() + 5.0
        while (
            _stage_counts(registry)["http.respond"] == before["http.respond"]
            and time.monotonic() < deadline
        ):
            time.sleep(0.01)
        after = _stage_counts(registry)
        batches = layer_metrics.delta(
            {"before": {}, "after": registry.to_dict()},
            "pio_batches_total", {}, "value",
        ) - batches_before
    finally:
        http.shutdown()
        server.close()
    assert len(answers) == 64 and all(a["status"] == 200 for a in answers)
    assert {s: after[s] - before[s] for s in HANDLER_STAGES} == {
        s: 1 for s in HANDLER_STAGES
    }
    assert 1 <= batches <= 64
    assert {s: after[s] - before[s] for s in BATCHER_STAGES} == {
        s: batches for s in BATCHER_STAGES
    }


@pytest.mark.parametrize("cell,prefix", [
    ("serve-pool-single", "single"), ("serve-pool-batch", "batch"),
])
def test_traced_rehearsal_reports_every_new_metric(servers_built, cell, prefix):
    from runners import serve_http

    bench, cell_entry, config, traffic = chip_run.load_cell(cell, True)
    args = argparse.Namespace(seed=2**31 + 77, seconds=1.5, trace=1, control="")
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    result = serve_http.run(
        cell_entry, bench, config, traffic, args, time.monotonic(), device
    )
    assert result["correct"] is True, result["compared"]
    assert servers_built == [(2, True)]
    metrics = result["metrics"]
    window = [f"{prefix}.{base}" for base in WINDOW_METRICS]
    for name in (
        *_new_metrics(prefix), "setup_compile_s", *window, f"{prefix}.launch_calls_share",
    ):
        assert name in metrics, name
        assert metrics[name]["value"] >= 0
    # one call into the runtime a batch; a batch at either end of the window
    # may be counted on one side only
    assert 90 < metrics[f"{prefix}.launch_calls_share"]["value"] < 110
    assert metrics[f"{prefix}.window_waited_share"]["value"] <= 100
    # the CPU's trace has no device plane: no share of the device's idle time
    assert f"{prefix}.launch_idle_share" not in metrics
    assert metrics[f"{prefix}.compiles_in_window"]["value"] == 0
    assert 0 < metrics[f"{prefix}.host_cpu_share"]["value"]
    # the stages tile the request: what the server times from outside is
    # not smaller than the sum of what it times inside
    inside = sum(
        metrics[f"{prefix}.{base}"]["value"]
        for base in ("decode_ms", "submit_ms", "await_ms", "serve_ms")
    )
    assert inside <= metrics[f"{prefix}.http_request_ms"]["value"] * 1.02
    assert (
        metrics[f"{prefix}.device_get_ms"]["value"]
        + metrics[f"{prefix}.materialize_ms"]["value"]
        <= metrics[f"{prefix}.device_dispatch_ms"]["value"]
    )
