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
sys.path[:0] = [CHIP, ROOT]

import layer_metrics  # noqa: E402
import modelstore  # noqa: E402
import run as chip_run  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)

STAGE_METRICS = {
    "decode_ms": "engine.decode", "respond_ms": "http.respond",
    "submit_ms": "engine.submit", "await_ms": "engine.await",
    "backpressure_ms": "batch.backpressure", "serve_ms": "engine.serve",
    "device_get_ms": "predict.device_get",
    "materialize_ms": "predict.materialize",
}
COUNTER_METRICS = ("host_cpu_share", "compiles_in_window")
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
def test_stage_metric_reads_its_stage_of_the_one_family(base, stage):
    from predictionio_tpu.obs import tracing

    with open(os.path.join(CHIP, "metrics", base + ".json")) as f:
        spec = json.load(f)
    assert stage in tracing.STAGES
    assert spec["reader"] == "histogram_mean" and spec["scale"] == 1000.0
    assert spec["families"] == ["pio_stage_seconds"]
    assert spec["labels"] == {"stage": stage}
    for prefix, moves in (("single", "query_p90_ms"), ("batch", "queries_per_s")):
        entry = next(
            m for m in BENCH["per_layer"] if m["name"] == f"{prefix}.{base}"
        )
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


def test_new_entries_only_follow_the_accepted_ones():
    names = [m["name"] for m in BENCH["per_layer"]]
    first_new = names.index("single.decode_ms")
    assert first_new == 23  # the accepted benchmark's entries come first
    assert names[first_new:] == [
        *_new_metrics("single"), *_new_metrics("batch"), "setup_compile_s",
    ]
    setup = BENCH["per_layer"][-1]
    assert setup["moves"] == "setup_s" and setup["unit"] == "s"
    assert sorted(setup["workloads"]) == sorted(
        w["name"] for w in BENCH["workloads"]
    )


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
def test_traced_rehearsal_reports_every_new_metric(cell, prefix):
    from runners import serve_http

    bench, cell_entry, config, traffic = chip_run.load_cell(cell, True)
    args = argparse.Namespace(seed=2**31 + 77, seconds=1.5, trace=1, control="")
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    result = serve_http.run(
        cell_entry, bench, config, traffic, args, time.monotonic(), device
    )
    assert result["correct"] is True, result["compared"]
    metrics = result["metrics"]
    for name in (*_new_metrics(prefix), "setup_compile_s"):
        assert name in metrics, name
        assert metrics[name]["value"] >= 0
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
