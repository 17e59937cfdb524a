"""The per-layer metrics that read a stage's thread CPU beside its wall
(`pio_stage_cpu_seconds`, `pio_stage_profiled_seconds_total`, which count only
while a profiler runs): their readers on hand-made runs and on a program
without the families, their block of the manifest and its faults, and a traced
rehearsal of the open-loop cell that reports them while an untraced one does
not."""

from __future__ import annotations

import argparse
import os
import re
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHIP = os.path.join(ROOT, "benchmarks", "chip")
sys.path[:0] = [CHIP, ROOT, os.path.dirname(os.path.abspath(__file__))]

import layer_metrics  # noqa: E402
import manifest_rules as rules  # noqa: E402
import run as chip_run  # noqa: E402

SINGLE = ["serve-pool-single"]
BATCH = ["serve-pool-batch", "serve-pool-int8-batch"]
CHURN = ["serve-pool-churn-batch"]
#: every cell that reports `queries_per_s`: one launch reader serves them all
CLOSED = BATCH + ["serve-ecomm-batch", "serve-simprod-batch"] + CHURN
HTTP = "HTTP, admission, cache"
#: this PR's per-layer entries in their order: (name, workloads, layer, moves)
BLOCK = [
    ("single.launch_cpu_ms", SINGLE, "predict", "query_p50_ms"),
    ("single.launch_offcpu_ms", SINGLE, "predict", "query_p50_ms"),
    ("single.handler_offcpu_ms", SINGLE, HTTP, "query_p50_ms"),
    ("batch.launch_cpu_ms", CLOSED, "predict", "queries_per_s"),
    ("batch.launch_offcpu_ms", CLOSED, "predict", "queries_per_s"),
    ("batch.handler_offcpu_ms", BATCH, HTTP, "queries_per_s"),
    ("churn.load_cpu_ms", CHURN, "model pool", "queries_per_s"),
    ("churn.load_deserialize_cpu_ms", CHURN, "model pool", "queries_per_s"),
]
NAMES = [b[0] for b in BLOCK]


def _families(rows):
    """``{stage: (count, cpu seconds, wall seconds)}`` as the two families'
    samples in a registry's dictionary."""
    return {
        "pio_stage_cpu_seconds": {"samples": [
            {"labels": {"stage": s}, "count": n, "sum": cpu} for s, (n, cpu, _w) in rows.items()
        ]},
        "pio_stage_profiled_seconds_total": {"samples": [
            {"labels": {"stage": s}, "value": wall} for s, (_n, _c, wall) in rows.items()
        ]},
    }


BEFORE = {
    "predict.prep": (10, 0.010, 0.030), "predict.enqueue": (10, 0.020, 0.050),
    "http.read": (40, 0.004, 0.008), "http.admit": (40, 0.001, 0.001),
    "engine.decode": (40, 0.002, 0.002), "engine.submit": (40, 0.004, 0.006),
    "engine.await": (40, 0.010, 2.000), "engine.serve": (40, 0.002, 0.003),
    "http.respond": (40, 0.010, 0.020), "pool.wait": (3, 0.001, 0.300),
    "pool.load": (2, 0.100, 0.160), "pool.deserialize": (2, 0.070, 0.080),
}
#: over the window: 100 launches, 200 requests, 4 cold loads
AFTER = {
    **BEFORE,
    "predict.prep": (110, 0.110, 0.230), "predict.enqueue": (110, 0.220, 0.650),
    "http.read": (240, 0.024, 0.048), "http.admit": (240, 0.006, 0.006),
    "engine.decode": (240, 0.012, 0.012), "engine.submit": (240, 0.024, 0.056),
    "engine.await": (240, 0.060, 9.000), "engine.serve": (240, 0.012, 0.023),
    "http.respond": (240, 0.060, 0.140), "pool.wait": (9, 0.002, 0.900),
    "pool.load": (6, 0.340, 0.480), "pool.deserialize": (6, 0.210, 0.240),
}
WANT = {
    # prep 0.1 s and enqueue 0.2 s of CPU over 100 launches
    "launch_cpu_ms": 3.0,
    # their wall 0.2 + 0.6 s less that CPU, over the same 100
    "launch_offcpu_ms": 5.0,
    # read 0.02, admit 0, decode 0, submit 0.03, serve 0.01, respond 0.07 s
    # off the CPU over 200 requests; await and pool.wait are left out
    "handler_offcpu_ms": 0.65,
    "load_cpu_ms": 60.0,
    "load_deserialize_cpu_ms": 35.0,
}


@pytest.mark.parametrize("name", NAMES)
def test_each_reader_on_a_hand_made_run_and_on_an_empty_one(name):
    run = {
        "before": _families(BEFORE), "after": _families(AFTER), "trace": {},
        "load": {}, "config": {}, "traffic": {},
    }
    assert layer_metrics.read(name, run) == pytest.approx(WANT[name.split(".", 1)[1]])
    # an untraced run's families (nothing profiled), and the parent's program
    # without them: nothing, never a 0, and no reader raises
    idle = {**run, "after": run["before"]}
    assert layer_metrics.read(name, idle) is None
    assert layer_metrics.read(name, {**run, "before": {}, "after": {}}) is None


def test_launch_cpu_and_off_cpu_add_up_to_the_profiled_wall():
    run = {"before": _families(BEFORE), "after": _families(AFTER), "traffic": {}}
    both = sum(layer_metrics.read(f"batch.{base}", run) for base in ("launch_cpu_ms", "launch_offcpu_ms"))
    assert both == pytest.approx(1e3 * (0.2 + 0.6) / 100)


def _check_the_block(bench, root):
    """What this PR owns of the manifest, and nothing about what follows it:
    the eight entries stand together and in their order behind every entry
    accepted before them, each read in the cells the table gives and moving
    the end-to-end metric those cells report."""
    listed = [m["name"] for m in bench["per_layer"]]
    assert NAMES[0] in listed, f"{NAMES[0]!r} is not in per_layer"
    at = listed.index(NAMES[0])
    assert listed[at:at + len(NAMES)] == NAMES, "the clock entries stand together and in their order"
    # the names accepted before the clock entries, less those folded
    before = rules.load_accepted(root)["per_layer"][:78]
    assert set(before) <= set(listed[:at]), "the clock entries come after the ones accepted before them"
    for metric, (name, cells, layer, moves) in zip(bench["per_layer"][at:], BLOCK):
        assert metric["workloads"] == cells, f"{name!r} is read in {cells}"
        assert (metric["layer"], metric["moves"]) == (layer, moves), f"{name!r} is of {layer!r}"
        assert (metric["unit"], metric["better"], metric["source"]) == ("ms", "lower", "program_span")
        rules.check_per_layer_metric(bench, root, metric)
        for cell in cells:
            assert moves in rules.reports(bench, "end_to_end", cell)


def test_the_clock_entries_come_after_the_accepted(manifest):
    _check_the_block(*manifest)


def _an_entry_moved_away(bench):
    names = [m["name"] for m in bench["per_layer"]]
    bench["per_layer"].insert(10, bench["per_layer"].pop(names.index(NAMES[4])))


def _an_entry_read_in_another_cell_too(bench):
    rules.entry(bench, "per_layer", "churn.load_cpu_ms")["workloads"].append("serve-pool-batch")


def _an_entry_in_another_layer(bench):
    rules.entry(bench, "per_layer", "single.handler_offcpu_ms")["layer"] = "predict"


@pytest.mark.parametrize("fault,message", [
    (_an_entry_moved_away, "stand together and in their order"),
    (_an_entry_read_in_another_cell_too, "'churn.load_cpu_ms' is read in ['serve-pool-churn-batch']"),
    (_an_entry_in_another_layer, "'single.handler_offcpu_ms' is of 'HTTP, admission, cache'"),
], ids=lambda v: getattr(v, "__name__", "message"))
def test_each_fault_in_the_block_fails_on_its_own_assertion(manifest, fault, message):
    bench, root = manifest
    fault(bench)
    with pytest.raises(AssertionError, match=re.escape(message)):
        _check_the_block(bench, root)


@pytest.mark.parametrize("trace", [1, 0])
def test_the_open_loop_rehearsal_reports_them_only_when_traced(trace):
    from runners import serve_http

    bench, cell, config, traffic = chip_run.load_cell("serve-pool-single", True)
    args = argparse.Namespace(seed=2**31 + 91, seconds=1.5, trace=trace, control="")
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    result = serve_http.run(cell, bench, config, traffic, args, time.monotonic(), device)
    assert result["correct"] is True, result["compared"]
    metrics = result["metrics"]
    ours = [n for n in NAMES if n.startswith("single.")]
    if not trace:
        assert not set(ours) & set(metrics)
        return
    for name in ours:
        assert name in metrics, name
        assert metrics[name]["unit"] == "ms"
    # a launch's CPU is no more than its wall, and each is a fraction of a request
    assert 0 < metrics["single.launch_cpu_ms"]["value"]
    assert metrics["single.launch_offcpu_ms"]["value"] > -0.01
    assert metrics["single.handler_offcpu_ms"]["value"] > -0.01
