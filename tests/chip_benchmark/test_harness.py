"""The chip benchmark's harness, on the CPU at tiny size: the manifest and
its data files (the rules are `manifest_rules`, functions of the manifest
and its checkout, shown on a made-up addition too), the arithmetic of the
yardstick, the trace reduction on small recorded traces, the command's
contract, the control, and a run with the timed path broken underneath."""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHIP = os.path.join(ROOT, "benchmarks", "chip")
sys.path[:0] = [CHIP, ROOT, os.path.dirname(os.path.abspath(__file__))]

import layer_metrics  # noqa: E402
import loadgen  # noqa: E402
import manifest_rules as rules  # noqa: E402
import reference  # noqa: E402
import roofline  # noqa: E402
import run as chip_run  # noqa: E402
import trace_reduce  # noqa: E402

NAME, UNIT = rules.NAME, rules.UNIT
BENCH = rules.load_bench(ROOT)
CONFIGS = [c["name"] for c in BENCH["configs"]]
with open(os.path.join(os.path.dirname(__file__), "data", "accepted_per_layer.json")) as _f:
    ACCEPTED_PER_LAYER = json.load(_f)["names"]


def _config(name):
    return rules.config_body(BENCH, ROOT, name)


# -- the manifest and its data files ----------------------------------------


def test_manifest_keys_and_limits():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert 1 <= BENCH["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    assert len(json.dumps(BENCH)) <= 64 * 1024
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


@pytest.mark.parametrize(
    "entry",
    BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"] + BENCH["per_layer"],
    ids=lambda e: e["name"],
)
def test_names_units_and_lines(entry):
    assert NAME.match(entry["name"])
    for key in ("config", "traffic", "moves"):
        if key in entry:
            assert NAME.match(entry[key])
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in (
            "device_trace", "program_span", "program_counter", "host_clock"
        )
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200
            assert "\n" not in entry[key] and "\t" not in entry[key]
    if "bound" in entry:
        assert 0.01 <= entry["bound"] <= 0.1


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_finds_its_files_and_metrics(cell):
    bench, found, config, traffic = chip_run.load_cell(cell["name"], False)
    assert (bench, found) == (BENCH, cell)
    assert config == _config(cell["config"])
    assert traffic == rules.traffic_body(BENCH, ROOT, cell["traffic"])
    rules.check_cell(BENCH, ROOT, cell)


def test_runner_declares_what_it_reads_of_a_configuration():
    from runners import serve_http

    assert rules.runner_config_keys(BENCH, ROOT, "serve_http") == serve_http.CONFIG_KEYS
    # what the runner and the readers it feeds take from the configuration
    read = set()
    for path in ("runners/serve_http.py", "layer_metrics.py"):
        with open(os.path.join(CHIP, path)) as f:
            read |= set(re.findall(r'(?:config|cfg|"config"\])\["(\w+)"\]', f.read()))
    assert read - set(rules.CONFIG_KEYS) == set(serve_http.CONFIG_KEYS)


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_reader_and_target(metric):
    rules.check_per_layer_metric(BENCH, ROOT, metric)


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry(config):
    rules.check_config_entry(BENCH, ROOT, config)
    if config["name"] in rules.ACCEPTED_CONFIGS:
        rules.check_accepted_config(BENCH, ROOT, config)


def test_accepted_configurations_and_cells_are_all_there():
    assert CONFIGS[:2] == list(rules.ACCEPTED_CONFIGS)
    assert [w["name"] for w in BENCH["workloads"]][:3] == list(rules.ACCEPTED_CELLS)


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_every_control_is_held_to_the_limits_by_some_test(config):
    rules.check_control_is_tested(BENCH, ROOT, config)


def test_files_under_paths_have_plain_names():
    for path in BENCH["paths"]:
        for folder, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", name), name
    for word in BENCH["command"]:
        assert not word.startswith("/") and ".." not in word


# -- what a later PR may add as files, shown on a made-up addition ------------


def _all_rules(bench, root, accepted):
    """Every rule of the manifest over every entry of it."""
    for entry in bench["configs"]:
        rules.check_config_entry(bench, root, entry)
        rules.check_control_is_tested(bench, root, entry)
        if entry["name"] in rules.ACCEPTED_CONFIGS:
            rules.check_accepted_config(bench, root, entry)
    for cell in bench["workloads"]:
        rules.check_cell(bench, root, cell)
    for metric in bench["per_layer"]:
        rules.check_per_layer_metric(bench, root, metric)
    rules.check_per_layer_order(bench, root, accepted)


def _copy_of_the_benchmark(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for path in BENCH["paths"]:
        shutil.copytree(
            os.path.join(ROOT, path), os.path.join(root, path),
            ignore=shutil.ignore_patterns("__pycache__"),
        )
    return rules.load_bench(root), root


def _write(root, path, body):
    with open(os.path.join(root, path), "w") as f:
        json.dump(body, f)


ML20M = "benchmarks/chip/configs/rec-pool-ml20m.json"


def _made_up_addition(tmp_path):
    """A copy of the benchmark with what a `model_config` PR would bring
    as files and entries: a configuration of another source's widths on
    another server setting, one cell, two per-layer metrics with their
    readers. Nothing of it is measured; the names are made up."""
    bench, root = _copy_of_the_benchmark(tmp_path)
    tenants, n_users, n_items, rank = 400, 138493, 26744, 10
    body = {
        **_config("rec-pool-kddcup11"),
        "source": "MovieLens 20M: 138,493 users x 26,744 movies (Harper and Konstan, ACM TiiS 5(4), 2015)",
        "deployment": "a pool of small tenants on one chip, posts of 256",
        "published": {"n_users": n_users, "n_items": n_items, "rank": rank},
        "n_users": n_users, "n_items": n_items, "rank": rank, "tenants": tenants,
        "server": {**rules.DEPLOY_DEFAULTS, "max_batch": 256},
        "resident_table_bytes": tenants * (n_users + n_items) * rank * 4,
        "floor_note": "400 tenants x 6.61 MB are 2.64 GB, 15.4% of 16 GiB: over the "
        "12.5% floor, which holds where the device is busy 75% of the traced window",
        "assumed": {"tenants": tenants, "server.max_batch": 256, "zipf_exponent": 1.0, "num": 10},
    }
    _write(root, ML20M, body)
    bench["configs"].append({
        "name": "rec-pool-ml20m", "source": body["source"], "file": ML20M,
        "reduced": [], "why": "hundreds of small tenants",
    })
    cell = "serve-pool-ml20m-batch256"
    bench["workloads"].append({
        "name": cell, "config": "rec-pool-ml20m", "traffic": "batch_closed_loop",
        "chips": 1, "why": "posts of 256 over 400 small tenants",
    })
    next(m for m in bench["end_to_end"] if m["name"] == "queries_per_s")["workloads"].append(cell)
    for name, spec in (
        ("ml20m.evictions_per_s", {"reader": "counter_share", "families": ["pio_pool_evictions_total"], "over": "pio_process_clock_seconds_total"}),
        ("ml20m.restage_ms", {"reader": "histogram_mean", "families": ["pio_pool_stage_seconds"], "scale": 1000.0}),
    ):
        bench["per_layer"].append({
            "name": name, "unit": "1", "better": "lower", "source": "program_counter",
            "layer": "tenant pool", "moves": "queries_per_s", "workloads": [cell],
        })
        _write(root, f"benchmarks/chip/metrics/{name.split('.')[1]}.json", spec)
    return bench, root


def _cut_a_width(bench, root):
    body = rules.config_body(bench, root, "rec-pool-ml20m")
    _write(root, ML20M, {**body, "n_items": 8192})


def _set_the_server(bench, root):
    body = rules.config_body(bench, root, "rec-pool-ml20m")
    _write(root, ML20M, {**body, "server": {**body["server"], "pipeline_depth": 4}})


def _turn_the_cache_on(bench, root):
    body = rules.config_body(bench, root, "rec-pool-ml20m")
    _write(root, ML20M, {**body, "server": {**body["server"], "cache": True}})


def _shrink_the_pool(bench, root):
    body = rules.config_body(bench, root, "rec-pool-ml20m")
    _write(root, ML20M, {**body, "tenants": 300, "resident_table_bytes": 300 * 6609480})


def _insert_before_the_accepted(bench, root):
    bench["per_layer"].insert(3, bench["per_layer"].pop())


def _name_a_reference_that_is_not_there(bench, root):
    body = rules.config_body(bench, root, "rec-pool-ml20m")
    _write(root, ML20M, {**body, "reference": "reference_sequences"})


def _bring_a_reference_with_no_test(bench, root):
    _name_a_reference_that_is_not_there(bench, root)
    shutil.copy(
        os.path.join(CHIP, "reference.py"),
        os.path.join(root, "benchmarks/chip/reference_sequences.py"),
    )


def _leave_out_what_the_runner_reads(bench, root):
    body = rules.config_body(bench, root, "rec-pool-ml20m")
    del body["table_format"]
    _write(root, ML20M, body)


@pytest.mark.parametrize("fault,message", [
    (None, None),
    (_cut_a_width, "width 'n_items' differs from the published one and is not under reduced"),
    (_set_the_server, "server setting 'pipeline_depth' differs from deploy's default and is not under assumed"),
    (_turn_the_cache_on, "server setting 'cache' differs from deploy's default and is not under assumed"),
    (_shrink_the_pool, "resident_table_bytes is under 12.5% of the memory"),
    (_insert_before_the_accepted, "the accepted per-layer entries come first"),
    (_name_a_reference_that_is_not_there, "names a reference 'reference_sequences' that is not there"),
    (_bring_a_reference_with_no_test, "no test_control_reference_sequences.py holds"),
    (_leave_out_what_the_runner_reads, "runner 'serve_http' reads 'table_format'"),
], ids=lambda v: getattr(v, "__name__", None) or ("sound" if v is None else "message"))
def test_made_up_addition_meets_the_rules_and_each_fault_its_own(tmp_path, fault, message):
    """A configuration of other widths, another server setting, a cell and
    two per-layer entries land as files and entries with no test edited;
    each planted fault fails on the assertion meant for it."""
    bench, root = _made_up_addition(tmp_path)
    if fault is None:
        _all_rules(bench, root, ACCEPTED_PER_LAYER)
        _write(root, "BENCHMARK.json", bench)
        # and the harness itself finds the cell's files there
        _b, cell, config, traffic = chip_run.load_cell("serve-pool-ml20m-batch256", True, root)
        assert (config["rank"], config["server"]["max_batch"]) == (10, 256)
        assert config["n_users"] == 3000 and traffic["runner"] == "serve_http"
        return
    fault(bench, root)
    with pytest.raises(AssertionError, match=re.escape(message)):
        _all_rules(bench, root, ACCEPTED_PER_LAYER)


def _re_source_the_widths(body):
    return {**body, "n_users": 480189, "published": {**body["published"], "n_users": 480189}}


def _halve_the_pool(body):
    return {**body, "resident_table_bytes": int(0.2 * 2**34)}


def _tune_the_server(body):
    return {
        **body, "server": {**body["server"], "max_batch": 256},
        "assumed": {**body["assumed"], "server.max_batch": 256},
    }


@pytest.mark.parametrize("config", rules.ACCEPTED_CONFIGS)
@pytest.mark.parametrize("alter,message", [
    (_re_source_the_widths, "the widths of KDD Cup 2011 Track 1, uncut"),
    (_halve_the_pool, "a quarter of 16 GiB resident"),
    (_tune_the_server, "pio-tpu deploy's defaults"),
], ids=lambda v: getattr(v, "__name__", "message"))
def test_accepted_configuration_stays_pinned(tmp_path, config, alter, message):
    """An altered copy of an accepted configuration that meets the general
    rule (it says what it changed) still fails the pin of its name."""
    bench, root = _copy_of_the_benchmark(tmp_path)
    entry = next(c for c in bench["configs"] if c["name"] == config)
    _write(root, entry["file"], alter(rules.config_body(bench, root, config)))
    rules.check_config_entry(bench, root, entry)
    with pytest.raises(AssertionError, match=re.escape(message)):
        rules.check_accepted_config(bench, root, entry)


# -- the yardstick's arithmetic ---------------------------------------------


def test_percentile_and_due_time_latency():
    assert loadgen.percentile([1, 2, 3, 4, 5], 50) == 3.0
    assert loadgen.percentile(list(range(1, 101)), 95) == pytest.approx(95.05)
    # a request due at 1.0 s, sent late at 1.2 s and done at 1.5 s took
    # 500 ms of its user's time, not 300
    lat = loadgen.due_latencies_ms([1.0, 2.0], [1.5, 2.004])
    assert lat.tolist() == pytest.approx([500.0, 4.0])


def test_schedule_gives_every_seed_the_same_work_in_another_order():
    a, ta = loadgen.arrival_schedule(2**31 + 7, 350.0, 10.0, 24, 1.0)
    b, tb = loadgen.arrival_schedule(2**31 + 7, 350.0, 10.0, 24, 1.0)
    c, tc = loadgen.arrival_schedule(2**31 + 8, 350.0, 10.0, 24, 1.0)
    assert np.array_equal(a, b) and np.array_equal(ta, tb)
    assert not np.array_equal(a[:50], c[:50])
    assert len(a) == len(c) == 3500 and a.max() < 10.0 and np.all(np.diff(a) > 0)
    # each second: the same gaps and the same tenants, shuffled
    assert np.all((a[1050:1400] >= 3) & (a[1050:1400] < 4))
    gaps_a = np.sort(np.diff(a[1049:1400]))  # the 350 gaps of second 3
    gaps_c = np.sort(np.diff(c[2099:2450]))  # and of another seed's second 6
    assert np.allclose(gaps_a, gaps_c)
    assert np.array_equal(np.bincount(ta[:350], minlength=24), np.bincount(tc[700:1050], minlength=24))
    assert np.bincount(ta[:350])[0] == 93  # 350 / H(24), Zipf 1.0
    # exponential gaps: mean 1/rate, coefficient of variation near 1
    gaps = np.diff(a)
    assert gaps.mean() == pytest.approx(1 / 350.0, rel=0.01)
    assert 0.9 < gaps.std() / gaps.mean() < 1.05
    w = loadgen.zipf_weights(24, 1.0)
    assert w.sum() == pytest.approx(1.0) and w[0] / w[1] == pytest.approx(2.0)


def test_window_numbers_cover_all_requests_of_the_window():
    from runners import serve_http

    times = {"t_window": 10.0, "t_end": 20.0}
    rows = [[9.5, 9.5, 9.6, True, True]]  # due before the window: left out
    rows += [[10.0 + i, 10.0 + i, 10.0 + i + 0.001 * (i + 1), True, True] for i in range(9)]
    rows += [[19.5, 19.5, 19.6, False, True]]  # shed: missing, not fast
    got = serve_http.window_numbers({"loop": "open"}, times, [{"requests": rows}], 10.0)
    assert (got["attempted"], got["failed"], got["unanswered"]) == (10, 1, 0)
    assert got["query_p50_ms"] == pytest.approx(5.5)
    assert got["query_p99_ms"] > 1000.0
    posts = [[9.0, 64, 0, True], [12.0, 64, 0, True], [15.0, 60, 4, True], [21.0, 64, 0, True]]
    got = serve_http.window_numbers({"loop": "closed"}, times, [{"posts": posts}], 10.0)
    assert got == {"attempted": 128, "failed": 4, "unanswered": 0, "queries_per_s": 12.4}


def test_roofline_arithmetic():
    peak = roofline.peaks("TPU v5 lite")
    assert (peak["bf16_flops"], peak["hbm_bytes_per_s"]) == (197e12, 819e9)
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9")
    with pytest.raises(KeyError):
        roofline.peaks("source")
    assert roofline.topk_ops(64, 624961, 32) == 2 * 64 * 624961 * 32
    f32 = roofline.table_row_bytes(32, "f32")
    int8 = roofline.table_row_bytes(32, "int8")
    assert (f32, int8) == (128.0, 36.0)
    nbytes = roofline.topk_bytes(64, 624961, 32, 10, f32, f32)
    assert nbytes == 624961 * 128 + 64 * 128 + 64 * 10 * 8
    # bound by the table's bytes, not by the product
    least = roofline.roofline_seconds(roofline.topk_ops(64, 624961, 32), nbytes, peak)
    assert least == pytest.approx(nbytes / 819e9) and least > 2.56e9 / 197e12
    assert roofline.share_percent(1.0, 4.0) == 25.0
    assert roofline.share_percent(1.0, 0.0) is None


def _registry(count, total, family="pio_batch_occupancy", labels=None):
    return {family: {"samples": [
        {"labels": labels or {"batcher": "a"}, "count": count, "sum": total},
    ]}}


def test_layer_metric_readers_on_hand_made_runs():
    cfg = _config("rec-pool-kddcup11")
    run = {
        "before": _registry(10, 100.0), "after": _registry(110, 6500.0),
        "config": cfg, "traffic": {"route": "/queries.json"},
        "peak": roofline.peaks("TPU v5 lite"), "load": {"query_p99_ms": 12.5},
        "memory_peak_bytes": 5 * 2**30, "traced_queries": 40000.0,
        "trace": {
            "window_s": 4.0, "busy_s": 0.5, "module_s": 0.4,
            "module_runs": {"jit__gather_top_k_dot_xla": 200},
        },
    }
    assert layer_metrics.read("batch.batch_occupancy", run) == pytest.approx(64.0)
    assert layer_metrics.read("single.query_p99_ms", run) == 12.5
    assert layer_metrics.read("batch.hbm_peak_gib", run) == 5.0
    assert layer_metrics.read("batch.device_idle_share", run) == pytest.approx(87.5)
    assert layer_metrics.read("batch.topk_device_ms", run) == pytest.approx(2.0)
    least = (624961 * 128 + 64 * 128 + 64 * 80) / 819e9
    assert layer_metrics.read("batch.topk_roofline", run) == pytest.approx(
        100 * least / 0.002
    )
    mfu = 100 * (40000 * 2 * 32 * 624961 / 197e12) / 4.0
    assert layer_metrics.read("batch.serve_mfu", run) == pytest.approx(mfu)
    # nothing to read: nothing returned, never a 0
    run["trace"] = {}
    for name in ("batch.topk_roofline", "batch.serve_mfu", "batch.device_idle_share"):
        assert layer_metrics.read(name, run) is None
    assert layer_metrics.read("single.http_request_ms", run) is None
    with pytest.raises(FileNotFoundError):
        layer_metrics.read("batch.no_such_metric", run)


# -- the trace reduction ------------------------------------------------------


def test_trace_reduce_on_hand_made_events():
    dev, host = "/device:TPU:0", "/host:CPU"
    ops, mods = trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE
    events = [
        (host, "main", trace_reduce.WINDOW_EVENT, 1_000, 10_000),
        (dev, ops, "%fusion.1 = f32[64,16]{1,0} fusion(...)", 500, 1_000),   # half inside
        (dev, ops, "%fusion.1 = f32[64,16]{1,0} fusion(...)", 2_000, 1_000),
        (dev, ops, "%copy = f32[8]{0} copy(...)", 2_500, 1_000),            # overlaps
        (dev, ops, "%copy = f32[8]{0} copy(...)", 20_000, 1_000),           # after
        (dev, mods, "jit__gather_top_k_dot_xla(123)", 2_000, 1_500),
        (dev, mods, "jit_other(9)", 5_000, 100),
    ]
    got = trace_reduce.reduce(events, ["jit__gather_top_k_dot_xla"])
    assert got["window_s"] == pytest.approx(10_000e-9)
    assert got["busy_s"] == pytest.approx((500 + 1_500) * 1e-9)
    assert got["module_s"] == pytest.approx(1_500e-9)
    assert got["module_runs"] == {"jit__gather_top_k_dot_xla": 1}
    assert got["device_ops"][0] == ["fusion.1 f32[64,16]", pytest.approx(1_500e-9)]
    assert got["idle_gaps"][0][1] == pytest.approx(8_000e-9)
    assert trace_reduce.reduce([e for e in events if e[0] == host]) == {}
    assert trace_reduce.union_seconds([(0, 10), (5, 20), (30, 40)]) == pytest.approx(30e-9)


def test_idle_time_goes_to_the_first_open_state_inside_the_window():
    dev, host = "/device:TPU:0", "/host:CPU"
    ops = trace_reduce.OPS_LINE
    events = [
        (host, "python", trace_reduce.WINDOW_EVENT, 1_000, 10_000),  # 1,000..11,000
        (dev, ops, "%fusion.1 = f32[64,16]{1,0} fusion(...)", 2_000, 1_000, "top_k"),
        (dev, ops, "%copy = f32[8]{0} copy(...)", 6_000, 500, ""),
        # a handler from before the window to 9,000, its stages inside
        (host, "t1", "http.read", 500, 1_000),            # clipped to 1,000..1,500
        (host, "t1", "engine.await", 1_500, 6_500),       # no state of its own
        (host, "t1", "http.respond", 8_000, 1_000),
        # the batcher's threads: launch outranks the device_get it overlaps
        (host, "t2", "predict.enqueue", 1_800, 1_700),    # 1,800..3,500, busy 2,000..3,000
        (host, "t3", "predict.device_get", 3_200, 1_300),  # 3,200..4,500
        (host, "t2", "batch.window", 10_500, 5_000),      # clipped to 10,500..11,000
        (host, "t9", "not.a.stage", 1_000, 10_000),
    ]
    got = trace_reduce.reduce(events)
    gaps = dict(got["idle_gaps"])
    assert list(gaps) == sorted(gaps, key=lambda k: -gaps[k])
    assert set(gaps) == {s for s, _ in trace_reduce.IDLE_STATES} | {"no_request", "unattributed"}
    ns = {state: round(seconds * 1e9) for state, seconds in gaps.items()}
    assert ns == {
        "launch": 200 + 500,            # 1,800..2,000 and 3,000..3,500
        "device_get": 1_000,            # 3,500..4,500
        "materialize_settle": 0, "backpressure": 0,
        "batch_window": 500,
        "request_in": 500,              # 1,000..1,500
        "response_out": 1_000,
        # the handler waits, no stage of a state runs: 1,500..1,800,
        # 4,500..6,000, 6,500..8,000
        "unattributed": 300 + 1_500 + 1_500,
        "no_request": 1_500,            # 9,000..10,500
    }
    assert sum(gaps.values()) == pytest.approx(got["window_s"] - got["busy_s"], abs=1e-12)
    assert got["busy_s"] == pytest.approx(1_500e-9)
    assert got["device_ops"] == [
        ["top_k/fusion.1 f32[64,16]", pytest.approx(1_000e-9)],
        ["copy f32[8]", pytest.approx(500e-9)],
    ]
    # two device planes: the mean of their idle times, state by state
    second = [("/device:TPU:1", *e[1:]) for e in events if e[0] == dev][:1]
    both = dict(trace_reduce.reduce(events + second)["idle_gaps"])
    assert both["unattributed"] == pytest.approx((3_300 + 3_800) / 2 * 1e-9)
    assert both["launch"] == pytest.approx(700e-9)
    # no stage event: the one lump, as before the stages
    bare = trace_reduce.reduce([e for e in events if e[2] not in trace_reduce.STAGES])
    assert bare["idle_gaps"] == [[trace_reduce.LUMP, pytest.approx(8_500e-9)]]
    run = {"trace": got}
    assert layer_metrics.read("batch.launch_idle_share", run) == pytest.approx(7.0)
    assert layer_metrics.read("single.launch_idle_share", {"trace": bare}) is None
    assert layer_metrics.read("single.launch_idle_share", {"trace": {}}) is None


def test_stage_names_and_idle_states_are_the_program_s():
    from predictionio_tpu.obs import tracing
    from predictionio_tpu.utils import profiling

    assert trace_reduce.STAGES == tracing.STAGES
    assert trace_reduce.IDLE_STATES == profiling.IDLE_STATES
    assert trace_reduce.HANDLER_STAGES == profiling._HANDLER_STAGES


def test_idle_states_agree_with_the_program_s_summary_on_a_recorded_trace():
    """PR 25's recorded v5e trace of one f32 and one int8 batch: given the
    window `profiling.summarize` takes (first event's start to the last
    one's end), the harness's own reduction reads the same states, busy
    time and scopes."""
    from predictionio_tpu.utils import profiling

    trace_dir = os.path.join(ROOT, "tests", "data", "stages_trace")
    events = trace_reduce.load_events(trace_dir)
    assert {e[2] for e in events if e[0].startswith("/host:")} <= set(trace_reduce.STAGES)
    spans = [(e[3], e[3] + e[4]) for e in events]
    lo, hi = min(s for s, _e in spans), max(e for _s, e in spans)
    events.append(("/host:CPU", "python", trace_reduce.WINDOW_EVENT, lo, hi - lo, ""))
    got = trace_reduce.reduce(events, ["jit__gather_top_k_dot_xla", "jit__top_k_dot_quant_xla"])
    summary = profiling.summarize(trace_dir)
    assert got["window_s"] == pytest.approx(summary["window_s"], abs=1e-6)
    assert got["busy_s"] == pytest.approx(summary["device"]["busy_s"], abs=1e-6)
    gaps = dict(got["idle_gaps"])
    assert set(gaps) == set(summary["idle"])
    for state, seconds in summary["idle"].items():
        assert gaps[state] == pytest.approx(seconds, abs=1e-6), state
    assert gaps["launch"] > 0 and gaps["device_get"] > 0
    assert sum(gaps.values()) == pytest.approx(got["window_s"] - got["busy_s"], abs=1e-6)
    # the longest operations under the scope the summary's by_scope sums
    top_k = [seconds for name, seconds in got["device_ops"] if name.startswith("top_k/")]
    assert got["device_ops"][0][0] == "top_k/fusion.1 f32[64,16]"
    assert sum(top_k) == pytest.approx(summary["device"]["by_scope"]["top_k"], rel=0.02)
    assert sum(top_k) <= summary["device"]["by_scope"]["top_k"] + 1e-9
    assert all(len(name) <= 80 for name, _ in got["device_ops"])
    assert sum(got["module_runs"].values()) == 2


def test_trace_reduce_on_a_recorded_trace():
    """100 events of a v5e run of `serve-pool-batch` (PR 24): the first
    0.2 s of its traced window."""
    with open(os.path.join(os.path.dirname(__file__), "data", "trace_events.json")) as f:
        events = [tuple(e) for e in json.load(f)]
    got = trace_reduce.reduce(events, ["jit__gather_top_k_dot_xla"])
    assert got["window_s"] == pytest.approx(0.2)
    assert 0 < got["busy_s"] < got["window_s"]
    assert 0 < got["module_s"] <= got["busy_s"] * 1.05
    assert sum(got["module_runs"].values()) == 7
    assert set(got["module_runs"]) == {"jit__gather_top_k_dot_xla"}
    assert got["device_ops"][0][0] == "fusion.1 f32[64,16]"
    assert len(got["device_ops"]) <= 10
    assert all(len(name) <= 80 for name, _ in got["device_ops"])


# -- the reference, the control, and a broken path -----------------------------


def _tables(seed, n_users=400, n_items=3000, rank=32):
    rng = np.random.default_rng(seed)
    users = (0.25 * rng.standard_normal((n_users, rank))).astype(np.float32)
    norms = np.exp(0.5 * rng.standard_normal((n_items, 1)))
    items = (norms * rng.standard_normal((n_items, rank))).astype(np.float32)
    return users, items


def _exact_answers(users, items, idx, num=10):
    top_idx, top = reference.top_k(reference.reference_scores(users[idx], items), num)
    return [(top_idx[q], top[q]) for q in range(len(idx))]


@pytest.mark.parametrize(
    "config", [c for c in CONFIGS if rules.reference_module(BENCH, ROOT, c) == "reference"]
)
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_comes_out_not_correct(config, seed):
    """The reference one precision step down, in the program's place, fails
    the configuration's limits; the exact answers pass them. For the
    configurations `reference.py` judges: one that names another reference
    brings `test_control_<module>.py` with a test of this name."""
    cfg = _config(config)
    users, items = _tables(seed)
    idx = np.arange(128)
    exact = reference.Comparison(10)
    exact.add(users, items, idx, _exact_answers(users, items, idx))
    numbers = {**exact.numbers(), "unanswered": 0.0, "evictions": 0.0}
    assert reference.judge(numbers, cfg["limits"])[0]
    control = reference.Comparison(10)
    control.add(users, items, idx, reference.control_answers(
        users, items, idx, 10, cfg["control"]["precision"]
    ))
    numbers = {**control.numbers(), "unanswered": 0.0, "evictions": 0.0}
    correct, compared = reference.judge(numbers, cfg["limits"])
    assert not correct
    assert compared["score_rms"][0] > compared["score_rms"][1]
    assert compared["rank_gap_rms"][0] > compared["rank_gap_rms"][1]


def test_comparison_catches_wrong_items_and_malformed_answers():
    users, items = _tables(5)
    idx = np.arange(64)
    answers = _exact_answers(users, items, idx)
    wrong_user = reference.Comparison(10)
    wrong_user.add(users, items, idx[::-1], answers)  # another user's answer
    assert wrong_user.numbers()["rank_gap_rms"] > 0.3
    one = reference.Comparison(10)
    swapped = list(answers)
    swapped[7] = (np.arange(10), swapped[7][1])  # one altered answer of 64
    one.add(users, items, idx, swapped)
    assert one.numbers()["rank_gap_rms"] > 0.05
    bad = reference.Comparison(10)
    bad.add(users, items, idx[:3], [None, (answers[1][0][:5], answers[1][1][:5]), answers[2]])
    assert bad.numbers()["bad_answers"] == 2.0
    assert reference.parse_answer({"itemScores": []}, 10) is None
    good = {"itemScores": [{"item": f"i{j}", "score": 1.0 - j / 10} for j in range(10)]}
    assert reference.parse_answer(good, 10)[0].tolist() == list(range(10))
    assert reference.parse_answer({"itemScores": good["itemScores"][:9]}, 10) is None


def _rehearse(workload, seed=11, seconds=1.5, trace=0):
    bench, cell, config, traffic = chip_run.load_cell(workload, True)
    from runners import serve_http

    args = argparse.Namespace(seed=seed, seconds=seconds, trace=trace, control="")
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    return serve_http.run(cell, bench, config, traffic, args, time.monotonic(), device)


@pytest.mark.parametrize("cell,fault", [
    ("serve-pool-batch", "altered_answer"),
    ("serve-pool-batch", "half_left_out"),
    ("serve-pool-int8-batch", "altered_answer"),
    ("serve-pool-int8-batch", "half_left_out"),
    ("serve-pool-single", "altered_answer"),
])
def test_broken_timed_path_is_not_correct(monkeypatch, cell, fault):
    """The rest of a run, the look for a chip skipped, over a predict path
    that alters one answer of each device batch where it is produced, or
    leaves half of the batch out: `correct` comes out false. (A lone query
    is a batch of one, so it has no half to leave out.)"""
    from predictionio_tpu.models.recommendation import ALSAlgorithm

    collect = ALSAlgorithm.batch_predict_collect

    def broken(self, model, handle, queries):
        out = collect(self, model, handle, queries)
        if fault == "half_left_out":
            return out[: len(out) // 2] + [{"itemScores": []}] * (len(out) - len(out) // 2)
        rows = out[len(out) // 2]["itemScores"]
        out[len(out) // 2] = {"itemScores": [
            {"item": f"i{j}", "score": r["score"]} for j, r in enumerate(rows)
        ]}
        return out

    monkeypatch.setattr(ALSAlgorithm, "batch_predict_collect", broken)
    result = _rehearse(cell)
    assert result["correct"] is False
    if fault == "half_left_out":
        assert result["compared"]["bad_answers"][0] > 0
    else:
        assert result["compared"]["rank_gap_rms"][0] > result["compared"]["rank_gap_rms"][1]


def test_sound_rehearsal_is_correct_and_evicts_nothing():
    result = _rehearse("serve-pool-single", seed=2**31 + 12345, trace=1)
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 20
    assert result["compared"]["evictions"] == [0.0, 0]
    assert "single.queue_wait_ms" in result["metrics"]
    # no device plane on the CPU: a share of a peak is left out, not 0
    assert "single.serve_mfu" not in result["metrics"]
    assert list(result)[-1] == "compared"


# -- the command ----------------------------------------------------------------


def _command(*extra):
    return subprocess.run(
        [sys.executable, os.path.join(CHIP, "run.py"), "--workload",
         "serve-pool-batch", "--seed", "5", "--seconds", "1", "--trace", "0", *extra],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
    )


def test_no_chip_is_an_error_with_no_number():
    done = _command()
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "no accelerator" in done.stderr


def test_rehearsal_prints_the_contract_line_last():
    done = _command("--rehearse-cpu")
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert list(line)[-1] == "compared"
    assert line["device"]["platform"] == "cpu"
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert set(line["metrics"]) == {"setup_s", "queries_per_s"}
    for metric in line["metrics"].values():
        assert set(metric) == {"value", "unit"} and metric["value"] > 0
    for name, (value, limit) in line["compared"].items():
        assert f"compared {name} " in done.stderr
    assert done.stderr.strip().splitlines()[-1].startswith("compared ")


def test_imports_touch_no_tpu_topology():
    """Importing the harness's modules starts nothing and asks the TPU
    library nothing: no topology call, no device look-up."""
    code = (
        "import sys; sys.path[:0] = [%r, %r]\n"
        "import run, loadgen, reference, roofline, trace_reduce, layer_metrics\n"
        "assert 'jax' not in sys.modules, 'jax imported at import time'\n"
    ) % (CHIP, ROOT)
    done = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=60)
    assert done.returncode == 0, done.stderr
