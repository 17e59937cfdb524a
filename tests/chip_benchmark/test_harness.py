"""The chip benchmark's harness, on the CPU at tiny size: the manifest and
its data files, the arithmetic of the yardstick, the trace reduction on a
small recorded trace, the command's contract, the control, and a run with
the timed path broken underneath."""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHIP = os.path.join(ROOT, "benchmarks", "chip")
sys.path[:0] = [CHIP, ROOT]

import layer_metrics  # noqa: E402
import loadgen  # noqa: E402
import reference  # noqa: E402
import roofline  # noqa: E402
import run as chip_run  # noqa: E402
import trace_reduce  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
CONFIGS = [c["name"] for c in BENCH["configs"]]


def _config(name):
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)


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
    assert found == cell and cell["chips"] in (1, 4)
    assert os.path.exists(os.path.join(CHIP, "runners", traffic["runner"] + ".py"))
    for key in ("n_users", "n_items", "rank", "tenants", "limits", "control"):
        assert key in config
    reports = {
        kind: [
            m["name"] for m in bench[kind]
            if cell["name"] in m.get("workloads", [cell["name"]])
        ]
        for kind in ("end_to_end", "per_layer")
    }
    assert "setup_s" in reports["end_to_end"] and len(reports["end_to_end"]) >= 2
    assert reports["per_layer"]


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_reader_and_target(metric):
    end_to_end = {m["name"]: m for m in BENCH["end_to_end"]}
    target = end_to_end[metric["moves"]]
    assert set(metric) <= {
        "name", "unit", "better", "source", "layer", "moves", "workloads"
    }
    for cell in metric["workloads"]:
        assert cell in CELLS
        assert cell in target.get("workloads", CELLS)
    base = metric["name"].split(".", 1)[-1]
    assert any(
        os.path.exists(os.path.join(CHIP, "metrics", stem + ext))
        for stem in (metric["name"], base) for ext in (".json", ".py")
    )
    if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert any(config["file"].startswith(p + "/") for p in BENCH["paths"])
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])
    body = _config(config["name"])
    # the widths of KDD Cup 2011 Track 1, uncut
    assert (body["n_users"], body["n_items"], body["rank"]) == (1000990, 624961, 32)
    assert body["resident_table_bytes"] > 0.25 * 2**34
    assert body["server"] == {
        "max_batch": 64, "max_wait_ms": 2.0, "pipeline_depth": 2,
        "adaptive_wait": True, "admission": True, "warmup": True,
    }


def test_files_under_paths_have_plain_names():
    for path in BENCH["paths"]:
        for folder, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", name), name
    for word in BENCH["command"]:
        assert not word.startswith("/") and ".." not in word


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


@pytest.mark.parametrize("config", CONFIGS)
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_comes_out_not_correct(config, seed):
    """The reference one precision step down, in the program's place, fails
    the configuration's limits; the exact answers pass them."""
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
