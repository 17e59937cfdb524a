"""The churn cell's comparison and its three planted faults, its sample, its
readers, its block of the manifest, and a rehearsal of the cell at its tiny
size on the CPU: sound, through the command from the tree and from the
grown copy, over a loader that stages the wrong generation, and on a
program whose pool has no stage."""

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
sys.path[:0] = [CHIP, ROOT, os.path.dirname(os.path.abspath(__file__))]

import layer_metrics  # noqa: E402
import manifest_rules as rules  # noqa: E402
import reference  # noqa: E402
import reference_churn  # noqa: E402
import run as chip_run  # noqa: E402

BENCH = rules.load_bench(ROOT)
CONFIG = "rec-pool-ml20m-churn"
CELL = "serve-pool-churn-batch"
POOL = (
    "miss_share", "cold_load_ms", "load_read_ms", "load_deserialize_ms",
    "load_promote_ms", "load_warmup_ms", "evict_close_ms", "cold_wait_ms",
    "loader_busy_share", "evictions_per_s", "resident_tenants", "over_ledger_gib",
    "setup_preload_s",
)
#: the per-layer entries the cell reads alone, by name and in their order:
#: the model pool's; its fourteen copies of what every cell has were folded
#: into the `batch.` entries (`rules.FOLDED`)
PER_LAYER = ["churn." + base for base in POOL]


def _tables(seed, n_users=200, n_items=2000, rank=32):
    rng = np.random.default_rng(seed)
    users = (0.25 * rng.standard_normal((n_users, rank))).astype(np.float32)
    norms = np.exp(0.5 * rng.standard_normal((n_items, 1)))
    items = (norms * rng.standard_normal((n_items, rank))).astype(np.float32)
    return users, items


def _numbers(users, items, idx, answers, **others):
    comparison = reference_churn.Comparison(10)
    comparison.add(users, items, idx, answers)
    return {
        **comparison.numbers(), "unanswered": 0.0, "failed_posts": 0.0,
        "cold_sample_short": 0.0, "missing_evictions": 0.0,
        "leaked_threads": 0.0, "over_ledger_gib": 0.0, **others,
    }


@pytest.mark.parametrize("control", reference_churn.CONTROLS)
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_comes_out_not_correct(control, seed):
    """The reference's own answers pass the configuration's limits; the
    tables one precision step down do not, and neither do another tenant's
    tables under the asked tenant's ids."""
    cfg = rules.config_body(BENCH, ROOT, CONFIG)
    assert cfg["control"]["precision"] == "fp8"
    assert cfg["control"]["second"] == "wrong_blob"
    users, items = _tables(seed)
    idx = np.arange(64)
    top_idx, top = reference.top_k(reference.reference_scores(users[idx], items), 10)
    exact = [(top_idx[q], top[q]) for q in range(64)]
    correct, compared = reference_churn.judge(
        _numbers(users, items, idx, exact), cfg["limits"]
    )
    assert correct, compared
    served = reference_churn.control_answers(
        users, items, idx, 10, control, other=_tables(seed + 100)
    )
    correct, compared = reference_churn.judge(
        _numbers(users, items, idx, served), cfg["limits"]
    )
    assert not correct
    assert compared["bad_answers"][0] == 0
    over = 1 if control == "fp8" else 100
    for name in ("score_rms", "rank_gap_rms"):
        assert compared[name][0] > over * compared[name][1], name


@pytest.mark.parametrize("name", [
    "failed_posts", "cold_sample_short", "missing_evictions", "leaked_threads",
    "over_ledger_gib", "unanswered",
])
def test_each_guarantee_alone_decides_correct(name):
    """Exact answers, and one of the deployment's own numbers over its
    limit: a post refused, a sample with too little of it from reloaded
    tenants, a pool that did not churn, a thread left behind, the chip
    holding more than the ledger says."""
    cfg = rules.config_body(BENCH, ROOT, CONFIG)
    users, items = _tables(4)
    idx = np.arange(32)
    top_idx, top = reference.top_k(reference.reference_scores(users[idx], items), 10)
    exact = [(top_idx[q], top[q]) for q in range(32)]
    assert name in cfg["limits"]
    numbers = _numbers(users, items, idx, exact, **{name: cfg["limits"][name] + 1.0})
    correct, compared = reference_churn.judge(numbers, cfg["limits"])
    assert not correct
    assert [k for k, (v, lim) in compared.items() if v > lim] == [name]


def test_an_answer_carries_its_tenant():
    good = {"itemScores": [{"item": f"t007.i{j}", "score": 1.0 - j / 10} for j in range(10)]}
    idx, scores = reference_churn.parse_answer(good, 10, "t007")
    assert idx.tolist() == list(range(10)) and scores[0] == 1.0
    # another tenant's map, the shared form of the other cells, a short list
    assert reference_churn.parse_answer(good, 10, "t008") is None
    assert reference_churn.parse_answer(good, 10, "t00") is None
    plain = {"itemScores": [{"item": f"i{j}", "score": 1.0} for j in range(10)]}
    assert reference_churn.parse_answer(plain, 10, "t007") is None
    assert reference_churn.parse_answer({"itemScores": good["itemScores"][:9]}, 10, "t007") is None
    assert reference_churn.parse_answer(None, 10, "t007") is None
    assert reference_churn.parse_answer({"itemScores": [{"item": 3}] * 10}, 10, "t007") is None


def test_the_two_shortfalls():
    assert reference_churn.cold_shortfall(171, 512) == 0
    assert reference_churn.cold_shortfall(170, 512) == 1
    assert reference_churn.cold_shortfall(0, 128) == 43
    assert reference_churn.cold_shortfall(0, 0) == 1  # an empty sample is short
    assert reference_churn.shortfall(100, 250) == 0
    assert reference_churn.shortfall(100, 37) == 63


def test_the_sample_takes_the_reloaded_tenants_first():
    from runners import serve_churn

    def post(tenant, n=64):
        return (tenant, [(u, None) for u in range(n)])

    kept = [post(t) for t in (0, 0, 0, 1, 1, 2, 3, 40, 41, 42, 43, 44, 45, 300, 301)]
    cold = {40, 41, 42, 43, 44, 45, 300, 301}
    traffic = {"check_tenants": 8, "check_queries": 512}
    for seed in (1, 2, 3):
        sample = serve_churn.sample_answers(kept, traffic, seed, cold)
        assert sum(len(v) for v in sample.values()) == 512
        in_cold = [t for t in sample if t in cold]
        assert len(in_cold) == 4 and len(sample) <= 8
        assert sum(len(sample[t]) for t in in_cold) == 256
    # too few reloaded tenants among the kept posts: what there is, then the rest
    sample = serve_churn.sample_answers(kept, traffic, 1, {300})
    assert len(sample[300]) == 64
    assert sum(len(v) for v in sample.values()) == 512
    assert reference_churn.cold_shortfall(64, 512) > 0
    # every tenant reloaded (the rehearsal): the second pass takes them too
    sample = serve_churn.sample_answers(kept, traffic, 1, {p[0] for p in kept})
    assert sum(len(v) for v in sample.values()) == 512
    assert serve_churn.sample_answers([], traffic, 1, cold) == {}


def _counter(samples):
    return {"samples": [{"labels": lab, "value": v} for lab, v in samples]}


def _stages(rows):
    return {"samples": [
        {"labels": {"stage": name}, "count": n, "sum": s} for name, (n, s) in rows.items()
    ]}


def hand_made_run():
    """A traced run of the cell as the runner gathers one, on the CPU: 50
    misses in 1,000 lookups over the window, their loads and 45 evictions
    beside them, no device plane."""
    before = {
        "pio_pool_hits_total": _counter([({"tenant": "t000"}, 100.0), ({"tenant": "t300"}, 2.0)]),
        "pio_pool_misses_total": _counter([({"tenant": "t000"}, 1.0), ({"tenant": "t300"}, 1.0)]),
        "pio_pool_evictions_total": _counter([({"tenant": "t300"}, 0.0)]),
        "pio_process_clock_seconds_total": _counter([({}, 100.0)]),
        "pio_pool_budget_bytes": _counter([({}, 8.0 * 2**30)]),
        "pio_pool_tenants_resident": _counter([({}, 405.0)]),
        "pio_stage_seconds": _stages({
            "pool.load": (406, 40.6), "pool.read": (406, 8.12), "pool.deserialize": (406, 20.3),
            "pool.promote": (406, 4.06), "pool.warmup": (406, 4.06), "pool.close": (0, 0.0),
            "pool.wait": (406, 50.0),
        }),
    }
    after = {
        "pio_pool_hits_total": _counter([({"tenant": "t000"}, 1040.0), ({"tenant": "t300"}, 12.0)]),
        "pio_pool_misses_total": _counter([({"tenant": "t000"}, 1.0), ({"tenant": "t300"}, 51.0)]),
        "pio_pool_evictions_total": _counter([({"tenant": "t300"}, 45.0)]),
        "pio_process_clock_seconds_total": _counter([({}, 145.0)]),
        "pio_pool_budget_bytes": _counter([({}, 8.0 * 2**30)]),
        "pio_pool_tenants_resident": _counter([({}, 406.0)]),
        "pio_stage_seconds": _stages({
            "pool.load": (456, 46.6), "pool.read": (456, 9.12), "pool.deserialize": (456, 23.3),
            "pool.promote": (456, 4.56), "pool.warmup": (456, 4.26), "pool.close": (45, 0.9),
            "pool.wait": (456, 60.0),
        }),
    }
    return {
        "before": before, "after": after, "built": before, "trace": {}, "load": {},
        "config": {}, "traffic": {"route": "/batch/queries.json"},
        "memory_peak_bytes": int(8.25 * 2**30), "peak": None,
    }


def test_churn_readers_on_hand_made_runs():
    run = hand_made_run()
    before, after = run["before"], run["after"]
    read = lambda base: layer_metrics.read("churn." + base, run)  # noqa: E731
    assert read("miss_share") == pytest.approx(100 * 50 / 1000)
    assert read("cold_load_ms") == pytest.approx(1e3 * 6.0 / 50)
    assert read("load_read_ms") == pytest.approx(20.0)
    assert read("load_deserialize_ms") == pytest.approx(60.0)
    assert read("load_promote_ms") == pytest.approx(10.0)
    assert read("load_warmup_ms") == pytest.approx(4.0)
    assert read("evict_close_ms") == pytest.approx(20.0)
    assert read("cold_wait_ms") == pytest.approx(200.0)
    assert read("loader_busy_share") == pytest.approx(100 * 6.9 / 45)
    assert read("evictions_per_s") == pytest.approx(1.0)
    assert read("resident_tenants") == 406.0
    assert read("over_ledger_gib") == pytest.approx(0.25)
    assert read("setup_preload_s") == pytest.approx(40.6)
    # the window's reloaded tenants, by the misses' delta
    from runners import serve_churn

    assert serve_churn.cold_tenants(run) == {300}
    pooled = serve_churn.pool_numbers(run, before)
    assert (pooled["loads"], pooled["evictions"], pooled["misses"], pooled["lookups"]) == (
        50, 45, 50, 1000
    )
    assert (pooled["loads_in_setup"], pooled["evictions_in_setup"]) == (406, 0)
    # a program without the pool's stages and series, as the parent: nothing,
    # never a 0, and no reader raises
    clock = {"pio_process_clock_seconds_total": before["pio_process_clock_seconds_total"]}
    bare = {**run, "before": clock, "after": {
        "pio_process_clock_seconds_total": after["pio_process_clock_seconds_total"]
    }, "built": {}, "memory_peak_bytes": 0}
    for base in POOL:
        assert read(base) is not None, base
        assert layer_metrics.read("churn." + base, bare) is None, base


def _check_the_block(bench, root):
    """What this PR owns of the manifest, and nothing about what follows it."""
    # what was accepted before this block: the KDD Cup, e-commerce and
    # similar-product cells' names, less those folded
    before = rules.load_accepted(root)["per_layer"][:65]
    block = rules.check_owned_block(bench, root, CELL, PER_LAYER, before)
    assert len(block) == 13
    assert [m["moves"] for m in block] == ["queries_per_s"] * 12 + ["setup_s"]
    assert {m["layer"] for m in block} == {"model pool"}
    assert rules.reports(bench, "end_to_end", CELL) == ["setup_s", "queries_per_s"]
    cell = rules.entry(bench, "workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "churn_batch_closed_loop", 1
    )
    assert rules.reference_module(bench, root, CONFIG) == "reference_churn"
    traffic = rules.traffic_body(bench, root, cell["traffic"])
    assert traffic["runner"] == "serve_churn"
    assert (traffic["loop"], traffic["clients"], traffic["procs"], traffic["batch"]) == (
        "closed", 4, 2, 64
    )
    entry = rules.entry(bench, "configs", CONFIG)
    rules.check_config_entry(bench, root, entry)
    rules.check_control_is_tested(bench, root, entry)
    for metric in block:
        rules.check_per_layer_metric(bench, root, metric)


def test_new_cell_and_its_entries_come_after_the_accepted(manifest):
    _check_the_block(*manifest)
    rules.check_folded(*manifest, CELL)


def _an_entry_moved_away(bench):
    names = [m["name"] for m in bench["per_layer"]]
    bench["per_layer"].insert(10, bench["per_layer"].pop(names.index(PER_LAYER[3])))


def _an_entry_read_in_another_cell_too(bench):
    rules.entry(bench, "per_layer", "churn.miss_share")["workloads"].append("serve-pool-batch")


def _the_cell_before_an_accepted_one(bench):
    cells = [w["name"] for w in bench["workloads"]]
    bench["workloads"].insert(1, bench["workloads"].pop(cells.index(CELL)))


def _the_cell_gone(bench):
    bench["workloads"] = [w for w in bench["workloads"] if w["name"] != CELL]


@pytest.mark.parametrize("fault,message", [
    (_an_entry_moved_away, "stand together and in their order"),
    (_an_entry_read_in_another_cell_too, "'churn.miss_share' is read in 'serve-pool-churn-batch' alone"),
    (_the_cell_before_an_accepted_one, "'serve-pool-churn-batch' comes after the cells accepted before it"),
    (_the_cell_gone, "no cell 'serve-pool-churn-batch' in workloads"),
], ids=lambda v: getattr(v, "__name__", "message"))
def test_each_fault_in_the_block_fails_on_its_own_assertion(manifest, fault, message):
    bench, root = manifest
    fault(bench)
    with pytest.raises(AssertionError, match=re.escape(message)):
        _check_the_block(bench, root)


def test_the_configuration_states_the_deployment():
    """The widths of MovieLens 20M uncut, the bytes they come to, the pool's
    budget left to deploy, and a tiny size at which the pool still evicts."""
    cfg = rules.config_body(BENCH, ROOT, CONFIG)
    assert (cfg["n_users"], cfg["n_items"], cfg["n_ratings"], cfg["rank"]) == (
        138493, 26744, 20000263, 32
    )
    assert rules.entry(BENCH, "configs", CONFIG)["reduced"] == []
    per_tenant = (138493 + 26744) * 32 * 4
    assert cfg["table_bytes_per_tenant"] == per_tenant == 21150336
    # the budget deploy's default chose on the chip: half of 15.75 GiB
    resident = 8454668032 // per_tenant
    assert resident == 399 and cfg["resident_table_bytes"] == resident * per_tenant
    assert cfg["tenants"] * per_tenant > 1.25 * 8454668032  # a quarter more than fits
    assert cfg["resident_table_bytes"] > 0.49 * rules.CHIP_MEMORY_BYTES
    assert cfg["pool_budget_bytes"] is None and cfg["server"] == rules.DEPLOY_DEFAULTS
    assert cfg["limits"]["missing_evictions"] == 0 and cfg["min_evictions"] == 100
    tiny = cfg["rehearse"]
    room = tiny["pool_budget_bytes"] // ((tiny["n_users"] + tiny["n_items"]) * 32 * 4)
    assert (tiny["tenants"], room) == (6, 4)


def _rehearse(seed=11, seconds=1.5, trace=0, control=""):
    bench, cell, config, traffic = chip_run.load_cell(CELL, True)
    from runners import serve_churn

    args = argparse.Namespace(seed=seed, seconds=seconds, trace=trace, control=control)
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    return serve_churn.run(cell, bench, config, traffic, args, time.monotonic(), device)


def test_sound_rehearsal_is_correct_and_churns(servers_built):
    result = _rehearse(seed=2**31 + 4321, seconds=2.0, trace=1, control="wrong_blob")
    assert result["correct"] is True, result["compared"]
    assert servers_built == [(2, True)]
    assert result["failed"] == 0 and result["attempted"] > 500
    for name in ("bad_answers", "unanswered", "failed_posts", "cold_sample_short",
                 "missing_evictions", "leaked_threads"):
        assert result["compared"][name][0] == 0, name
    pool = result["pool"]
    # the preload filled the pool and stopped: four of six, no eviction
    assert (pool["loads_in_setup"], pool["evictions_in_setup"]) == (4, 0)
    assert pool["evictions"] >= 2 and pool["loads"] >= pool["evictions"]
    assert pool["resident_at_end"] == 4 and pool["budget_bytes"] == 4608000
    assert result["sampled"]["queries"] == 128
    assert 3 * result["sampled"]["cold_queries"] >= result["sampled"]["queries"]
    shared = ("http_request_ms", "submit_ms", "await_ms", "queue_wait_ms", "batch_occupancy",
              "device_dispatch_ms", "host_cpu_share", "compiles_in_window", "launch_calls_share")
    for name in ["batch." + base for base in shared] + PER_LAYER[:11] + PER_LAYER[12:]:
        assert name in result["metrics"], name
    value = lambda name: result["metrics"]["churn." + name]["value"]  # noqa: E731
    assert result["metrics"]["batch.compiles_in_window"]["value"] == 0
    assert 2 < value("miss_share") < 60
    assert value("resident_tenants") == 4
    assert 0 < value("loader_busy_share") < 100
    assert value("cold_wait_ms") > 0.3 * value("cold_load_ms")
    # every bucket was warmed by the first tenant: a later load warms none
    assert value("load_warmup_ms") < 0.2 * value("cold_load_ms")
    # no device on the CPU: a share of a peak, or the chip's memory, is left out
    for name in ("batch.topk_roofline", "batch.serve_mfu", "batch.hbm_peak_gib",
                 "churn.over_ledger_gib"):
        assert name not in result["metrics"], name
    breakdown = result["breakdown"]
    # a cover is a ratio of sums over the window: a post or a load that is
    # open at one of its ends counts on one side only
    posts = result["attempted"] / 64
    assert 0.9 - 2 / posts < breakdown["handler_cover"] <= 1.001 + 2 / posts
    edge = 1 / pool["loads"]
    assert 0.9 - edge < breakdown["load_cover"] <= 1.001 + edge
    assert pool["wait_ms"] > 0.3 * pool["load_ms"] > 0
    # a miss counts when its wait begins, the wait when it ends
    assert abs(sum(pool["waits_by_bucket_s"].values()) - pool["misses"]) <= 2
    assert pool["requests"] > 8 and pool["wait_s"] < pool["request_s"]
    assert "pool.load" in breakdown["stage_ms"] and "pool.wait" in breakdown["stage_ms"]
    assert result["control"]["rank_gap_rms"] > 100 * result["compared"]["rank_gap_rms"][1]
    assert list(result)[-1] == "compared"


def test_a_loader_that_stages_the_wrong_generation_is_not_correct(monkeypatch):
    """The rest of a run over a server whose reloads stage the next
    tenant's variant: the answers after a reload carry another tenant's
    ids, and `correct` comes out false on them."""
    from predictionio_tpu.serving.engine_server import EngineServer

    stage = EngineServer._stage
    staged = set()

    def wrong_on_reload(self, *args, engine_variant=None, tenant=None, **kwargs):
        if tenant in staged:
            engine_variant = f"t{(int(tenant[1:]) + 1) % len(self._tenants):03d}"
        staged.add(tenant)
        return stage(self, *args, engine_variant=engine_variant, tenant=tenant, **kwargs)

    monkeypatch.setattr(EngineServer, "_stage", wrong_on_reload)
    result = _rehearse()
    assert result["correct"] is False
    assert result["compared"]["bad_answers"][0] > 0


def test_a_program_without_the_pool_s_stages_fails_at_once(monkeypatch):
    """Over the parent's program the runner ends the run before it builds
    anything: the driver's try of the new cell on the parent fails cleanly."""
    from predictionio_tpu.obs import tracing

    monkeypatch.delattr(tracing, "POOL_LOAD")
    t0 = time.monotonic()
    with pytest.raises(SystemExit, match="no `pool.load` stage"):
        _rehearse()
    assert time.monotonic() - t0 < 5.0


def test_the_cell_rehearses_through_the_command_from_the_tree_and_the_copy(manifest):
    """`run.py --rehearse-cpu` from the checkout and from the copy grown by
    another PR's addition: the cell's files are found by name in both."""
    _bench, root = manifest
    done = subprocess.run(
        [sys.executable, os.path.join("benchmarks", "chip", "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 36), "--seconds", "1.5", "--trace", "0", "--rehearse-cpu"],
        capture_output=True, text=True, timeout=300, cwd=root,
        env={**os.environ, "PYTHONPATH": ROOT},
    )
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"setup_s", "queries_per_s"}
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert line["failed"] == 0 and line["pool"]["evictions"] >= 2
    assert "compared missing_evictions 0.0 limit 0" in done.stderr
    # the server and its pool closed: nothing of theirs is left on the device
    assert line["pool"]["alive_after_close_bytes"] == 0


def test_a_generation_kept_past_its_eviction_reads_over_the_ledger():
    """The third planted fault, in the run itself. At the cell's size, by
    the two readings `PERF.md` §2 gives: a sound run's `over_ledger_gib`
    passes, one tenant's tables more do not, by that limit alone, with
    room on both sides. At the tiny size, through the command: the runner
    keeps one staged generation, the pool evicts it, and its tables are
    what is left on the device after the server and the pool have closed."""
    cfg = rules.config_body(BENCH, ROOT, CONFIG)
    assert cfg["control"]["third"] in reference_churn.RUN_CONTROLS
    users, items = _tables(5)
    idx = np.arange(32)
    top_idx, top = reference.top_k(reference.reference_scores(users[idx], items), 10)
    exact = [(top_idx[q], top[q]) for q in range(32)]
    sound = (8469914624 - 8454668032) / 2**30
    kept = sound + cfg["table_bytes_per_tenant"] / 2**30
    limit = cfg["limits"]["over_ledger_gib"]
    assert 1.5 * sound < limit < kept / 1.3
    correct, _ = reference_churn.judge(
        _numbers(users, items, idx, exact, over_ledger_gib=sound), cfg["limits"]
    )
    assert correct
    correct, compared = reference_churn.judge(
        _numbers(users, items, idx, exact, over_ledger_gib=kept), cfg["limits"]
    )
    assert not correct
    assert [k for k, (v, lim) in compared.items() if v > lim] == ["over_ledger_gib"]

    done = subprocess.run(
        [sys.executable, os.path.join("benchmarks", "chip", "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 37), "--seconds", "1.5", "--trace", "0", "--rehearse-cpu",
         "--control", "kept_generation"],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
        env={**os.environ, "PYTHONPATH": ROOT},
    )
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    tiny = cfg["rehearse"]
    # the least popular of the four the preload staged, evicted under the run
    assert line["control"]["kept_tenant"] in {f"t{t:03d}" for t in range(tiny["tenants"])}
    assert line["control"]["evictions_of_kept"] >= 1
    assert line["pool"]["alive_after_close_bytes"] == (
        (tiny["n_users"] + tiny["n_items"]) * cfg["rank"] * 4
    )
    assert line["pool"]["leaked_threads"] == 0 and line["failed"] == 0
