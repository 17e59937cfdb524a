"""The e-commerce cell's reference, its two controls, its roofline
arithmetic and its readers, and a rehearsal of the cell at its tiny size
on the CPU: sound, and with the served path broken underneath."""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHIP = os.path.join(ROOT, "benchmarks", "chip")
sys.path[:0] = [CHIP, ROOT, os.path.dirname(os.path.abspath(__file__))]

import layer_metrics  # noqa: E402
import loadgen_ecomm  # noqa: E402
import manifest_rules as rules  # noqa: E402
import reference_ecomm  # noqa: E402
import roofline  # noqa: E402
import roofline_ecomm  # noqa: E402
import run as chip_run  # noqa: E402

BENCH = rules.load_bench(ROOT)
CONFIG = "ecomm-pool-taobao-ub"
CELL = "serve-ecomm-batch"
#: PR 28's per-layer entries, by name and in their order; a later PR's
#: `ecomm.*` entry (PR 32's `ecomm.launch_calls_share`) is none of them
PER_LAYER = [
    "ecomm." + base for base in (
        "http_request_ms", "queue_wait_ms", "batch_occupancy", "device_dispatch_ms",
        "topk_device_ms", "device_idle_share", "launch_idle_share", "hbm_peak_gib",
        "host_cpu_share", "compiles_in_window", "serve_mfu", "setup_compile_s",
        "rules_lookup_ms", "excluded_per_query", "filtered_share", "cold_share",
        "masked_topk_roofline",
    )
]


def _shop(seed, n_users=300, n_items=6000, n_categories=40, rank=16):
    rng = np.random.default_rng(seed)
    users = (0.25 * rng.standard_normal((n_users, rank))).astype(np.float32)
    norms = np.exp(0.5 * rng.standard_normal((n_items, 1)))
    items = (norms * rng.standard_normal((n_items, rank))).astype(np.float32)
    unavailable = np.zeros(n_items, bool)
    unavailable[loadgen_ecomm.unavailable_items(seed, 0, n_items)] = True
    active = loadgen_ecomm.active_users(seed, n_users, 200)
    seen = {
        f"u{int(u)}": rows
        for u, rows in zip(active, loadgen_ecomm.seen_lists(seed, n_items, 200))
    }
    views = {
        k: v.astype(np.int64)
        for k, v in loadgen_ecomm.unknown_views(seed, 0, n_items).items()
    }
    seen.update(views)
    return active, reference_ecomm.Shop(
        users=users, items=items,
        category=loadgen_ecomm.item_categories(seed, 0, n_items, n_categories),
        popularity=np.argsort(np.argsort(norms[:, 0])).astype(np.float64),
        unavailable=unavailable, seen=seen, views=views,
    )


def _queries(seed, active, n_items=6000, n_categories=40, posts=3):
    rng = np.random.default_rng([seed, 99])
    plan = {"n_items": n_items, "num": 10, "batch": 64, "unknown_share": 0.05}
    weights = loadgen_ecomm.zipf_weights(n_categories, 1.0)
    return [
        q for _ in range(posts)
        for q in loadgen_ecomm.draw_queries(rng, plan, active, weights)
    ]


def _numbers(shop, queries, answers):
    comparison = reference_ecomm.Comparison(10)
    comparison.add(shop, queries, answers)
    return {
        **comparison.numbers(), "stale_answers": 0.0, "unanswered": 0.0,
        "evictions": 0.0,
    }


@pytest.mark.parametrize("control", ["fp8", "filter_after_top64"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_comes_out_not_correct(control, seed):
    """The reference's own answers pass the configuration's limits; the
    reference one precision step down fails ``score_rms`` and
    ``rank_gap_rms``; the template's former algorithm (rules after the
    global top 64) answers short."""
    cfg = rules.config_body(BENCH, ROOT, CONFIG)
    assert cfg["control"]["precision"] == "fp8"
    assert cfg["control"]["second"] == "filter_after_top64"
    active, shop = _shop(seed)
    queries = _queries(seed, active)
    exact = reference_ecomm.reference_answers(shop, queries, 10)
    correct, compared = reference_ecomm.judge(_numbers(shop, queries, exact), cfg["limits"])
    assert correct, compared
    served = reference_ecomm.control_answers(shop, queries, 10, control)
    correct, compared = reference_ecomm.judge(_numbers(shop, queries, served), cfg["limits"])
    assert not correct
    if control == "fp8":
        assert compared["score_rms"][0] > compared["score_rms"][1]
        assert compared["rank_gap_rms"][0] > compared["rank_gap_rms"][1]
        assert compared["bad_answers"][0] == 0
    else:
        assert compared["short_answers"][0] > 0


def test_comparison_counts_each_kind_of_wrong_answer():
    active, shop = _shop(5)
    user = f"u{int(active[0])}"
    seen = int(shop.seen[user][0])
    queries = [
        {"user": user, "num": 10},
        {"user": user, "num": 10, "categories": ["c3"]},
        {"user": user, "num": 10, "blackList": ["i7"]},
        {"user": user, "num": 10, "whiteList": [f"i{i}" for i in range(200, 300)]},
        {"user": "n5", "num": 10},
    ]
    exact = reference_ecomm.reference_answers(shop, queries, 10)
    assert _numbers(shop, queries, exact)["rule_violations"] == 0

    def swapped(q, row):
        rows, scores = exact[q]
        return [
            (np.r_[row, rows[1:]], scores) if k == q else a for k, a in enumerate(exact)
        ]

    outside = int(np.flatnonzero(shop.category != 3)[0])
    unavailable = int(np.flatnonzero(shop.unavailable)[0])
    for q, row in ((0, seen), (1, outside), (2, 7), (3, 5), (4, unavailable)):
        numbers = _numbers(shop, queries, swapped(q, row))
        assert numbers["rule_violations"] >= 1, (q, row)
    # an item the reference scores below 0 for a known user
    scores = reference_ecomm.all_scores(shop, queries[:1])[1][0]
    negative = int(np.argmin(np.where(reference_ecomm.candidates(shop, queries[0]), scores, 0)))
    assert _numbers(shop, queries, swapped(0, negative))["rule_violations"] == 1
    short = [(exact[0][0][:6], exact[0][1][:6])] + exact[1:]
    assert _numbers(shop, queries, short)["short_answers"] == 1
    bad = [None, (exact[1][0][[0, 0, 1]], exact[1][1][:3])] + exact[2:]
    assert _numbers(shop, queries, bad)["bad_answers"] == 1  # None; the double is parse's
    good = {"itemScores": [{"item": f"i{j}", "score": 1.0 - j / 10} for j in range(4)]}
    assert reference_ecomm.parse_answer(good, 10, 6000)[0].tolist() == [0, 1, 2, 3]
    assert reference_ecomm.parse_answer({"itemScores": []}, 10, 6000)[0].tolist() == []
    twice = {"itemScores": good["itemScores"][:1] * 2}
    assert reference_ecomm.parse_answer(twice, 10, 6000) is None
    assert reference_ecomm.parse_answer(good, 3, 6000) is None
    assert reference_ecomm.parse_answer(good, 10, 3) is None


def test_seeded_data_has_the_shape_the_configuration_states():
    cfg = rules.config_body(BENCH, ROOT, CONFIG)
    sizes = loadgen_ecomm.category_sizes(cfg["n_items"], cfg["n_categories"])
    assert sizes.sum() == cfg["n_items"] and len(sizes) == 9439
    assert 420_000 < sizes[0] < 435_000 and 40 <= sizes[-1] <= 50
    lists = loadgen_ecomm.seen_lists(3, cfg["n_items"], 3859)
    lengths = np.array([len(x) for x in lists])
    assert 80 < lengths.mean() < 106 and lengths.max() <= 4096 and lengths.min() >= 1
    assert all(len(np.unique(x)) == len(x) for x in lists[:50])
    assert len(loadgen_ecomm.unavailable_items(3, 0, cfg["n_items"])) == 41620
    active = loadgen_ecomm.active_users(3, cfg["n_users"], cfg["active_users"])
    assert len(np.unique(active)) == cfg["active_users"]
    rng = np.random.default_rng(0)
    plan = {"n_items": cfg["n_items"], "num": 10, "batch": 4000, "unknown_share": 0.05}
    queries = loadgen_ecomm.draw_queries(
        rng, plan, active, loadgen_ecomm.zipf_weights(cfg["n_categories"], 1.0)
    )
    share = lambda key: np.mean([key in q for q in queries])  # noqa: E731
    assert 0.27 < share("categories") < 0.33 and 0.13 < share("blackList") < 0.17
    assert 0.035 < share("whiteList") < 0.065
    assert 0.03 < np.mean([q["user"][0] != "u" for q in queries]) < 0.07
    # what the manifest says of the cut matches the file
    assert rules.entry(BENCH, "configs", CONFIG)["reduced"] == ["n_seen_events"]
    assert cfg["n_seen_events"] == cfg["tenants"] * cfg["active_users"] * 93
    assert cfg["resident_table_bytes"] == cfg["tenants"] * (
        cfg["n_users"] + cfg["n_items"]
    ) * cfg["rank"] * 4 > 0.25 * rules.CHIP_MEMORY_BYTES


def test_roofline_ecomm_arithmetic():
    peak = roofline.peaks("TPU v5 lite")
    assert roofline_ecomm.masked_topk_ops(64, 4162024, 16) == 2 * 64 * 4162024 * 16
    nbytes = roofline_ecomm.masked_topk_bytes(64, 4162024, 16, 10, 6000)
    assert nbytes == (
        4162024 * (64 + 4 + 0.125) + 4 * 6000 + 64 * (64 + 76 + 1) + 64 * 10 * 8
    )
    # bound by the bytes of the table and the rules' per-item data
    least = roofline.roofline_seconds(
        roofline_ecomm.masked_topk_ops(64, 4162024, 16), nbytes, peak
    )
    assert least == pytest.approx(nbytes / 819e9) and least > 8.5e9 / 197e12
    # the same work whatever implements it: nothing of the batch x items
    # intermediate an XLA program writes out is counted
    assert nbytes < 64 * 4162024 * 4 / 3


def _counter(value, labels=None):
    return {"samples": [{"labels": labels or {}, "value": value}]}


def test_ecomm_readers_on_hand_made_runs():
    cfg = rules.config_body(BENCH, ROOT, CONFIG)
    before = {
        "pio_batch_occupancy": {"samples": [{"labels": {}, "count": 0, "sum": 0.0}]},
    }
    after = {
        "pio_batch_occupancy": {"samples": [{"labels": {}, "count": 100, "sum": 6400.0}]},
        "pio_ecomm_queries_total": {"samples": [
            {"labels": {"branch": "known"}, "value": 6080.0},
            {"labels": {"branch": "similar"}, "value": 160.0},
            {"labels": {"branch": "popular"}, "value": 160.0},
        ]},
        "pio_ecomm_filtered_queries_total": {"samples": [
            {"labels": {"rule": "categories"}, "value": 1920.0},
            {"labels": {"rule": "blackList"}, "value": 960.0},
            {"labels": {"rule": "whiteList"}, "value": 320.0},
        ]},
        "pio_ecomm_excluded_items_total": _counter(640000.0),
        "pio_stage_seconds": {"samples": [
            {"labels": {"stage": "predict.rules"}, "count": 100, "sum": 0.05},
        ]},
    }
    run = {
        "before": before, "after": after, "config": cfg,
        "traffic": {"route": "/batch/queries.json"},
        "peak": roofline.peaks("TPU v5 lite"), "traced_queries": 6400.0,
        "trace": {
            "window_s": 4.0, "busy_s": 2.0, "module_s": 0.5,
            "module_runs": {"jit__rules_top_k": 100},
        },
    }
    assert layer_metrics.read("ecomm.excluded_per_query", run) == pytest.approx(100.0)
    assert layer_metrics.read("ecomm.filtered_share", run) == pytest.approx(50.0)
    assert layer_metrics.read("ecomm.cold_share", run) == pytest.approx(5.0)
    assert layer_metrics.read("ecomm.rules_lookup_ms", run) == pytest.approx(0.5)
    assert layer_metrics.read("ecomm.topk_device_ms", run) == pytest.approx(5.0)
    least = roofline_ecomm.masked_topk_bytes(64, 4162024, 16, 10, 6400) / 819e9
    assert layer_metrics.read("ecomm.masked_topk_roofline", run) == pytest.approx(
        100 * least / 0.005
    )
    mfu = 100 * (6400 * 2 * 16 * 4162024 / 197e12) / 4.0
    assert layer_metrics.read("ecomm.serve_mfu", run) == pytest.approx(mfu)
    # a program without the counters, or no trace: nothing, never a 0
    bare = {**run, "after": before, "trace": {}}
    for name in ("excluded_per_query", "filtered_share", "cold_share",
                 "rules_lookup_ms", "masked_topk_roofline"):
        assert layer_metrics.read("ecomm." + name, bare) is None, name


def _check_the_block(bench, root):
    """What PR 28 owns of the manifest, and nothing about what follows it."""
    accepted_before = rules.load_accepted(root)["per_layer"][:50]
    block = rules.check_owned_block(bench, root, CELL, PER_LAYER, accepted_before)
    assert len(block) == 17
    assert {m["moves"] for m in block} == {"queries_per_s", "setup_s"}
    assert rules.reports(bench, "end_to_end", CELL) == ["setup_s", "queries_per_s"]
    assert rules.entry(bench, "workloads", CELL)["config"] == CONFIG
    assert rules.reference_module(bench, root, CONFIG) == "reference_ecomm"


def test_new_cell_and_its_entries_come_after_the_accepted(manifest):
    bench, root = manifest
    _check_the_block(bench, root)
    listed = [m["name"] for m in bench["per_layer"]]
    assert listed.index("ecomm.launch_calls_share") > listed.index(PER_LAYER[-1])


def _an_entry_between(bench):
    bench["per_layer"].insert(60, bench["per_layer"].pop())


def _the_block_s_order_changed(bench):
    bench["per_layer"][52], bench["per_layer"][53] = bench["per_layer"][53], bench["per_layer"][52]


def _an_entry_read_in_another_cell_too(bench):
    rules.entry(bench, "per_layer", "ecomm.queue_wait_ms")["workloads"].append("serve-pool-batch")


def _the_block_before_an_accepted_entry(bench):
    bench["per_layer"].append(bench["per_layer"].pop(49))


def _the_cell_before_an_accepted_one(bench):
    bench["workloads"].insert(1, bench["workloads"].pop(3))


def _the_cell_gone(bench):
    bench["workloads"] = [w for w in bench["workloads"] if w["name"] != CELL]


@pytest.mark.parametrize("fault,message", [
    (_an_entry_between, "stand together and in their order"),
    (_the_block_s_order_changed, "stand together and in their order"),
    (_an_entry_read_in_another_cell_too, "'ecomm.queue_wait_ms' is read in 'serve-ecomm-batch' alone"),
    (_the_block_before_an_accepted_entry, "come after the ones accepted before them"),
    (_the_cell_before_an_accepted_one, "'serve-ecomm-batch' comes after the cells accepted before it"),
    (_the_cell_gone, "no cell 'serve-ecomm-batch' in workloads"),
], ids=lambda v: getattr(v, "__name__", "message"))
def test_each_fault_in_the_block_fails_on_its_own_assertion(manifest, fault, message):
    import re

    bench, root = manifest
    fault(bench)
    with pytest.raises(AssertionError, match=re.escape(message)):
        _check_the_block(bench, root)


def _rehearse(seed=11, seconds=1.5, trace=0, control=""):
    bench, cell, config, traffic = chip_run.load_cell(CELL, True)
    from runners import serve_ecomm

    args = argparse.Namespace(seed=seed, seconds=seconds, trace=trace, control=control)
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    return serve_ecomm.run(cell, bench, config, traffic, args, time.monotonic(), device)


def test_sound_rehearsal_is_correct_and_reports_the_rules(servers_built):
    result = _rehearse(seed=2**31 + 4321, trace=1, control="filter_after_top64")
    assert result["correct"] is True, result["compared"]
    assert servers_built == [(2, True)]
    assert result["failed"] == 0 and result["attempted"] > 500
    for name in ("rule_violations", "short_answers", "stale_answers", "evictions"):
        assert result["compared"][name][0] == 0, name
    assert result["sampled"]["probes"] == 128 and result["sampled"]["queries"] == 96
    for name in ("ecomm.rules_lookup_ms", "ecomm.excluded_per_query",
                 "ecomm.filtered_share", "ecomm.cold_share", "ecomm.queue_wait_ms",
                 "ecomm.compiles_in_window", "ecomm.batch_occupancy",
                 "ecomm.launch_calls_share"):
        assert name in result["metrics"], name
    assert result["metrics"]["ecomm.compiles_in_window"]["value"] == 0
    assert 95 < result["metrics"]["ecomm.launch_calls_share"]["value"] < 105
    assert 40 < result["metrics"]["ecomm.filtered_share"]["value"] < 60
    # no device plane on the CPU: a share of a peak is left out, not 0
    assert "ecomm.masked_topk_roofline" not in result["metrics"]
    assert "ecomm.serve_mfu" not in result["metrics"]
    assert result["control"]["short_answers"] > 0
    assert result["phases"]["events"] > 3 * 200 * 30
    assert list(result)[-1] == "compared"


@pytest.mark.parametrize("fault", ["lists_never_reach_the_device", "stale_rules"])
def test_broken_served_path_is_not_correct(monkeypatch, fault):
    """The rest of a run over a template whose seen, black and white lists
    never reach the device, or that reads each user's events once and
    never again: `correct` comes out false."""
    from predictionio_tpu.models.ecommerce import ECommAlgorithm
    from predictionio_tpu.ops import similarity

    if fault == "stale_rules":
        from predictionio_tpu.data.storage.memory import MemoryEvents

        monkeypatch.setattr(MemoryEvents, "entity_version", lambda *a: 1)
        result = _rehearse()
        assert result["correct"] is False
        assert result["compared"]["stale_answers"][0] > 0
        return
    launch = ECommAlgorithm.batch_predict_launch

    def no_lists(self, model, queries):
        # the lists never reach the device
        pack = similarity.pack_lists
        monkeypatch.setattr(similarity, "pack_lists", lambda lists: pack([x[:0] for x in lists]))
        try:
            return launch(self, model, queries)
        finally:
            monkeypatch.setattr(similarity, "pack_lists", pack)

    monkeypatch.setattr(ECommAlgorithm, "batch_predict_launch", no_lists)
    result = _rehearse()
    assert result["correct"] is False
    assert result["compared"]["stale_answers"][0] > 0


def test_a_program_without_the_rules_step_fails_at_once(monkeypatch):
    """Over the parent's program the runner ends the run before it builds
    anything: the driver's try of the new cell on the parent fails
    cleanly."""
    from predictionio_tpu.models import ecommerce

    monkeypatch.delattr(ecommerce, "StagedRules")
    t0 = time.monotonic()
    with pytest.raises(SystemExit, match="no rules before the top-k"):
        _rehearse()
    assert time.monotonic() - t0 < 5.0
