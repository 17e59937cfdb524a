"""The similar-product cell's reference, its two controls, its seeded data
and its readers, and a rehearsal of the cell at its tiny size on the CPU:
sound, with the served path broken underneath, and on a program whose
template has no launch of its own."""

from __future__ import annotations

import argparse
import os
import re
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHIP = os.path.join(ROOT, "benchmarks", "chip")
sys.path[:0] = [CHIP, ROOT, os.path.dirname(os.path.abspath(__file__))]

import layer_metrics  # noqa: E402
import loadgen_ecomm  # noqa: E402
import loadgen_simprod  # noqa: E402
import manifest_rules as rules  # noqa: E402
import reference_simprod  # noqa: E402
import roofline  # noqa: E402
import roofline_ecomm  # noqa: E402
import run as chip_run  # noqa: E402

BENCH = rules.load_bench(ROOT)
CONFIG = "simprod-pool-taobao-ub"
CELL = "serve-simprod-batch"
#: this PR's per-layer entries, by name and in their order
PER_LAYER = [
    "simprod." + base for base in (
        "http_request_ms", "queue_wait_ms", "batch_occupancy", "device_dispatch_ms",
        "topk_device_ms", "device_idle_share", "launch_idle_share",
        "launch_calls_share", "hbm_peak_gib", "host_cpu_share", "compiles_in_window",
        "serve_mfu", "serve_ms", "launches_per_post", "items_per_query",
        "excluded_per_query", "filtered_share", "like_short_share",
        "both_lists_share", "masked_topk_roofline", "setup_compile_s",
    )
]
PLAN = {
    "n_items": 6000, "num": 10, "batch": 64,
    "unknown_item_share": 0.03, "unknown_query_share": 0.01,
}


def _shop(seed, n_items=6000, n_categories=40, rank=16):
    rng = np.random.default_rng(seed)
    tables = []
    for a in range(2):
        norms = np.exp(0.5 * rng.standard_normal((n_items, 1)))
        tables.append((norms * rng.standard_normal((n_items, rank))).astype(np.float32))
    tables[1][~loadgen_simprod.like_rows(seed, 0, n_items, 0.30)] = 0.0
    return reference_simprod.Shop(
        tables=tables,
        category=loadgen_ecomm.item_categories(seed, 0, n_items, n_categories),
    )


def _queries(seed, n_categories=40, posts=3):
    rng = np.random.default_rng([seed, 99])
    weights = loadgen_simprod.zipf_weights(n_categories, 1.0)
    return [
        q for _ in range(posts)
        for q in loadgen_simprod.draw_queries(rng, PLAN, weights)
    ]


def _numbers(shop, queries, answers):
    comparison = reference_simprod.Comparison(10)
    comparison.add(shop, queries, answers)
    return {**comparison.numbers(), "unanswered": 0.0, "evictions": 0.0}


@pytest.mark.parametrize("control", ["fp8", "filter_after_top"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_comes_out_not_correct(control, seed):
    """The reference's own answers pass the configuration's limits; the
    reference one precision step down does not; the template's former
    algorithm (the filters after a global top-k) answers short."""
    cfg = rules.config_body(BENCH, ROOT, CONFIG)
    assert cfg["control"]["precision"] == "fp8"
    assert cfg["control"]["second"] == "filter_after_top"
    shop, queries = _shop(seed), _queries(seed)
    exact = reference_simprod.reference_answers(shop, queries, 10)
    numbers = _numbers(shop, queries, exact)
    correct, compared = reference_simprod.judge(numbers, cfg["limits"])
    assert correct, compared
    assert numbers["score_rms"] < 1e-9 and numbers["rank_gap_rms"] < 1e-9
    served = reference_simprod.control_answers(shop, queries, 10, control)
    correct, compared = reference_simprod.judge(
        _numbers(shop, queries, served), cfg["limits"]
    )
    assert not correct
    if control == "fp8":
        assert compared["score_rms"][0] > compared["score_rms"][1]
        assert compared["rank_gap_rms"][0] > compared["rank_gap_rms"][1]
        assert compared["bad_answers"][0] == 0
    else:
        assert compared["short_answers"][0] > 0


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_planted_combine_fault_comes_out_not_correct(seed):
    """The reference's own lists through a combine that sums the second
    list's scores as they are: the lists are right and the comparison still
    fails, well past the limit."""
    cfg = rules.config_body(BENCH, ROOT, CONFIG)
    shop, queries = _shop(seed), _queries(seed)
    served = reference_simprod.control_answers(shop, queries, 10, "raw_last_list")
    correct, compared = reference_simprod.judge(
        _numbers(shop, queries, served), cfg["limits"]
    )
    assert not correct
    assert compared["score_rms"][0] > 3 * compared["score_rms"][1]
    assert compared["rule_violations"][0] == 0 and compared["short_answers"][0] == 0


def test_population_deviation_reads_its_own_size():
    """The deviation of the population in place of the sample's moves every
    z of a full list by sqrt(10/9) - 1 = 5.4%, and that is what the
    comparison reads: not forgiven, and on its own under the limit, which
    leaves bfloat16's 4% room (PERF.md section 2: the CPU tests hold the
    combine to 1e-5, this comparison does not see a fault of this size)."""
    cfg = rules.config_body(BENCH, ROOT, CONFIG)
    shop, queries = _shop(4), _queries(4)
    served = reference_simprod.control_answers(
        shop, queries, 10, "population_deviation"
    )
    numbers = _numbers(shop, queries, served)
    # a shorter list moves further: sqrt(n / (n - 1)) - 1
    assert (10 / 9) ** 0.5 - 1 < numbers["score_rms"] < 0.06
    assert numbers["score_rms"] < cfg["limits"]["score_rms"]
    assert numbers["rank_gap_rms"] < 0.01 and numbers["rule_violations"] == 0


@pytest.mark.parametrize("sure, served, want", [
    # held by no list for sure: the one list that may hold it is counted,
    # so a served 0 is charged the whole z and not forgiven as "in no list"
    ((False, False), 0.0, -1.2),
    # held by the second list for sure: with or without the first, nearest
    ((False, True), 0.3, 0.3),
    ((False, True), -1.0, -0.9),
])
def test_a_served_item_is_counted_in_some_list(sure, served, want):
    members = [
        (np.array([-1.2]), np.array([sure[0]]), np.array([True])),
        (np.array([0.3]), np.array([sure[1]]), np.array([False])),
    ]
    got = reference_simprod.nearest_combined(np.array([served]), members)
    assert got.tolist() == pytest.approx([want])


def test_the_edge_band_follows_the_list_s_own_deviation():
    """An item is held for sure once its score lies past the list's last by
    `EDGE_DEVIATIONS` of the list's deviation, whatever the scores' size."""
    scores = np.linspace(0.90, 0.80, 10)
    lists = [(np.arange(10), scores), (np.arange(0), np.zeros(0))]
    deviation = scores.std(ddof=1)
    band = reference_simprod.EDGE_DEVIATIONS * deviation
    probe = np.array([[0.80 + 2 * band, 0.80 + band / 2, 0.80 - band / 2, 0.80 - 2 * band]])
    row_scores = np.vstack([probe, np.zeros((1, 4))])
    (z, sure, maybe), (_z, none, never) = reference_simprod._memberships(
        row_scores, np.ones(4, bool), lists, 10
    )
    assert sure.tolist() == [True, False, False, False]
    assert maybe.tolist() == [False, True, True, False]
    assert not none.any() and not never.any()
    assert band < 0.02 * scores[0]  # the band that was: 2% of the best


def test_comparison_counts_each_kind_of_wrong_answer():
    shop = _shop(5)
    queries = [
        {"items": ["i1", "i2"], "num": 10},
        {"items": ["i3"], "num": 10, "categories": ["c3"]},
        {"items": ["i4"], "num": 10, "blackList": ["i7"]},
        {"items": ["i5"], "num": 10, "whiteList": [f"i{i}" for i in range(200, 300)]},
    ]
    exact = reference_simprod.reference_answers(shop, queries, 10)
    assert _numbers(shop, queries, exact)["rule_violations"] == 0

    def swapped(q, row):
        rows, scores = exact[q]
        return [
            (np.r_[row, rows[1:]], scores) if k == q else a for k, a in enumerate(exact)
        ]

    outside = int(np.flatnonzero(shop.category != 3)[0])
    for q, row in ((0, 1), (1, outside), (2, 7), (3, 6)):
        assert _numbers(shop, queries, swapped(q, row))["rule_violations"] >= 1, (q, row)
    # a candidate that neither list could hold: far down both rankings
    scores = reference_simprod.all_scores(shop, queries[:1])[:, 0]
    low = int(np.argmin(scores.max(0)))
    assert _numbers(shop, queries, swapped(0, low))["rule_violations"] == 1
    short = [(exact[0][0][:6], exact[0][1][:6])] + exact[1:]
    assert _numbers(shop, queries, short)["short_answers"] == 1
    bad = [None] + exact[1:]
    assert _numbers(shop, queries, bad)["bad_answers"] == 1
    # two items swapped across the edge of a list read a small gap, no
    # violation: a query whose like list is empty (its item has no like
    # vector) is answered by the view list alone, and the item just past
    # that list's last is served in the last one's place
    unliked = int(np.flatnonzero(~shop.tables[1].any(axis=1))[0])
    lone = [{"items": [f"i{unliked}"], "num": 10}]
    (rows, z), = reference_simprod.reference_answers(shop, lone, 10)
    _num, ok, lists, scores = reference_simprod.reference_lists(shop, lone, 10)[0]
    assert len(lists[1][0]) == 0 and rows.tolist() == lists[0][0].tolist()
    past, _ = reference_simprod.ranked(scores[0], ok & (scores[0] > 0), 11)
    numbers = _numbers(shop, lone, [(np.r_[rows[:9], past[10]], z)])
    assert numbers["rule_violations"] == 0 and numbers["short_answers"] == 0
    assert 0 < numbers["score_rms"] < 0.2 and numbers["rank_gap_rms"] < 0.2
    good = {"itemScores": [{"item": f"i{j}", "score": 1.0 - j / 10} for j in range(4)]}
    assert reference_simprod.parse_answer(good, 10, 6000)[0].tolist() == [0, 1, 2, 3]
    assert reference_simprod.parse_answer({"itemScores": []}, 10, 6000)[0].tolist() == []
    twice = {"itemScores": good["itemScores"][:1] * 2}
    assert reference_simprod.parse_answer(twice, 10, 6000) is None
    assert reference_simprod.parse_answer(good, 3, 6000) is None
    assert reference_simprod.parse_answer(good, 10, 3) is None


def test_seeded_data_has_the_shape_the_configuration_states():
    cfg = rules.config_body(BENCH, ROOT, CONFIG)
    traffic = rules.traffic_body(BENCH, ROOT, "simprod_batch_closed_loop")
    assert sum(share for share, _lo, _hi in loadgen_simprod.BASKETS) == pytest.approx(1.0)
    liked = loadgen_simprod.like_rows(3, 0, cfg["n_items"], cfg["like_coverage"])
    assert 0.299 < liked.mean() < 0.301 and cfg["like_coverage"] == 0.30
    assert (liked != loadgen_simprod.like_rows(3, 1, cfg["n_items"], 0.30)).any()
    rng = np.random.default_rng(0)
    plan = {
        "n_items": cfg["n_items"], "num": 10, "batch": 8000,
        "unknown_item_share": traffic["unknown_item_share"],
        "unknown_query_share": traffic["unknown_query_share"],
    }
    queries = loadgen_simprod.draw_queries(
        rng, plan, loadgen_simprod.zipf_weights(cfg["n_categories"], 1.0)
    )
    lengths = np.array([len(q["items"]) for q in queries])
    assert 0.47 < np.mean(lengths == 1) < 0.53
    assert 0.32 < np.mean((lengths >= 2) & (lengths <= 8)) < 0.38
    assert 0.11 < np.mean((lengths >= 9) & (lengths <= 16)) < 0.15
    assert 0.012 < np.mean(lengths >= 17) < 0.028 and lengths.max() <= 50
    unknown = [[int(i[1:]) >= cfg["n_items"] for i in q["items"]] for q in queries]
    assert 0.025 < np.mean([u for us in unknown for u in us]) < 0.05
    assert 0.02 < np.mean([all(us) for us in unknown]) < 0.04  # 1% + lone unknowns
    share = lambda key: np.mean([key in q for q in queries])  # noqa: E731
    assert 0.27 < share("categories") < 0.33 and 0.13 < share("blackList") < 0.17
    assert 0.035 < share("whiteList") < 0.065
    assert all(q["num"] == 10 for q in queries)
    # what the manifest says of the widths matches the file
    assert rules.entry(BENCH, "configs", CONFIG)["reduced"] == []
    assert (cfg["n_items"], cfg["n_categories"], cfg["rank"]) == (4162024, 9439, 16)
    assert (cfg["n_view_events"], cfg["n_like_events"]) == (89716264, 2888258)
    assert cfg["algorithms"] == ["view", "like"] and cfg["tenants"] == 10
    rows = -(-cfg["n_items"] // 1024) * 1024
    assert cfg["resident_table_bytes"] == cfg["tenants"] * 2 * rows * cfg["rank"] * 4
    assert cfg["resident_table_bytes"] == 5328076800 > 0.25 * rules.CHIP_MEMORY_BYTES
    assert (cfg["tenants"] - 2) * 2 * rows * 64 < 0.25 * rules.CHIP_MEMORY_BYTES
    for key in ("tenants", "zipf_exponent", "num", "like_coverage", "factors", "categories"):
        assert key in cfg["assumed"], key
    assert len(cfg["departures"]) >= 4


def _counter(samples):
    return {"samples": [{"labels": labels, "value": value} for labels, value in samples]}


def test_simprod_readers_on_hand_made_runs():
    cfg = rules.config_body(BENCH, ROOT, CONFIG)
    before = {
        "pio_batch_occupancy": {"samples": [{"labels": {}, "count": 0, "sum": 0.0}]},
    }
    results = lambda a, *counts: [  # noqa: E731
        ({"algorithm": a, "result": r}, c)
        for r, c in zip(("answered", "short", "empty"), counts)
    ]
    route = {"service": "engine", "route": "/batch/queries.json"}
    after = {
        "pio_batch_occupancy": {"samples": [{"labels": {}, "count": 200, "sum": 12800.0}]},
        "pio_batches_total": _counter([
            ({"batcher": "x/t000/algo0"}, 100.0), ({"batcher": "x/t000/algo1"}, 100.0),
        ]),
        "pio_http_request_seconds": {"samples": [
            {"labels": route, "count": 100, "sum": 3.0},
            {"labels": {"service": "engine", "route": "/"}, "count": 7, "sum": 0.1},
        ]},
        "pio_similar_queries_total": _counter(
            results("view", 6300.0, 60.0, 40.0) + results("like", 4800.0, 1400.0, 200.0)
        ),
        "pio_similar_query_items_total": _counter([
            ({"algorithm": a, "known": k}, c) for a in ("view", "like")
            for k, c in (("yes", 27000.0), ("no", 900.0), ("zero_row", 900.0))
        ]),
        "pio_similar_excluded_items_total": _counter([
            ({"algorithm": "view"}, 76800.0), ({"algorithm": "like"}, 76800.0),
        ]),
        "pio_similar_filtered_queries_total": _counter([
            ({"rule": "category"}, 3840.0), ({"rule": "blackList"}, 1920.0),
            ({"rule": "whiteList"}, 640.0),
        ]),
        "pio_serving_combined_items_total": _counter([
            ({"lists": "1"}, 60000.0), ({"lists": "2"}, 3000.0),
        ]),
    }
    run = {
        "before": before, "after": after, "config": cfg,
        "traffic": {"route": "/batch/queries.json"},
        "peak": roofline.peaks("TPU v5 lite"), "traced_queries": 12800.0,
        "trace": {
            "window_s": 4.0, "busy_s": 3.0, "module_s": 1.2,
            "module_runs": {"jit__rules_top_k": 200},
        },
    }
    assert layer_metrics.read("simprod.launches_per_post", run) == pytest.approx(2.0)
    assert layer_metrics.read("simprod.items_per_query", run) == pytest.approx(4.5)
    assert layer_metrics.read("simprod.excluded_per_query", run) == pytest.approx(12.0)
    assert layer_metrics.read("simprod.filtered_share", run) == pytest.approx(50.0)
    assert layer_metrics.read("simprod.like_short_share", run) == pytest.approx(25.0)
    assert layer_metrics.read("simprod.both_lists_share", run) == pytest.approx(100 / 21)
    assert layer_metrics.read("simprod.topk_device_ms", run) == pytest.approx(6.0)
    assert layer_metrics.read("simprod.device_idle_share", run) == pytest.approx(25.0)
    least = roofline_ecomm.masked_topk_bytes(64, 4162024, 16, 10, 768) / 819e9
    assert layer_metrics.read("simprod.masked_topk_roofline", run) == pytest.approx(
        100 * least / 0.006
    )
    # 2 x 2 x 16 x I operations a query answered: each query is a row of
    # two device batches, which is what the window's occupancy sums
    mfu = 100 * (6400 * 2 * 2 * 16 * 4162024 / 197e12) / 4.0
    assert layer_metrics.read("simprod.serve_mfu", run) == pytest.approx(mfu)
    # a program without the counters, or no trace: nothing, never a 0
    bare = {**run, "after": before, "trace": {}}
    for name in PER_LAYER[13:20]:
        assert layer_metrics.read(name, bare) is None, name


def _check_the_block(bench, root):
    """What this PR owns of the manifest, and nothing about what follows it."""
    accepted_before = rules.load_accepted(root)["per_layer"]
    block = rules.check_owned_block(bench, root, CELL, PER_LAYER, accepted_before)
    assert len(block) == 21
    assert [m["moves"] for m in block] == ["queries_per_s"] * 20 + ["setup_s"]
    assert rules.reports(bench, "end_to_end", CELL) == ["setup_s", "queries_per_s"]
    cell = rules.entry(bench, "workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "simprod_batch_closed_loop", 1
    )
    assert rules.reference_module(bench, root, CONFIG) == "reference_simprod"
    assert rules.traffic_body(bench, root, cell["traffic"])["runner"] == "serve_simprod"


def test_new_cell_and_its_entries_come_after_the_accepted(manifest):
    _check_the_block(*manifest)


def _an_entry_moved_away(bench):
    names = [m["name"] for m in bench["per_layer"]]
    bench["per_layer"].insert(10, bench["per_layer"].pop(names.index(PER_LAYER[3])))


def _an_entry_read_in_another_cell_too(bench):
    rules.entry(bench, "per_layer", "simprod.serve_ms")["workloads"].append("serve-pool-batch")


def _the_cell_before_an_accepted_one(bench):
    cells = [w["name"] for w in bench["workloads"]]
    bench["workloads"].insert(1, bench["workloads"].pop(cells.index(CELL)))


def _the_cell_gone(bench):
    bench["workloads"] = [w for w in bench["workloads"] if w["name"] != CELL]


@pytest.mark.parametrize("fault,message", [
    (_an_entry_moved_away, "stand together and in their order"),
    (_an_entry_read_in_another_cell_too, "'simprod.serve_ms' is read in 'serve-simprod-batch' alone"),
    (_the_cell_before_an_accepted_one, "'serve-simprod-batch' comes after the cells accepted before it"),
    (_the_cell_gone, "no cell 'serve-simprod-batch' in workloads"),
], ids=lambda v: getattr(v, "__name__", "message"))
def test_each_fault_in_the_block_fails_on_its_own_assertion(manifest, fault, message):
    bench, root = manifest
    fault(bench)
    with pytest.raises(AssertionError, match=re.escape(message)):
        _check_the_block(bench, root)


def _rehearse(seed=11, seconds=1.5, trace=0, control=""):
    bench, cell, config, traffic = chip_run.load_cell(CELL, True)
    from runners import serve_simprod

    args = argparse.Namespace(seed=seed, seconds=seconds, trace=trace, control=control)
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    return serve_simprod.run(cell, bench, config, traffic, args, time.monotonic(), device)


def test_sound_rehearsal_is_correct_and_crosses_both_batchers(servers_built):
    result = _rehearse(seed=2**31 + 4321, trace=1, control="filter_after_top")
    assert result["correct"] is True, result["compared"]
    assert servers_built == [(2, True)]
    assert result["failed"] == 0 and result["attempted"] > 500
    for name in ("rule_violations", "short_answers", "bad_answers", "evictions"):
        assert result["compared"][name][0] == 0, name
    assert result["sampled"]["queries"] == 96
    value = lambda name: result["metrics"]["simprod." + name]["value"]  # noqa: E731
    for name in PER_LAYER[:4] + PER_LAYER[7:8] + PER_LAYER[9:11] + PER_LAYER[12:19]:
        assert name in result["metrics"], name
    assert value("compiles_in_window") == 0
    assert 95 < value("launch_calls_share") < 105
    assert 1.9 < value("launches_per_post") < 2.1
    assert 3.5 < value("items_per_query") < 5.5
    assert 40 < value("filtered_share") < 60
    assert 5 < value("like_short_share") < 95
    assert 0 <= value("both_lists_share") < 50
    # no device plane on the CPU: a share of a peak is left out, not 0
    assert "simprod.masked_topk_roofline" not in result["metrics"]
    assert "simprod.serve_mfu" not in result["metrics"]
    assert result["control"]["short_answers"] > 0
    assert "predict.prep" in result["breakdown"]["stage_ms"] if "breakdown" in result else True
    assert list(result)[-1] == "compared"


@pytest.mark.parametrize("fault", ["lists_never_reach_the_device", "raw_scores_summed"])
def test_broken_served_path_is_not_correct(monkeypatch, fault):
    """The rest of a run over a template whose own-items, black and white
    lists never reach the device, or whose Serving sums the lists' raw
    scores (the parent's combine): `correct` comes out false."""
    from predictionio_tpu.models import similarproduct
    from predictionio_tpu.ops import similarity

    if fault == "raw_scores_summed":
        monkeypatch.setattr(
            similarproduct, "_standardized", lambda scores: list(scores)
        )
        result = _rehearse()
        assert result["correct"] is False
        assert result["compared"]["score_rms"][0] > result["compared"]["score_rms"][1]
        return
    pack = similarity.pack_lists
    monkeypatch.setattr(
        similarity, "pack_lists", lambda lists: pack([x[:0] for x in lists])
    )
    result = _rehearse()
    assert result["correct"] is False
    assert result["compared"]["rule_violations"][0] > 0


def test_a_program_without_the_launch_fails_at_once(monkeypatch):
    """Over the parent's program (one predict a query, no launch of its
    own) the runner ends the run before it builds anything: the driver's
    try of the new cell on the parent fails cleanly."""
    from predictionio_tpu.core.controller import Algorithm
    from predictionio_tpu.models.similarproduct import SimilarALSAlgorithm

    monkeypatch.setattr(
        SimilarALSAlgorithm, "batch_predict_launch", Algorithm.batch_predict_launch
    )
    t0 = time.monotonic()
    with pytest.raises(SystemExit, match="no launch of its own"):
        _rehearse()
    assert time.monotonic() - t0 < 5.0
