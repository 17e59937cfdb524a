"""The complementary-purchase cell's reference, its three controls, its
seeded table and carts, its roofline arithmetic and readers, the cell's
block of the manifest, and rehearsals of the cell at its tiny size on the
CPU: sound, and with the served path broken underneath."""

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
import loadgen  # noqa: E402
import loadgen_cpurchase  # noqa: E402
import manifest_rules as rules  # noqa: E402
import modelstore_cpurchase  # noqa: E402
import reference_cpurchase  # noqa: E402
import roofline  # noqa: E402
import roofline_cpurchase  # noqa: E402
import run as chip_run  # noqa: E402

BENCH = rules.load_bench(ROOT)
CONFIG = "cpurchase-instacart-2017"
CELL = "serve-cpurchase-batch"
#: the per-layer entries the cell reads alone, by name and in their order
PER_LAYER = ["cpurchase." + base for base in (
    "http_request_ms", "queue_wait_ms", "batch_occupancy", "device_dispatch_ms",
    "device_idle_share", "launch_idle_share", "hbm_peak_gib", "host_cpu_share",
    "compiles_in_window", "prep_ms", "items_per_query", "pad_share",
    "score_device_ms", "score_roofline",
)]
#: a small catalog for the CPU, its counts scaled as the configuration's are
SMALL = {
    "n_items": 2000, "n_baskets": 200_000, "n_order_rows": 1_000_000,
    "item_zipf_exponent": 1.0,
}
NUM = 10


def _cfg():
    return rules.config_body(BENCH, ROOT, CONFIG)


def _carts(seed, n=128, n_items=SMALL["n_items"]):
    cdf = np.cumsum(loadgen.zipf_weights(n_items, 1.0))
    rng = np.random.default_rng([seed, 5])
    return loadgen_cpurchase.draw_carts(rng, cdf, n, _cfg()["cart_items"])


def _model(seed, small=SMALL):
    return modelstore_cpurchase.build_model(
        seed, small["n_items"], small["n_order_rows"], small["n_baskets"],
        small["item_zipf_exponent"],
    )


def _program_answers(seed, carts, metric="lift"):
    """What the template answers over the seeded model, staged as deploy
    stages it: its own launch and collect, parsed as the runner parses a
    served answer."""
    from predictionio_tpu.models.complementarypurchase import CPAlgoParams, CPAlgorithm
    from predictionio_tpu.parallel.mesh import ComputeContext

    cfg = _cfg()
    algorithm = CPAlgorithm(CPAlgoParams(metric=metric, min_support=cfg["min_support"]))
    model = algorithm.stage_model(ComputeContext.create(batch="cp-control"), _model(seed))
    answers = algorithm.batch_predict(model, [
        {"items": [loadgen_cpurchase.item_name(i) for i in cart], "num": NUM}
        for cart in carts
    ])
    return [reference_cpurchase.parse_answer(a, SMALL["n_items"]) for a in answers]


def _numbers(seed, carts, answers=None, control=None, metric="lift"):
    """The comparison's numbers of ``answers`` (or the control's) against
    the reference over the small catalog."""
    cfg = _cfg()
    table = reference_cpurchase.SeededTable(seed, SMALL)
    scores, own = reference_cpurchase.reference_answers(
        table, carts, NUM, metric, cfg["min_support"], control
    )
    comparison = reference_cpurchase.Comparison(NUM, cfg["tie_tolerance"])
    comparison.add(scores, carts, own if answers is None else answers)
    return {**comparison.numbers(), "unanswered": 0.0, "failed": 0.0}


@pytest.mark.parametrize("control", reference_cpurchase.CONTROLS)
@pytest.mark.parametrize("seed", [1, 2**31 + 3, 2**33 + 5])
def test_control_comes_out_not_correct(control, seed):
    """The template's own answers pass the configuration's limits; each
    control in its place fails them: the bfloat16 rules by their scores,
    the truncated merge and the missing support floor by their items and
    their scores."""
    cfg = _cfg()
    carts = _carts(seed)
    correct, compared = reference_cpurchase.judge(
        _numbers(seed, carts, _program_answers(seed, carts)), cfg["limits"]
    )
    assert correct, compared
    correct, compared = reference_cpurchase.judge(
        _numbers(seed, carts, control=control), cfg["limits"]
    )
    assert not correct
    failed = {k for k, (v, lim) in compared.items() if v > lim}
    assert "score_rel_max" in failed
    if control != "bf16":
        assert {"item_mismatch", "rank_mismatch"} <= failed
    assert cfg["control"]["precision"] == "bf16"
    assert cfg["control"]["others"] == ["truncated", "no_support_floor"]


def test_confidence_answers_pass_the_limits_too():
    seed = 2**31 + 11
    carts = _carts(seed, 64)
    correct, compared = reference_cpurchase.judge(
        _numbers(seed, carts, _program_answers(seed, carts, "confidence"),
                 metric="confidence"),
        _cfg()["limits"],
    )
    assert correct, compared


def test_comparison_counts_each_kind_of_wrong_answer():
    seed = 9
    carts = _carts(seed, 64)
    answers = _program_answers(seed, carts)

    def numbers(altered):
        return _numbers(seed, carts, altered)

    assert numbers(answers)["score_rel_max"] < 1e-5
    swapped = list(answers)
    items, served = swapped[3]
    swapped[3] = (items[[1, 0, *range(2, len(items))]], served)
    got = numbers(swapped)
    assert got["rank_mismatch"] >= 1 and got["score_rel_max"] > 1e-5
    planted = list(answers)
    items, served = planted[4]
    planted[4] = (np.r_[carts[4][0], items[1:]], served)  # a cart item first
    got = numbers(planted)
    assert got["cart_item_returned"] == 1 and got["item_mismatch"] >= 1
    short = list(answers)
    short[5] = (short[5][0][:3], short[5][1][:3])
    short[6] = None
    got = numbers(short)
    assert (got["short_answers"], got["bad_answers"]) == (1.0, 1.0)
    assert numbers([None] * 64)["score_rel_max"] == 1e30


def test_an_answer_is_a_list_of_distinct_known_items():
    good = {"itemScores": [{"item": "p00007", "score": 3.0}, {"item": "p00002", "score": 3.0}]}
    items, scores = reference_cpurchase.parse_answer(good, 100)
    assert items.tolist() == [7, 2] and scores.tolist() == [3.0, 3.0]
    empty = reference_cpurchase.parse_answer({"itemScores": []}, 100)
    assert len(empty[0]) == 0
    for bad in (
        {"itemScores": [{"item": "p00001", "score": 1.0}, {"item": "p00002", "score": 2.0}]},
        {"itemScores": [{"item": "p00001", "score": 1.0}, {"item": "p00001", "score": 1.0}]},
        {"itemScores": [{"item": "p00001", "score": 0.0}]},
        {"itemScores": [{"item": "p00001", "score": float("nan")}]},
        {"itemScores": [{"item": "p00100", "score": 1.0}]},
        {"itemScores": [{"item": "x1", "score": 1.0}]},
        {"itemScores": [{"item": "p00001"}]},
        {}, None, [],
    ):
        assert reference_cpurchase.parse_answer(bad, 100) is None, bad


def test_seeded_table_is_the_references():
    """The table the device draws holds the reference's integers, in the
    template's layout; it is symmetric and no entry passes its row's
    diagonal."""
    from predictionio_tpu.ops import cooccurrence

    seed = 2**31 + 77
    table = np.asarray(_model(seed).table)
    n = SMALL["n_items"]
    assert table.shape == (1, n, cooccurrence.layout(n)[1])
    counts = table[0, :, :n]
    rows = np.array([0, 1, 2, 999, n - 1])
    reference = reference_cpurchase.SeededTable(seed, SMALL)
    np.testing.assert_array_equal(counts[rows], reference.rows(rows))
    assert np.all(table[0, :, n:] == 0.0)
    np.testing.assert_array_equal(counts, counts.T)
    assert np.all(counts == np.rint(counts))
    assert np.all(counts <= np.diag(counts)[:, None])
    np.testing.assert_array_equal(np.diag(counts), reference.diag)
    assert 0.001 < np.mean(counts >= 2) < 0.2
    # another seed, another table
    other = reference_cpurchase.SeededTable(seed + 1, SMALL).rows(rows)
    assert np.abs(other - counts[rows]).max() > 100


def test_seeded_table_fills_every_panel(monkeypatch):
    """Past one panel's width the draw writes every panel, and each holds
    the reference's integers."""
    from predictionio_tpu.ops import cooccurrence

    monkeypatch.setattr(cooccurrence, "PANEL_LANES", 256)
    small = {**SMALL, "n_items": 700}
    seed = 4
    table = np.asarray(_model(seed, small).table)
    assert table.shape == (3, 700, 256)
    dense = table.transpose(1, 0, 2).reshape(700, 768)[:, :700]
    rows = np.array([0, 255, 256, 699])
    np.testing.assert_array_equal(
        dense[rows], reference_cpurchase.SeededTable(seed, small).rows(rows)
    )
    assert np.all(table[2, :, 700 - 512:] == 0.0)


def test_carts_are_distinct_popular_and_sized():
    cfg = _cfg()
    carts = _carts(3, 2000, cfg["n_items"])
    sizes = np.array([len(c) for c in carts])
    assert sizes.min() >= 1 and sizes.max() <= 64
    assert 7.5 <= np.median(sizes) <= 8.5 and 9 < sizes.mean() < 10.5
    assert all(len(set(c)) == len(c) for c in carts)
    items = np.concatenate(carts)
    assert items.min() >= 0 and items.max() < cfg["n_items"]
    assert np.mean(items < 1000) > 0.5  # drawn by popularity
    assert _carts(3, 50) == _carts(3, 50) and _carts(4, 50) != _carts(3, 50)


def test_the_configuration_states_its_widths_and_bytes():
    from predictionio_tpu.ops import cooccurrence

    cfg = _cfg()
    assert (cfg["n_items"], cfg["n_baskets"], cfg["n_order_rows"]) == (49688, 3214874, 32434489)
    assert cfg["published"] == {
        "n_items": 49688, "n_baskets": 3214874, "n_order_rows": 32434489,
        "n_users": 206209,
    }
    n_panels, width = cooccurrence.layout(49688)
    assert (n_panels, width) == (2, 24960)
    assert roofline_cpurchase.padded_items(49688) == n_panels * width
    assert cfg["resident_table_bytes"] == 49688 * 49920 * 4 == 9921699840
    assert rules.entry(BENCH, "configs", CONFIG)["reduced"] == ["cart_items"]
    counts = reference_cpurchase.popularity(49688, 32434489, 1.0)
    assert counts.max() <= cfg["n_baskets"] < 2**24 and counts.min() >= 1
    assert cfg["jit_names"] == ["basket_rule_scores"]
    assert cfg["metric"] in cooccurrence.METRICS


def test_roofline_cpurchase_arithmetic():
    got = roofline_cpurchase.score_bytes(64, 630, 49688)
    assert got == (630 + 64) * 49920 * 4 + 8 * 630
    assert roofline_cpurchase.score_ops(630, 49688) == 2 * 630 * 49920
    assert roofline_cpurchase.padded_items(300) == 384
    peak = roofline.peaks("TPU v5 lite")
    least = roofline.roofline_seconds(roofline_cpurchase.score_ops(630, 49688), got, peak)
    assert least == pytest.approx(got / 819e9)


def _counter(value):
    return {"samples": [{"labels": {}, "value": value}]}


def hand_made_run():
    """A traced run of the cell as the runner gathers one: 100 batches of
    64 carts, 9.9 distinct items a cart, 1,024 slots a batch, and 30 ms
    of the scoring scope over 4 s."""
    before = {
        "pio_batch_occupancy": {"samples": [{"labels": {}, "count": 0, "sum": 0.0}]},
        "pio_cpurchase_items_total": _counter(0.0),
        "pio_cpurchase_item_slots_total": _counter(0.0),
        "pio_cpurchase_unknown_items_total": _counter(0.0),
        "pio_stage_seconds": {"samples": [
            {"labels": {"stage": "predict.prep"}, "count": 0, "sum": 0.0},
        ]},
    }
    after = {
        "pio_batch_occupancy": {"samples": [{"labels": {}, "count": 100, "sum": 6400.0}]},
        "pio_cpurchase_items_total": _counter(63360.0),
        "pio_cpurchase_item_slots_total": _counter(102400.0),
        "pio_cpurchase_unknown_items_total": _counter(0.0),
        "pio_stage_seconds": {"samples": [
            {"labels": {"stage": "predict.prep"}, "count": 100, "sum": 0.08},
        ]},
    }
    return {
        "before": before, "after": after, "config": _cfg(),
        "traffic": {"route": "/batch/queries.json"}, "load": {},
        "peak": roofline.peaks("TPU v5 lite"), "traced_queries": 6400.0,
        "scope_s": 0.03,
        "trace": {
            "window_s": 4.0, "busy_s": 0.5, "module_s": 0.04,
            "module_runs": {"jit_basket_rule_scores": 100},
        },
    }


def test_cpurchase_readers_on_hand_made_runs():
    run = hand_made_run()
    assert layer_metrics.read("cpurchase.items_per_query", run) == pytest.approx(9.9)
    assert layer_metrics.read("cpurchase.pad_share", run) == pytest.approx(38.125)
    assert layer_metrics.read("cpurchase.prep_ms", run) == pytest.approx(0.8)
    assert layer_metrics.read("cpurchase.score_device_ms", run) == pytest.approx(0.3)
    least = roofline_cpurchase.score_bytes(64, 633.6, 49688) / 819e9
    assert layer_metrics.read("cpurchase.score_roofline", run) == pytest.approx(
        100 * least * 100 / 0.03
    )
    assert layer_metrics.read("cpurchase.batch_occupancy", run) == pytest.approx(64.0)
    assert layer_metrics.read("cpurchase.device_idle_share", run) == pytest.approx(87.5)
    # a program without the counters or the scope, or no trace: nothing
    bare = {**run, "after": run["before"], "trace": {}, "scope_s": 0.0}
    for name in ("items_per_query", "pad_share", "prep_ms", "score_device_ms",
                 "score_roofline"):
        assert layer_metrics.read("cpurchase." + name, bare) is None, name


def _check_the_block(bench, root):
    """What this PR owns of the manifest, and nothing about what follows
    it: the cell's block, behind the entries accepted before it."""
    before = rules.load_accepted(root)["per_layer"]
    text = [m["name"] for m in bench["per_layer"] if m["name"].startswith(("textcls.", "burst."))]
    block = rules.check_owned_block(bench, root, CELL, PER_LAYER, before + text)
    assert {m["moves"] for m in block} == {"queries_per_s"}
    assert rules.reports(bench, "end_to_end", CELL) == ["setup_s", "queries_per_s"]
    cell = rules.entry(bench, "workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "cpurchase_batch_closed_loop", 1
    )
    assert rules.reference_module(bench, root, CONFIG) == "reference_cpurchase"


def test_the_cell_and_its_entries_come_after_the_accepted(manifest):
    _check_the_block(*manifest)


def test_the_cart_mix_keeps_the_text_cells_loop():
    text = rules.traffic_body(BENCH, ROOT, "textcls_batch_closed_loop")
    carts = rules.traffic_body(BENCH, ROOT, "cpurchase_batch_closed_loop")
    changed = {k for k in carts if carts[k] != text.get(k)}
    assert changed == {"runner", "why"}


def _rehearse(seed=11, seconds=1.5, trace=0, control=""):
    bench, entry, config, traffic = chip_run.load_cell(CELL, True)
    import importlib

    runner = importlib.import_module("runners." + traffic["runner"])
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=trace, control=control)
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    return runner.run(entry, bench, config, traffic, args, time.monotonic(), device)


def test_sound_rehearsal_is_correct_and_reports_the_cart_path(servers_built):
    result = _rehearse(seed=2**31 + 4321, trace=1, control="bf16,truncated,no_support_floor")
    assert result["correct"] is True, result["compared"]
    assert servers_built == [(2, True)]
    assert result["failed"] == 0 and result["attempted"] > 500
    assert result["sampled"]["queries"] == 128
    for name in ("item_mismatch", "rank_mismatch", "cart_item_returned",
                 "short_answers", "bad_answers"):
        assert result["compared"][name][0] == 0, name
    for name in ("cpurchase.prep_ms", "cpurchase.items_per_query", "cpurchase.pad_share",
                 "cpurchase.queue_wait_ms", "cpurchase.batch_occupancy",
                 "cpurchase.compiles_in_window", "cpurchase.http_request_ms"):
        assert name in result["metrics"], name
    assert result["metrics"]["cpurchase.compiles_in_window"]["value"] == 0
    assert 8 < result["metrics"]["cpurchase.items_per_query"]["value"] < 12
    # no device plane on the CPU: the scope's time and roofline are left out
    assert "cpurchase.score_roofline" not in result["metrics"]
    assert "cpurchase.score_device_ms" not in result["metrics"]
    config = rules.config_body(BENCH, ROOT, CONFIG)
    for control in reference_cpurchase.CONTROLS:
        assert not reference_cpurchase.judge(
            {**result["control"][control], "unanswered": 0.0, "failed": 0.0},
            config["limits"],
        )[0], control
    assert list(result)[-1] == "compared"


@pytest.mark.parametrize("fault", ["one_answer_altered", "no_support_floor"])
def test_broken_served_path_is_not_correct(monkeypatch, fault):
    """The rest of a run over a template that swaps the best two items of
    one answer a batch, or serves every pair seen once: `correct` comes
    out false."""
    from predictionio_tpu.models import complementarypurchase as cp
    from predictionio_tpu.ops import cooccurrence

    if fault == "no_support_floor":
        step = cooccurrence.basket_rule_scores

        def floorless(table, stats, pairs, n_rows, k, metric="lift", min_support=2):
            return step(table, stats, pairs, n_rows, k, metric=metric, min_support=1)

        monkeypatch.setattr(cooccurrence, "basket_rule_scores", floorless)
    else:
        collect = cp.CPAlgorithm.batch_predict_collect

        def altered(self, model, handle, queries):
            out = collect(self, model, handle, queries)
            scores = out[0]["itemScores"]
            scores[0]["item"], scores[1]["item"] = scores[1]["item"], scores[0]["item"]
            return out

        monkeypatch.setattr(cp.CPAlgorithm, "batch_predict_collect", altered)
    result = _rehearse()
    assert result["correct"] is False
    failed = {k for k, (v, lim) in result["compared"].items() if v > lim}
    assert failed & {"item_mismatch", "rank_mismatch", "score_rel_max"}


def test_a_program_without_the_cart_step_fails_at_once(monkeypatch):
    """Over a program without the cart-scoring step (the template's
    per-item top-k lists) the runner ends the run before it builds
    anything: a try of the cell on such a checkout fails cleanly, and
    soon."""
    from predictionio_tpu.ops import cooccurrence

    monkeypatch.delattr(cooccurrence, "basket_rule_scores")
    t0 = time.monotonic()
    with pytest.raises(SystemExit, match="no cart-scoring step"):
        _rehearse()
    assert time.monotonic() - t0 < 5.0


def test_an_unknown_control_is_refused():
    with pytest.raises(SystemExit, match="no control 'fp8'"):
        _rehearse(control="fp8")
