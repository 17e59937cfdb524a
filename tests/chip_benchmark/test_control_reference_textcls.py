"""The text-classification cell's reference, its three controls, its
seeded model and titles, its roofline arithmetic and readers, the two
cells' blocks of the manifest (the text cell's and the burst cell's), and
rehearsals of both cells at their tiny sizes on the CPU: sound, and with
the served path broken underneath."""

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
import loadgen  # noqa: E402
import loadgen_textcls  # noqa: E402
import manifest_rules as rules  # noqa: E402
import modelstore_textcls  # noqa: E402
import reference_textcls  # noqa: E402
import roofline  # noqa: E402
import roofline_textcls  # noqa: E402
import run as chip_run  # noqa: E402

BENCH = rules.load_bench(ROOT)
CONFIG = "textcls-rakuten-sigir18"
CELL = "serve-textcls-batch"
BURST = "serve-pool-single-burst"
#: the per-layer entries each cell reads alone, by name and in their order
PER_LAYER = ["textcls." + base for base in (
    "http_request_ms", "queue_wait_ms", "batch_occupancy", "device_dispatch_ms",
    "device_idle_share", "launch_idle_share", "launch_cpu_ms", "respond_ms",
    "hbm_peak_gib", "host_cpu_share", "compiles_in_window", "prep_ms",
    "tokens_per_query", "pad_share", "score_device_ms", "score_roofline",
)]
BURST_LAYER = ["burst." + base for base in (
    "shed_share", "queue_wait_ms", "query_p99_ms", "generator_late_ms",
    "window_waited_share", "device_idle_share",
)]
#: a small model for the CPU: 40 classes (padded to 128 lanes), 8,192 buckets
N_FEATURES, N_CLASSES, NUM = 8192, 40, 5


def _cfg():
    return rules.config_body(BENCH, ROOT, CONFIG)


def _titles(seed, n=128, vocabulary=3000):
    words = loadgen_textcls.vocabulary(seed, vocabulary)
    cdf = np.cumsum(loadgen.zipf_weights(len(words), 1.0))
    rng = np.random.default_rng([seed, 5])
    return loadgen_textcls.draw_titles(rng, words, cdf, n, _cfg()["title_tokens"])[0]


def _program_answers(seed, titles, num=NUM):
    """What the template answers over the seeded model: its own launch
    and collect, parsed as the runner parses a served answer."""
    from predictionio_tpu.models.textclassification import TextNBAlgorithm

    model = modelstore_textcls.build_model(seed, N_FEATURES, N_CLASSES)
    answers = TextNBAlgorithm().batch_predict(
        model, [{"text": t, "num": num} for t in titles]
    )
    return [reference_textcls.parse_answer(a, N_CLASSES) for a in answers]


def _dense_default_on_the_cpu(seed, titles, width):
    """The dense control as the TPU computes it: XLA's default precision
    there rounds both operands of the product to bfloat16 and accumulates
    in f32. The CPU backend computes f32 products at that setting, so the
    rounding is written out here; on the chip the runner calls the
    program's own `log_scores`."""
    import ml_dtypes

    from predictionio_tpu.models.textclassification import hash_counts, tokenize

    pi, theta_t = (np.asarray(a) for a in modelstore_textcls.device_model(seed, width, N_CLASSES))
    x = np.stack([hash_counts(tokenize(t), width) for t in titles])

    def bf16(a):
        return a.astype(ml_dtypes.bfloat16).astype(np.float32)

    scores = (bf16(x) @ bf16(theta_t[:, :N_CLASSES]) + pi[:N_CLASSES]).astype(np.float64)
    return reference_textcls.answers_of(scores, NUM)


def _numbers(seed, titles, answers, width=N_FEATURES):
    comparison = reference_textcls.Comparison(NUM, _cfg()["tie_tolerance"])
    comparison.add(
        reference_textcls.reference_scores(seed, titles, width, N_CLASSES), answers
    )
    return {**comparison.numbers(), "unanswered": 0.0, "failed": 0.0}


@pytest.mark.parametrize("control", reference_textcls.CONTROLS)
@pytest.mark.parametrize("seed", [1, 2**31 + 3, 2**33 + 5])
def test_control_comes_out_not_correct(control, seed):
    """The template's own answers pass the configuration's limits; each
    control in its place fails them: the bfloat16 table by its scores,
    the 32-bit hash by its labels, the dense product at the TPU's
    default precision by its scores."""
    cfg = _cfg()
    titles = _titles(seed)
    correct, compared = reference_textcls.judge(
        _numbers(seed, titles, _program_answers(seed, titles)), cfg["limits"]
    )
    assert correct, compared
    if control == "dense_default":
        answers = _dense_default_on_the_cpu(seed, titles, N_FEATURES)
    else:
        answers = reference_textcls.control_answers(
            seed, titles, N_FEATURES, N_CLASSES, NUM, control
        )
    correct, compared = reference_textcls.judge(
        _numbers(seed, titles, answers), cfg["limits"]
    )
    assert not correct
    failed = {k for k, (v, lim) in compared.items() if v > lim}
    assert ("label_mismatch" if control == "hash_fault" else "score_abs_max") in failed
    assert cfg["control"]["precision"] == "bf16"
    assert cfg["control"]["others"] == ["hash_fault", "dense_default"]


def test_comparison_counts_each_kind_of_wrong_answer():
    seed = 9
    titles = _titles(seed, 64)
    answers = _program_answers(seed, titles)
    scores = reference_textcls.reference_scores(seed, titles, N_FEATURES, N_CLASSES)
    tol = _cfg()["tie_tolerance"]

    def numbers(altered):
        comparison = reference_textcls.Comparison(NUM, tol)
        comparison.add(scores, altered)
        return comparison.numbers()

    assert numbers(answers)["score_abs_max"] < 1e-4
    swapped = list(answers)
    classes, served = swapped[3]
    swapped[3] = (classes[[1, 0, 2, 3, 4]], served)  # the best two swapped
    got = numbers(swapped)
    gap = scores[3, classes[0]] - scores[3, classes[1]]
    assert got["label_mismatch"] == (1.0 if gap > tol else 0.0)
    assert got["score_abs_max"] == pytest.approx(gap, abs=1e-4)
    short = list(answers)
    short[5] = (short[5][0][:3], short[5][1][:3])
    short[6] = None
    got = numbers(short)
    assert (got["short_answers"], got["bad_answers"]) == (1.0, 1.0)
    # the reference's own answers within the tolerance are no mismatch
    got = numbers(reference_textcls.answers_of(scores, NUM))
    assert got["label_mismatch"] == got["rank_mismatch"] == got["score_abs_max"] == 0.0
    assert numbers([None] * 64)["score_abs_max"] == 1e30


def test_an_answer_is_a_label_and_ordered_known_classes():
    good = {"label": "c0002", "scores": {"c0002": -10.0, "c0007": -11.0, "c0001": -11.0}}
    classes, scores = reference_textcls.parse_answer(good, N_CLASSES)
    assert classes.tolist() == [2, 7, 1] and scores.tolist() == [-10.0, -11.0, -11.0]
    for bad in (
        {**good, "label": "c0007"},                          # label not first
        {"label": "c0002", "scores": {"c0002": -12.0, "c0007": -11.0}},  # rising
        {"label": "c0002", "scores": {"c0002": float("nan")}},
        {"label": "c0099", "scores": {"c0099": -1.0}},      # unknown class
        {"label": "x2", "scores": {"x2": -1.0}},
        {"label": "c0002", "scores": {}},
        {"label": "c0002"}, None, [],
    ):
        assert reference_textcls.parse_answer(bad, N_CLASSES) is None, bad


def test_seeded_table_is_the_references_and_padded_to_the_lanes():
    seed = 2**31 + 77
    pi, theta_t = (np.asarray(a) for a in modelstore_textcls.device_model(seed, N_FEATURES, N_CLASSES))
    lanes = roofline_textcls.tiled_classes(N_CLASSES)
    assert theta_t.shape == (N_FEATURES, lanes) and pi.shape == (lanes,)
    rows = np.array([0, 1, 4097, N_FEATURES - 1])
    np.testing.assert_allclose(
        theta_t[rows, :N_CLASSES],
        reference_textcls.theta_rows(seed, rows, N_FEATURES, N_CLASSES), atol=5e-6,
    )
    np.testing.assert_allclose(
        pi[:N_CLASSES], reference_textcls.prior(seed, N_FEATURES, N_CLASSES), atol=5e-6
    )
    assert np.all(theta_t[:, N_CLASSES:] == 0.0) and np.all(pi[N_CLASSES:] == -np.inf)
    z = reference_textcls.normals(seed, np.arange(500), 400)
    assert abs(z.mean()) < 0.01 and abs(z.std() - 1.0) < 0.01
    # another seed, another table
    other = reference_textcls.theta_rows(seed + 1, rows, N_FEATURES, N_CLASSES)
    assert np.abs(other - theta_t[rows, :N_CLASSES]).max() > 1.0


def test_titles_tokenize_to_the_drawn_words():
    cfg = _cfg()
    words = loadgen_textcls.vocabulary(3, 20000)
    assert len(set(words)) == 20000
    assert all(re.fullmatch(r"[a-z0-9']+", w) for w in words)
    assert 0.02 < np.mean([bool(re.search(r"\d", w)) for w in words]) < 0.1
    cdf = np.cumsum(loadgen.zipf_weights(len(words), 1.0))
    titles, drawn = loadgen_textcls.draw_titles(
        np.random.default_rng(4), words, cdf, 2000, cfg["title_tokens"]
    )
    assert [reference_textcls.tokens(t) for t in titles] == drawn
    lengths = np.array([len(d) for d in drawn])
    assert lengths.min() >= 1 and lengths.max() <= 40
    assert 9 <= np.median(lengths) <= 11
    assert any(t != t.lower() for t in titles) and any("," in t for t in titles)
    # the same seed spells the same vocabulary, another seed another
    assert loadgen_textcls.vocabulary(3, 20000) == words
    assert loadgen_textcls.vocabulary(4, 20000)[:100] != words[:100]


def test_the_configuration_states_its_widths_and_bytes():
    cfg = _cfg()
    assert (cfg["n_classes"], cfg["n_features"]) == (3008, 2**20)
    assert cfg["resident_table_bytes"] == 3008 * 2**20 * 4 == 12616466432
    assert cfg["published"] == {
        "n_classes": 3008, "n_train_titles": 800000, "n_test_titles": 200000,
    }
    assert rules.entry(BENCH, "configs", CONFIG)["reduced"] == []
    assert roofline_textcls.tiled_classes(3008) == 3072
    assert cfg["n_features"] % modelstore_textcls.ROW_BLOCK == 0


def test_roofline_textcls_arithmetic():
    got = roofline_textcls.score_bytes(64, 720, 1024, 3008)
    assert got == (720 + 1 + 64) * 3072 * 4 + 12 * 1024
    assert roofline_textcls.score_ops(720, 3008) == 2 * 720 * 3008
    peak = roofline.peaks("TPU v5 lite")
    least = roofline.roofline_seconds(
        roofline_textcls.score_ops(720, 3008), got, peak
    )
    assert least == pytest.approx(got / 819e9)


def _counter(value):
    return {"samples": [{"labels": {}, "value": value}]}


def hand_made_run():
    """A traced run of the cell as the runner gathers one: 100 batches of
    64 titles, 11 tokens and 10.5 distinct pairs a title, 1,024 slots a
    batch, and 2 ms of the scoring scope over 4 s."""
    before = {
        "pio_batch_occupancy": {"samples": [{"labels": {}, "count": 0, "sum": 0.0}]},
        "pio_textcls_tokens_total": _counter(0.0),
        "pio_textcls_pairs_total": _counter(0.0),
        "pio_textcls_pair_slots_total": _counter(0.0),
        "pio_stage_seconds": {"samples": [
            {"labels": {"stage": "predict.prep"}, "count": 0, "sum": 0.0},
        ]},
    }
    after = {
        "pio_batch_occupancy": {"samples": [{"labels": {}, "count": 100, "sum": 6400.0}]},
        "pio_textcls_tokens_total": _counter(70400.0),
        "pio_textcls_pairs_total": _counter(67200.0),
        "pio_textcls_pair_slots_total": _counter(102400.0),
        "pio_stage_seconds": {"samples": [
            {"labels": {"stage": "predict.prep"}, "count": 100, "sum": 0.15},
        ]},
    }
    return {
        "before": before, "after": after, "config": _cfg(),
        "traffic": {"route": "/batch/queries.json"}, "load": {},
        "peak": roofline.peaks("TPU v5 lite"), "traced_queries": 6400.0,
        "scope_s": 0.002,
        "trace": {
            "window_s": 4.0, "busy_s": 1.0, "module_s": 0.003,
            "module_runs": {"jit_sparse_log_scores": 100},
        },
    }


def test_textcls_readers_on_hand_made_runs():
    run = hand_made_run()
    assert layer_metrics.read("textcls.tokens_per_query", run) == pytest.approx(11.0)
    assert layer_metrics.read("textcls.pad_share", run) == pytest.approx(34.375)
    assert layer_metrics.read("textcls.prep_ms", run) == pytest.approx(1.5)
    assert layer_metrics.read("textcls.score_device_ms", run) == pytest.approx(0.02)
    least = roofline_textcls.score_bytes(64, 672, 1024, 3008) / 819e9
    assert layer_metrics.read("textcls.score_roofline", run) == pytest.approx(
        100 * least * 100 / 0.002
    )
    assert layer_metrics.read("textcls.batch_occupancy", run) == pytest.approx(64.0)
    assert layer_metrics.read("textcls.device_idle_share", run) == pytest.approx(75.0)
    # a program without the counters or the scope, or no trace: nothing
    bare = {**run, "after": run["before"], "trace": {}, "scope_s": 0.0}
    for name in ("tokens_per_query", "pad_share", "prep_ms", "score_device_ms",
                 "score_roofline"):
        assert layer_metrics.read("textcls." + name, bare) is None, name


def _check_the_blocks(bench, root):
    """What this PR owns of the manifest, and nothing about what follows
    it: the text cell's block, then the burst cell's."""
    before = rules.load_accepted(root)["per_layer"]
    block = rules.check_owned_block(bench, root, CELL, PER_LAYER, before)
    assert {m["moves"] for m in block} == {"queries_per_s"}
    assert rules.reports(bench, "end_to_end", CELL) == ["setup_s", "queries_per_s"]
    cell = rules.entry(bench, "workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "textcls_batch_closed_loop", 1
    )
    assert rules.reference_module(bench, root, CONFIG) == "reference_textcls"
    block = rules.check_owned_block(bench, root, BURST, BURST_LAYER, before + PER_LAYER)
    assert {m["moves"] for m in block} == {"query_p50_ms"}
    assert rules.reports(bench, "end_to_end", BURST) == ["setup_s", "query_p50_ms"]
    cell = rules.entry(bench, "workloads", BURST)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "rec-pool-kddcup11", "single_burst_open_loop", 1
    )


def test_the_two_cells_and_their_entries_come_after_the_accepted(manifest):
    _check_the_blocks(*manifest)


def test_the_burst_mix_is_the_single_mix_in_bursts():
    single = rules.traffic_body(BENCH, ROOT, "single_open_loop")
    burst = rules.traffic_body(BENCH, ROOT, "single_burst_open_loop")
    changed = {k for k in burst if burst[k] != single.get(k)}
    assert changed == {"rate", "burst", "clients", "why", "rehearse"}
    assert burst["rate"] == 250.0 and burst["clients"] == 128
    period, share = burst["burst"]["period_s"], burst["burst"]["on_share"]
    assert burst["rate"] / share == pytest.approx(400.0) and period * share == 1.25


def _rehearse(cell=CELL, seed=11, seconds=1.5, trace=0, control=""):
    bench, entry, config, traffic = chip_run.load_cell(cell, True)
    import importlib

    runner = importlib.import_module("runners." + traffic["runner"])
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=trace, control=control)
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    return runner.run(entry, bench, config, traffic, args, time.monotonic(), device)


def test_sound_rehearsal_is_correct_and_reports_the_text_path(servers_built):
    result = _rehearse(seed=2**31 + 4321, trace=1, control="bf16,hash_fault")
    assert result["correct"] is True, result["compared"]
    assert servers_built == [(2, True)]
    assert result["failed"] == 0 and result["attempted"] > 500
    assert result["sampled"]["queries"] == 128
    for name in ("label_mismatch", "rank_mismatch", "short_answers", "bad_answers"):
        assert result["compared"][name][0] == 0, name
    for name in ("textcls.prep_ms", "textcls.tokens_per_query", "textcls.pad_share",
                 "textcls.queue_wait_ms", "textcls.batch_occupancy",
                 "textcls.compiles_in_window", "textcls.http_request_ms"):
        assert name in result["metrics"], name
    assert result["metrics"]["textcls.compiles_in_window"]["value"] == 0
    assert 8 < result["metrics"]["textcls.tokens_per_query"]["value"] < 15
    # no device plane on the CPU: the scope's time and roofline are left out
    assert "textcls.score_roofline" not in result["metrics"]
    assert "textcls.score_device_ms" not in result["metrics"]
    config = rules.config_body(BENCH, ROOT, CONFIG)
    for control in ("bf16", "hash_fault"):
        assert not reference_textcls.judge(
            {**result["control"][control], "unanswered": 0.0, "failed": 0.0},
            config["limits"],
        )[0], control
    assert list(result)[-1] == "compared"


@pytest.mark.parametrize("fault", ["one_answer_altered", "hash_at_32_bits"])
def test_broken_served_path_is_not_correct(monkeypatch, fault):
    """The rest of a run over a template that swaps the best two classes
    of one answer a batch, or hashes tokens with the 32-bit FNV-1a:
    `correct` comes out false."""
    from predictionio_tpu.models import textclassification as text

    if fault == "hash_at_32_bits":
        def fnv32(tokens, n_features, seed=0):
            return np.array(
                [reference_textcls.fnv1a(t, reference_textcls.FNV32) % n_features
                 for t in tokens], np.int64,
            )

        monkeypatch.setattr(text, "hash_buckets", fnv32)
    else:
        collect = text.TextNBAlgorithm.batch_predict_collect

        def altered(self, model, handle, queries):
            out = collect(self, model, handle, queries)
            scores = list(out[0]["scores"].items())
            scores[0], scores[1] = scores[1], scores[0]
            out[0] = {"label": scores[0][0], "scores": dict(scores)}
            return out

        monkeypatch.setattr(text.TextNBAlgorithm, "batch_predict_collect", altered)
    result = _rehearse()
    assert result["correct"] is False
    failed = {k for k, (v, lim) in result["compared"].items() if v > lim}
    assert failed & {"label_mismatch", "bad_answers", "score_abs_max"}


def test_a_program_without_the_sparse_step_fails_at_once(monkeypatch):
    """Over a program without the sparse step (the template's dense
    form) the runner ends the run before it builds anything: a try of
    the cell on such a checkout fails cleanly, and soon."""
    from predictionio_tpu.ops import naive_bayes

    monkeypatch.delattr(naive_bayes, "sparse_log_scores")
    t0 = time.monotonic()
    with pytest.raises(SystemExit, match="no sparse naive-Bayes step"):
        _rehearse()
    assert time.monotonic() - t0 < 5.0


def test_an_unknown_control_is_refused():
    with pytest.raises(SystemExit, match="no control 'fp8'"):
        _rehearse(control="fp8")


def test_sound_burst_rehearsal_is_correct_and_reports_the_bursts(servers_built):
    result = _rehearse(BURST, seed=2**31 + 99, seconds=2.0, trace=1)
    assert result["correct"] is True, result["compared"]
    assert servers_built == [(2, True)]
    assert result["failed"] == 0 and result["attempted"] > 40
    for name in ("burst.shed_share", "burst.queue_wait_ms", "burst.query_p99_ms",
                 "burst.generator_late_ms", "burst.window_waited_share"):
        assert name in result["metrics"], name
    assert not any(name.startswith("single.") for name in result["metrics"])
