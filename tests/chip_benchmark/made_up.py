"""A made-up addition to the benchmark: everything a `model_config` PR may
bring, as new files and appended entries, with no file that is there
edited. A configuration of another source's widths on another server
setting that names a reference of its own; that reference and its
`test_control_<module>.py`; one cell; a traffic file of its own that names
the generator's two optional parameters; a runner of its own; three
per-layer entries, two read by `.json` readers and one by a `.py` reader;
the cell's name appended to the `workloads` of the end-to-end metrics it
reports. Nothing of it is measured; the names are made up.

`grown_copy` writes the benchmark with the addition into a directory; the
``manifest`` fixture (`conftest.py`) hands every test of the manifest's
shape the repository and that copy in turn, and `over` parametrizes a test
over the entries of both.
"""

from __future__ import annotations

import copy
import json
import os
import shutil

import pytest

import manifest_rules as rules

N_USERS, N_ITEMS, RANK, TENANTS = 138493, 26744, 10, 400
END_TO_END = ("query_p50_ms",)


def names(tag: str = "ml20m") -> dict:
    """The addition's names, all made from ``tag``: another tag gives a
    second addition, to append to a checkout by hand and run the suite
    there, where the fixture then grows the copy by this one."""
    return {
        "config": f"rec-pool-{tag}", "cell": f"serve-pool-{tag}-bursts",
        "traffic": f"{tag}_bursty_open_loop", "runner": f"serve_{tag}",
        "reference": f"reference_{tag}",
        "config_file": f"benchmarks/chip/configs/rec-pool-{tag}.json",
        "per_layer": [
            f"{tag}.evictions_per_s", f"{tag}.encode_ms", f"{tag}.queries_per_batch"
        ],
    }


_RUNNER = '''"""Runner of the made-up cell: `serve_http`'s run, under a name of its own."""

from runners import serve_http

CONFIG_KEYS = {keys!r}


def run(cell, bench, config, traffic, args, t_process_start, device):
    return serve_http.run(cell, bench, config, traffic, args, t_process_start, device)
'''

_REFERENCE = '''"""The made-up configuration's reference: `reference.py`'s arithmetic, under
the name its configuration gives."""

from reference import *  # noqa: F401,F403
'''

_CONTROL_TEST = '''"""The made-up configuration's control against its limits."""

import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "chip"))

import {reference} as reference  # noqa: E402


def test_control_comes_out_not_correct():
    with open(os.path.join(ROOT, {config_file!r})) as f:
        cfg = json.load(f)
    rng = np.random.default_rng(1)
    users = (0.25 * rng.standard_normal((200, 32))).astype(np.float32)
    items = rng.standard_normal((2000, 32)).astype(np.float32)
    idx = np.arange(64)
    top_idx, top = reference.top_k(reference.reference_scores(users[idx], items), 10)
    exact = reference.Comparison(10)
    exact.add(users, items, idx, [(top_idx[q], top[q]) for q in range(64)])
    numbers = {{**exact.numbers(), "unanswered": 0.0, "evictions": 0.0}}
    assert reference.judge(numbers, cfg["limits"])[0]
    control = reference.Comparison(10)
    control.add(users, items, idx, reference.control_answers(
        users, items, idx, 10, cfg["control"]["precision"]
    ))
    numbers = {{**control.numbers(), "unanswered": 0.0, "evictions": 0.0}}
    assert not reference.judge(numbers, cfg["limits"])[0]
'''

_PY_READER = '''"""Queries a batch dispatched in the window."""
import layer_metrics


def read(run):
    batches = layer_metrics.delta(run, "pio_batches_total", {}, "value")
    if batches <= 0:
        return None
    return layer_metrics.delta(run, "pio_batch_occupancy", {}, "sum") / batches
'''


def addition(bench: dict, root: str, tag: str = "ml20m"):
    """``(grown, files)``: the manifest with the addition's entries
    appended, and ``{path under the checkout: text}`` of the files it
    brings. Reads the checkout at ``root``, writes nothing."""
    name = names(tag)
    grown = copy.deepcopy(bench)
    body = {
        **rules.config_body(bench, root, "rec-pool-kddcup11"),
        "source": "MovieLens 20M: 138,493 users x 26,744 movies (Harper and Konstan, ACM TiiS 5(4), 2015)",
        "deployment": "a pool of small tenants on one chip, batches of 256",
        "published": {"n_users": N_USERS, "n_items": N_ITEMS, "rank": RANK},
        "n_users": N_USERS, "n_items": N_ITEMS, "rank": RANK, "tenants": TENANTS,
        "server": {"max_batch": 256},
        "reference": name["reference"],
        "resident_table_bytes": TENANTS * (N_USERS + N_ITEMS) * RANK * 4,
        "floor_note": "400 tenants x 6.61 MB are 2.64 GB, 15.4% of 16 GiB: over the "
        "12.5% floor, which holds where the device is busy 75% of the traced window",
        "assumed": {"tenants": TENANTS, "server.max_batch": 256, "zipf_exponent": 1.0, "num": 10},
    }
    grown["configs"].append({
        "name": name["config"], "source": body["source"], "file": name["config_file"],
        "reduced": [], "why": "hundreds of small tenants",
    })
    grown["workloads"].append({
        "name": name["cell"], "config": name["config"], "traffic": name["traffic"],
        "chips": 1, "why": "open loop in bursts over 400 small tenants, users Zipf within a tenant",
    })
    for metric in END_TO_END:
        rules.entry(grown, "end_to_end", metric)["workloads"].append(name["cell"])
    open_loop = rules.traffic_body(bench, root, "single_open_loop")
    traffic = {
        **open_loop, "runner": name["runner"], "rate": 300.0,
        "user_zipf_exponent": 1.0, "burst": {"period_s": 1.0, "on_share": 0.25},
        "why": "end users who come in waves and come back: bursts of a quarter of each second, a tenant's users Zipf",
        "rehearse": {**open_loop["rehearse"], "burst": {"period_s": 0.5, "on_share": 0.5}},
    }
    chip = os.path.dirname(bench["command"][1])
    readers = {
        "evictions_per_s.json": json.dumps({"reader": "counter_share", "families": ["pio_pool_evictions_total"], "over": "pio_process_clock_seconds_total"}),
        "encode_ms.json": json.dumps({"reader": "histogram_mean", "families": ["pio_stage_seconds"], "labels": {"stage": "http.encode"}, "scale": 1000.0}),
        "queries_per_batch.py": _PY_READER,
    }
    for metric, unit in zip(name["per_layer"], ("%", "ms", "1")):
        grown["per_layer"].append({
            "name": metric, "unit": unit, "better": "lower", "source": "program_counter",
            "layer": "tenant pool", "moves": "query_p50_ms", "workloads": [name["cell"]],
        })
    files = {
        name["config_file"]: json.dumps(body),
        f"{chip}/traffic/{name['traffic']}.json": json.dumps(traffic),
        f"{chip}/runners/{name['runner']}.py": _RUNNER.format(
            keys=rules.runner_config_keys(bench, root, "serve_http")
        ),
        f"{chip}/{name['reference']}.py": _REFERENCE,
        f"{rules.TESTS}/test_control_{name['reference']}.py": _CONTROL_TEST.format(
            reference=name["reference"], config_file=name["config_file"]
        ),
        **{f"{chip}/metrics/{file}": text for file, text in readers.items()},
        "BENCHMARK.json": json.dumps(grown, indent=1),
    }
    return grown, files


def copy_of_the_benchmark(root: str, to: str):
    """``(bench, to)``: `BENCHMARK.json` and the directories of its `paths`
    copied from the checkout at ``root`` into the directory ``to``."""
    bench = rules.load_bench(root)
    shutil.copy(os.path.join(root, "BENCHMARK.json"), to)
    for path in bench["paths"]:
        shutil.copytree(
            os.path.join(root, path), os.path.join(to, path),
            ignore=shutil.ignore_patterns("__pycache__"),
        )
    return bench, to


def grown_copy(root: str, to: str, tag: str = "ml20m"):
    """``(grown, to)``: the copy with the addition written into it."""
    bench, to = copy_of_the_benchmark(root, to)
    grown, files = addition(bench, root, tag)
    for path, text in files.items():
        with open(os.path.join(to, path), "w") as f:
            f.write(text)
    return grown, to


def manifests(root: str) -> dict:
    """``{"tree": the manifest at root, "grown": it with the addition}``,
    for parametrizing over the entries of both before any copy is written."""
    bench = rules.load_bench(root)
    return {"tree": bench, "grown": addition(bench, root)[0]}


def over(both: dict, *kinds: str) -> list:
    """Parameters ``(manifest, entry)`` over the entries of ``kinds`` in the
    repository's manifest and in the grown one: for
    ``@pytest.mark.parametrize("manifest,entry", ..., indirect=["manifest"])``."""
    return [
        pytest.param(which, e, id=f"{which}-{e['name']}")
        for which, bench in both.items() for kind in kinds for e in bench[kind]
    ]
