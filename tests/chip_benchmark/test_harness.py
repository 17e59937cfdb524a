"""The chip benchmark's harness, on the CPU at tiny size: the manifest and
its data files (the rules are `manifest_rules`, functions of the manifest
and its checkout; every test of the manifest's shape takes the ``manifest``
fixture and runs over the repository and over a copy grown by `made_up`'s
addition, which is also rehearsed from there), the generator's draws
against the digests of the commit before its optional parameters came, the
arithmetic of the yardstick, the trace reduction on small recorded traces,
the command's contract, the control, and a run with the timed path broken
underneath."""

from __future__ import annotations

import argparse
import ast
import hashlib
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
import made_up  # noqa: E402
import manifest_rules as rules  # noqa: E402
import reference  # noqa: E402
import roofline  # noqa: E402
import run as chip_run  # noqa: E402
import trace_reduce  # noqa: E402

NAME, UNIT = rules.NAME, rules.UNIT
#: the manifest for what reads values (limits, widths, the rehearsals); a
#: test of its shape takes the `manifest` fixture, or `_over` for its entries
BENCH = rules.load_bench(ROOT)
BOTH = made_up.manifests(ROOT)
CONFIGS = [c["name"] for c in BENCH["configs"]]
ACCEPTED = rules.load_accepted(ROOT)
MADE_UP = made_up.names()


def _over(*kinds):
    return pytest.mark.parametrize(
        "manifest,entry", made_up.over(BOTH, *kinds), indirect=["manifest"]
    )


def _config(name):
    return rules.config_body(BENCH, ROOT, name)


# -- the manifest and its data files ----------------------------------------


def test_manifest_keys_and_limits(manifest):
    bench, _root = manifest
    assert set(bench) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert 1 <= bench["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(bench["workloads"]) // 4)
    assert len(json.dumps(bench)) <= 64 * 1024
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])


@_over("configs", "workloads", "end_to_end", "per_layer")
def test_names_units_and_lines(manifest, entry):
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


@_over("workloads")
def test_cell_finds_its_files_and_metrics(manifest, entry):
    bench, root = manifest
    found_bench, found, config, traffic = chip_run.load_cell(entry["name"], False, root)
    assert (found_bench, found) == (bench, entry)
    assert config == rules.config_body(bench, root, entry["config"])
    assert traffic == rules.traffic_body(bench, root, entry["traffic"])
    rules.check_cell(bench, root, entry)


def test_runner_declares_what_it_reads_of_a_configuration():
    from runners import serve_http

    assert rules.runner_config_keys(BENCH, ROOT, "serve_http") == serve_http.CONFIG_KEYS
    # what the runner and the readers it feeds take from the configuration
    read = set()
    for path in ("runners/serve_http.py", "layer_metrics.py"):
        with open(os.path.join(CHIP, path)) as f:
            read |= set(re.findall(r'(?:config|cfg|"config"\])\["(\w+)"\]', f.read()))
    assert read - set(rules.CONFIG_KEYS) == set(serve_http.CONFIG_KEYS)


@_over("per_layer")
def test_per_layer_metric_has_reader_and_target(manifest, entry):
    rules.check_per_layer_metric(*manifest, entry)


@_over("configs")
def test_config_entry(manifest, entry):
    rules.check_config_entry(*manifest, entry)
    if entry["name"] in rules.KDDCUP_CONFIGS:
        rules.check_accepted_config(*manifest, entry)


def test_accepted_configurations_and_cells_are_all_there(manifest):
    bench, root = manifest
    accepted = rules.load_accepted(root)
    rules.check_accepted_prefix(bench, root, accepted)
    assert tuple(accepted["configs"][:2]) == rules.KDDCUP_CONFIGS
    assert tuple(accepted["workloads"][:3]) == rules.KDDCUP_CELLS
    assert [len(accepted[kind]) for kind in rules.LISTS] == [3, 4, 71]


@_over("configs")
def test_every_control_is_held_to_the_limits_by_some_test(manifest, entry):
    rules.check_control_is_tested(*manifest, entry)


def test_files_under_paths_have_plain_names(manifest):
    bench, root = manifest
    for path in bench["paths"]:
        for folder, dirs, files in os.walk(os.path.join(root, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", name), name
    for word in bench["command"]:
        assert not word.startswith("/") and ".." not in word


#: the lists of the manifest a PR appends to, and the one its cells join
_LIST_KEYS = (*rules.LISTS, "end_to_end")


def _reads_of_the_module_s_manifest(path):
    """``(function, key)`` for every ``<NAME>["<list>"]`` inside a function of
    the test file at ``path``, ``<NAME>`` any name the module binds at its
    top level to what `load_bench` returns (`BENCH`, by convention)."""
    with open(path) as f:
        tree = ast.parse(f.read())
    module_names = {
        target.id for node in tree.body
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call)
        and getattr(node.value.func, "attr", getattr(node.value.func, "id", "")) == "load_bench"
        for target in node.targets if isinstance(target, ast.Name)
    }
    found = []
    for func in ast.walk(tree):
        if not isinstance(func, (ast.FunctionDef, ast.Lambda)):
            continue
        for node in ast.walk(func):
            if (
                isinstance(node, ast.Subscript)
                and isinstance(node.value, ast.Name) and node.value.id in module_names
                and isinstance(node.slice, ast.Constant) and node.slice.value in _LIST_KEYS
            ):
                found.append((getattr(func, "name", "lambda"), node.slice.value))
    return found


def test_no_test_reads_the_manifest_s_lists_but_through_the_fixture(manifest, tmp_path):
    """A test file under `tests/chip_benchmark`, a later PR's too, reads the
    lists of a manifest it loads at module level only while it is imported (to
    parametrize, through `made_up.over`): inside a function their shape
    comes from the ``manifest`` fixture, which also hands it the grown
    copy. So a pin on the end of a list cannot pass unseen."""
    _bench, root = manifest
    folder = os.path.join(root, rules.TESTS)
    files = sorted(f for f in os.listdir(folder) if f.startswith("test_") and f.endswith(".py"))
    assert len(files) >= 3
    for name in files:
        assert _reads_of_the_module_s_manifest(os.path.join(folder, name)) == [], name
    planted = tmp_path / "test_planted.py"
    planted.write_text(
        "MANIFEST = rules.load_bench('.')\nCELLS = MANIFEST['workloads']\n"
        "def test_tail(bench):\n    assert bench['configs'] and MANIFEST['per_layer'][50:] == []\n"
    )
    assert _reads_of_the_module_s_manifest(str(planted)) == [("test_tail", "per_layer")]


# -- what a later PR may add as files, shown on a made-up addition ------------


def _all_rules(bench, root, accepted):
    """Every rule of the manifest over every entry of it."""
    for entry in bench["configs"]:
        rules.check_config_entry(bench, root, entry)
        rules.check_control_is_tested(bench, root, entry)
        if entry["name"] in rules.KDDCUP_CONFIGS:
            rules.check_accepted_config(bench, root, entry)
    for cell in bench["workloads"]:
        rules.check_cell(bench, root, cell)
    for metric in bench["per_layer"]:
        rules.check_per_layer_metric(bench, root, metric)
    rules.check_accepted_prefix(bench, root, accepted)


def _write(root, path, body):
    with open(os.path.join(root, path), "w") as f:
        json.dump(body, f)


ML20M = MADE_UP["config_file"]


def _made_up_addition(tmp_path):
    """A copy of the benchmark with everything a `model_config` PR may
    bring as files and entries (`made_up.addition`), for one test to
    break."""
    return made_up.grown_copy(ROOT, str(tmp_path))


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


def _put_the_new_cell_first(bench, root):
    bench["workloads"].insert(0, bench["workloads"].pop())


def _put_the_new_configuration_second(bench, root):
    bench["configs"].insert(1, bench["configs"].pop())


def _name_an_entry_twice(bench, root):
    bench["per_layer"].append(dict(bench["per_layer"][-1]))


def _restate_a_default_that_is_not_deploy_s(bench, root):
    body = rules.config_body(bench, root, "rec-pool-ml20m")
    _write(root, ML20M, {**body, "server": {**body["server"], "max_wait_ms": 5.0}})


def _name_a_runner_that_is_not_there(bench, root):
    body = rules.traffic_body(bench, root, MADE_UP["traffic"])
    path = os.path.join(os.path.dirname(bench["command"][1]), "traffic", MADE_UP["traffic"] + ".json")
    _write(root, path, {**body, "runner": "serve_elsewhere"})


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
    (_insert_before_the_accepted, "the accepted per_layer names come first, in their order"),
    (_put_the_new_cell_first, "the accepted workloads names come first, in their order"),
    (_put_the_new_configuration_second, "the accepted configs names come first, in their order"),
    (_name_an_entry_twice, "a name stands twice in per_layer"),
    (_restate_a_default_that_is_not_deploy_s, "server setting 'max_wait_ms' differs from deploy's default and is not under assumed"),
    (_name_a_runner_that_is_not_there, "no runner 'serve_elsewhere' under runners/"),
    (_name_a_reference_that_is_not_there, "names a reference 'reference_sequences' that is not there"),
    (_bring_a_reference_with_no_test, "no test_control_reference_sequences.py holds"),
    (_leave_out_what_the_runner_reads, "runner 'serve_ml20m' reads 'table_format'"),
], ids=lambda v: getattr(v, "__name__", None) or ("sound" if v is None else "message"))
def test_made_up_addition_meets_the_rules_and_each_fault_its_own(tmp_path, fault, message):
    """A configuration of other widths on another server setting with a
    reference of its own, a cell, a traffic file, a runner and three
    per-layer entries land as files and entries with no test edited; each
    planted fault fails on the assertion meant for it."""
    bench, root = _made_up_addition(tmp_path)
    if fault is None:
        _all_rules(bench, root, ACCEPTED)
        # and the harness itself finds the cell's files there
        _b, cell, config, traffic = chip_run.load_cell(MADE_UP["cell"], True, root)
        assert (config["rank"], config["server"]) == (10, {"max_batch": 256})
        assert config["n_users"] == 3000 and traffic["runner"] == MADE_UP["runner"]
        assert traffic["burst"] == {"period_s": 0.5, "on_share": 0.5}
        assert traffic["user_zipf_exponent"] == 1.0
        return
    fault(bench, root)
    with pytest.raises(AssertionError, match=re.escape(message)):
        _all_rules(bench, root, ACCEPTED)


def _block_check_with_a_tail_pin(bench, block, after):
    """A block check as PR 28 wrote its own: whatever follows the accepted
    names is the block."""
    listed = [m["name"] for m in bench["per_layer"]]
    assert listed[:len(after)] == after
    assert listed[len(after):] == block, (
        "the block is held to be the end of the list: the next PR's entry fails it"
    )


def test_a_block_check_that_pins_the_tail_fails_on_the_grown_copy(manifest):
    """The fault that PR 28's test carried for four PRs, planted: written
    over the repository's last three entries it passes on the repository,
    as it did in the PR that wrote it, and fails on the grown copy with a
    message of its own; that the repository's names are a prefix holds on
    both."""
    bench, root = manifest
    tree = [m["name"] for m in rules.load_bench(ROOT)["per_layer"]]
    block, after = tree[-3:], tree[:-3]
    if root == ROOT:
        _block_check_with_a_tail_pin(bench, block, after)
    else:
        with pytest.raises(AssertionError, match="held to be the end of the list"):
            _block_check_with_a_tail_pin(bench, block, after)
    listed = [m["name"] for m in bench["per_layer"]]
    assert listed[:len(tree)] == tree


def _from_the_copy(root, *command):
    """A command run from the copy at ``root`` as the driver runs the
    benchmark from a checkout; the program itself comes from the
    repository, which the copy does not hold."""
    env = {**os.environ, "PYTHONPATH": ROOT}
    return subprocess.run(
        [sys.executable, *command], capture_output=True, text=True,
        timeout=300, cwd=root, env=env,
    )


def test_the_grown_copy_s_cell_rehearses_through_the_command(grown_root):
    """The made-up cell runs from the copy through `run.py` itself, which
    finds its configuration, its traffic file, the runner that file names
    and the readers of its entries there, `.py` and `.json`: `correct`
    true, the three appended names in the line, and every request of three
    bursts attempted. Its reference's own control test runs there too."""
    done = _from_the_copy(
        grown_root, os.path.join("benchmarks", "chip", "run.py"), "--workload",
        MADE_UP["cell"], "--seed", str(2**31 + 999), "--seconds", "1.5",
        "--trace", "1", "--rehearse-cpu",
    )
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == set(MADE_UP["per_layer"])
    assert line["metrics"][MADE_UP["per_layer"][2]]["value"] >= 1.0
    # 50/s in periods of 0.5 s: 25 a burst, three bursts in the window
    assert (line["attempted"], line["failed"]) == (75, 0)
    done = _from_the_copy(
        grown_root, "-m", "pytest", "-q", "-p", "no:cacheprovider",
        os.path.join(rules.TESTS, f"test_control_{MADE_UP['reference']}.py"),
    )
    assert done.returncode == 0 and "1 passed" in done.stdout, done.stdout[-2000:]


def test_deploy_s_defaults_are_the_server_s_own():
    """`DEPLOY_DEFAULTS` against `EngineServer.__init__`'s signature, read
    from its text; the two keys the configurations no longer restate are
    what they stated."""
    found = rules.signature_defaults(
        os.path.join(ROOT, "predictionio_tpu", "serving", "engine_server.py"),
        "EngineServer",
    )
    assert {k: found[k] for k in rules.DEPLOY_DEFAULTS} == rules.DEPLOY_DEFAULTS
    assert (found["pipeline_depth"], found["adaptive_wait"]) == (2, True)
    for config in CONFIGS:
        assert not {"pipeline_depth", "adaptive_wait"} & set(_config(config)["server"])


def _re_source_the_widths(body):
    return {**body, "n_users": 480189, "published": {**body["published"], "n_users": 480189}}


def _halve_the_pool(body):
    return {**body, "resident_table_bytes": int(0.2 * 2**34)}


def _tune_the_server(body):
    return {
        **body, "server": {**body["server"], "max_batch": 256},
        "assumed": {**body["assumed"], "server.max_batch": 256},
    }


@pytest.mark.parametrize("config", rules.KDDCUP_CONFIGS)
@pytest.mark.parametrize("alter,message", [
    (_re_source_the_widths, "the widths of KDD Cup 2011 Track 1, uncut"),
    (_halve_the_pool, "a quarter of 16 GiB resident"),
    (_tune_the_server, "pio-tpu deploy's defaults"),
], ids=lambda v: getattr(v, "__name__", "message"))
def test_accepted_configuration_stays_pinned(tmp_path, config, alter, message):
    """An altered copy of an accepted configuration that meets the general
    rule (it says what it changed) still fails the pin of its name."""
    bench, root = made_up.copy_of_the_benchmark(ROOT, str(tmp_path))
    entry = rules.entry(bench, "configs", config)
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


#: SHA-256 of what the generator drew at commit a7d4146 (PR 31), before its
#: optional parameters came: `arrival_schedule` at the single cell's
#: parameters (350/s, 53 s, 24 tenants, exponent 1.0); the first 2,000
#: requests (tenant, body) of the open loop's process 0 of 2 and the 400
#: requests it kept; the first 20 posts of a closed-loop client before the
#: window opens and inside it (where the kept sample draws from the same
#: generator). Taken by running the parent's loops over the fake socket below.
PARENT_DIGESTS = {
    11: {
        "schedule": "1c51f0d70718f0ff22c23e7eacb31acf30d000fb291b58d11b18a781966faddb",
        "open": "12b3ecbba6e936071993688ff2d01c1d682e66855e6f3cfbd4158a9be1fd07c6",
        "open_kept": "75447b64922d4d322ef3938170c2f5ac2fd929fb28caee52359be0ec449b86a1",
        "closed_settle": "73ac38be5e22a299642257601fe0b3d19533e0ea59b6e564879b314363f62fd3",
        "closed_window": "b1a5bbf7a76d9355fb4e6db5c3ab732951c3692c785dcff0c4b3d40c441dbade",
    },
    2147483725: {
        "schedule": "3ebd16d773e960e9540e8bb9b8c011eca47834956d608aa8194a4b58219fb52b",
        "open": "ac058fe05ba149effce0173aacc19e5c497fa3bf3a782bb9c779366e9bd4d922",
        "open_kept": "64e256df44687d743dc855b7c8a1de6ee5f0d606d6cc2dedf0812e8054dd6ad6",
        "closed_settle": "9c711a5066e2c013df880e63ab0bac58cdafa37e7f7cb68598c6140d6335fa84",
        "closed_window": "9f5d0b6084dc22e303e34628abc47cc8d6b24a5200a3ad2c8e48faaaac53ebd6",
    },
}
_TENANT_NAMES = [f"tenant{t}" for t in range(24)]
_PLAN = {
    "host": "h", "port": 1, "tenants": _TENANT_NAMES, "zipf_exponent": 1.0,
    "n_users": 1000990, "num": 10, "proc": 0,
}
_OPEN_PLAN = {
    **_PLAN, "loop": "open", "t_start": 0.0, "t_window": 8.0, "t_end": 53.0,
    "rate": 350.0, "procs": 2, "clients": 1, "keep": 400,
}


def _sha(parts):
    digest = hashlib.sha256()
    for part in parts:
        digest.update(part)
    return digest.hexdigest()


def _sent_lines(sent):
    return [str(t).encode() + b" " + body + b"\n" for t, body in sent]


def _drive(monkeypatch, loop, plan, limit):
    """``(the first limit (tenant, body) sent, the loop's result)`` of a
    generator loop over a socket that answers at once, on a clock that
    stands at 0 until ``limit`` requests are out and is past every end
    after."""
    sent = []

    class Clock:
        def monotonic(self):
            return 0.0 if len(sent) < limit else 1e9

        def sleep(self, seconds):
            pass

    class Connection:
        def __init__(self, *args, **kwargs):
            pass

        def close(self):
            pass

    def post(conn, path, body):
        if len(sent) < limit:
            sent.append((_TENANT_NAMES.index(path.split("accessKey=")[1]), body))
        return 200, b"[]" if loop == "closed" else b'{"itemScores": []}'

    monkeypatch.setattr(loadgen, "time", Clock())
    monkeypatch.setattr(loadgen, "_post", post)
    monkeypatch.setattr(loadgen.http.client, "HTTPConnection", Connection)
    run = {"closed": loadgen.run_closed, "open": loadgen.run_open}[loop]
    return sent, run(plan)


@pytest.mark.parametrize("seed", sorted(PARENT_DIGESTS))
def test_without_its_optional_parameters_the_generator_draws_what_it_drew(monkeypatch, seed):
    """Bit for bit: the schedule, and what both loops send and keep."""
    want = PARENT_DIGESTS[seed]
    offsets, who = loadgen.arrival_schedule(seed, 350.0, 53.0, 24, 1.0)
    assert len(offsets) == 18550
    assert _sha([offsets.astype("<f8").tobytes(), who.astype("<i8").tobytes()]) == want["schedule"]
    sent, out = _drive(monkeypatch, "open", {**_OPEN_PLAN, "seed": seed}, 2000)
    assert _sha(_sent_lines(sent)) == want["open"]
    assert (len(out["requests"]), len(out["kept"])) == (9275, 400)
    assert _sha([json.dumps([k[0], k[1]]).encode() for k in out["kept"]]) == want["open_kept"]
    for name, t_window in (("closed_settle", 1e18), ("closed_window", 0.0)):
        plan = {
            **_PLAN, "loop": "closed", "seed": seed, "t_window": t_window,
            "t_end": 1.0, "clients": 1, "batch": 64, "keep": 4,
        }
        sent, _out = _drive(monkeypatch, "closed", plan, 20)
        assert len(sent) == 20 and _sha(_sent_lines(sent)) == want[name], name


@pytest.mark.parametrize("seed", sorted(PARENT_DIGESTS))
def test_the_loops_send_what_the_pure_draws_give(seed):
    """`draw_open` and `draw_post` are the loops' draws, so the digests hold
    for them too, and a test of a parameter needs no socket."""
    plan = {**_OPEN_PLAN, "seed": seed}
    offsets, tenants, users, keep = loadgen.draw_open(plan)
    lines = [
        (int(t), json.dumps({"user": f"u{u}", "num": 10}).encode())
        for t, u in zip(tenants[:2000], users[:2000])
    ]
    assert _sha(_sent_lines(lines)) == PARENT_DIGESTS[seed]["open"]
    assert len(keep) == 400 and min(offsets[k] for k in keep) >= 8.0
    rng = np.random.default_rng([seed, 0, 0])
    weights = loadgen.zipf_weights(24, 1.0)
    posts = []
    for _ in range(20):
        tenant, drawn = loadgen.draw_post(rng, {**_PLAN, "batch": 64}, weights)
        posts.append((tenant, json.dumps([{"user": f"u{u}", "num": 10} for u in drawn]).encode()))
    assert _sha(_sent_lines(posts)) == PARENT_DIGESTS[seed]["closed_settle"]
    # absent keys are absent from the plan; a present one goes through
    from runners import serve_http

    assert serve_http.generator_parameters({"loop": "open"}) == {}
    named = {"loop": "open", "burst": {"period_s": 1.0, "on_share": 0.25}, "user_zipf_exponent": 1.0}
    assert serve_http.generator_parameters(named) == {k: named[k] for k in loadgen.OPTIONAL_PARAMETERS}


def test_bursts_hold_each_period_s_arrivals_in_its_first_share():
    burst = {"period_s": 2.0, "on_share": 0.25}
    a, ta = loadgen.arrival_schedule(2**31 + 7, 350.0, 10.0, 24, 1.0, burst=burst)
    c, tc = loadgen.arrival_schedule(2**31 + 8, 350.0, 10.0, 24, 1.0, burst=burst)
    assert len(a) == len(c) == 3500 and np.all(np.diff(a) > 0)
    assert not np.array_equal(a[:50], c[:50])
    # no arrival outside the first quarter of a period; every period of
    # every seed holds 700, so the mean rate over a period is the rate
    for offsets in (a, c):
        assert np.all(offsets % 2.0 < 0.5)
        assert np.bincount((offsets // 2.0).astype(int)).tolist() == [700] * 5
    assert len(a) / 10.0 == 350.0
    # inside a burst the rate is rate / on_share
    inside = np.diff(a[:700])
    assert inside.mean() == pytest.approx(0.25 / 350.0, rel=0.01)
    # the same gaps and the same share of each tenant as a plain block of
    # that length, scaled, in another order
    plain, tp = loadgen.arrival_schedule(2**31 + 7, 350.0, 10.0, 24, 1.0, block_s=2.0)
    assert np.allclose(np.sort(np.diff(a[:700])), 0.25 * np.sort(np.diff(plain[:700])))
    assert np.array_equal(np.bincount(ta[:700], minlength=24), np.bincount(tp[:700], minlength=24))
    assert np.array_equal(np.bincount(ta[:700], minlength=24), np.bincount(tc[1400:2100], minlength=24))
    # a whole share is the plain schedule of that block length, bit for bit
    whole, tw = loadgen.arrival_schedule(2**31 + 7, 350.0, 10.0, 24, 1.0, burst={"period_s": 2.0, "on_share": 1.0})
    assert np.array_equal(whole, plain) and np.array_equal(tw, tp)
    for bad in ({"period_s": 1.0, "on_share": 0.0}, {"period_s": 1.0, "on_share": 1.5}, {"period_s": 0.0, "on_share": 0.5}):
        with pytest.raises(ValueError, match="burst wants"):
            loadgen.arrival_schedule(1, 350.0, 10.0, 24, 1.0, burst=bad)


def test_a_closed_loop_that_names_a_burst_is_refused_not_ignored():
    from runners import serve_http

    traffic = {**rules.traffic_body(BENCH, ROOT, "batch_closed_loop"), "burst": {"period_s": 1.0, "on_share": 0.5}}
    with pytest.raises(SystemExit, match="'burst' on a closed loop"):
        serve_http.generator_parameters(traffic)
    with pytest.raises(SystemExit, match="'burst' on a closed loop"):
        serve_http.run({}, {}, {}, traffic, None, 0.0, {})


def test_zipf_users_are_hot_in_each_tenant_s_own_order():
    n_users, seed = 100_000, 2**31 + 5
    plan = {"seed": seed, "n_users": n_users, "tenants": ["a", "b", "c"], "user_zipf_exponent": 1.0}
    assert loadgen.user_skew({**plan, "user_zipf_exponent": None}) is None
    assert loadgen.user_skew({k: v for k, v in plan.items() if k != "user_zipf_exponent"}) is None
    skew = loadgen.user_skew(plan)
    # a tenant's order is a permutation of its users
    step, start = loadgen.user_order(seed, 0, n_users)
    order = (start + step * np.arange(n_users)) % n_users
    assert np.array_equal(np.sort(order), np.arange(n_users))
    assert loadgen.user_order(seed, 0, n_users) == (step, start)
    small = loadgen.user_order(3, 1, 7)
    assert sorted((small[1] + small[0] * r) % 7 for r in range(7)) == list(range(7))
    # the top 1% of the order draw the share the weights give them
    rng = np.random.default_rng(1)
    users = loadgen.zipf_users(rng, skew, np.zeros(200_000, int))
    weights = loadgen.zipf_weights(n_users, 1.0)
    hot = np.isin(users, order[: n_users // 100])
    assert hot.mean() == pytest.approx(weights[: n_users // 100].sum(), abs=0.005)
    assert np.mean(users == order[0]) == pytest.approx(weights[0], abs=0.003)
    # the tenants' hottest users differ, and a tenant's hot users are no
    # run of low row numbers
    hottest = [loadgen.user_order(seed, t, n_users)[1] for t in range(3)]
    assert len(set(hottest)) == 3 and max(hottest) > 1000
    assert np.min(np.abs(np.diff(order[:100]))) > 1 and order[:100].max() > n_users // 2
    mixed = loadgen.zipf_users(np.random.default_rng(2), skew, np.array([0, 1, 2] * 20_000))
    for t in range(3):
        values, counts = np.unique(mixed[t::3], return_counts=True)
        assert values[np.argmax(counts)] == hottest[t]
    # both loops: the closed loop's post is of one tenant's order, the open
    # loop's users of each request's own tenant; the kept entries carry them
    tenant, drawn = loadgen.draw_post(
        np.random.default_rng(3), {**plan, "batch": 4096}, loadgen.zipf_weights(3, 1.0), skew
    )
    assert np.mean(np.array(drawn) == hottest[tenant]) == pytest.approx(weights[0], abs=0.02)
    open_plan = {
        **plan, "t_start": 0.0, "t_window": 1.0, "t_end": 21.0, "rate": 1000.0,
        "zipf_exponent": 1.0, "proc": 0, "procs": 1, "keep": 50,
    }
    _offsets, tenants, users, keep = loadgen.draw_open(open_plan)
    assert len(users) == 21_000 and len(keep) == 50
    for t in range(3):
        assert np.mean(users[tenants == t] == hottest[t]) == pytest.approx(weights[0], abs=0.02)
    uniform = loadgen.draw_open({k: v for k, v in open_plan.items() if k != "user_zipf_exponent"})[2]
    assert np.mean(uniform == hottest[0]) < 0.001


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
def test_broken_timed_path_is_not_correct(monkeypatch, servers_built, cell, fault):
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
    # the server the cell builds is the one it built when its file restated
    # the two defaults
    assert servers_built == [(2, True)]
    if fault == "half_left_out":
        assert result["compared"]["bad_answers"][0] > 0
    else:
        assert result["compared"]["rank_gap_rms"][0] > result["compared"]["rank_gap_rms"][1]


def test_sound_rehearsal_is_correct_and_evicts_nothing(servers_built):
    result = _rehearse("serve-pool-single", seed=2**31 + 12345, trace=1)
    assert result["correct"] is True, result["compared"]
    assert servers_built == [(2, True)]
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
