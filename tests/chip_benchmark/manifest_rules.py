"""What the harness's tests ask of `BENCHMARK.json` and of the files it
names, as functions of ``(bench, root)``: the manifest as a dictionary and
the checkout it lies in. The tests run them over the repository and over a
copy grown by a made-up configuration, cell, traffic file, runner,
reference and metrics (`made_up.py`; the ``manifest`` fixture of
`conftest.py`), so that what a later PR may add as files is shown, not
promised, and a test that pins the end of a list fails in the PR that
writes it.

Each rule raises `AssertionError` with a message of its own. One rule for
the three lists a PR appends to (`check_accepted_prefix`): the names
accepted so far, `data/accepted.json`, are a prefix in their order, and
what follows is free. A PR's own test asserts on what that PR owns
(`check_owned_block`) and nothing about what follows it. The two KDD Cup
configurations stay pinned by name (`check_accepted_config`); every
configuration, those included, meets the general rule
(`check_config_entry`).
"""

from __future__ import annotations

import ast
import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TESTS = os.path.join("tests", "chip_benchmark")

#: one v5e chip's memory (`peaks.json`: 16 GiB), and the lower of the
#: driver's two floors for a new cell's size
CHIP_MEMORY_BYTES = 2**34
LOWER_FLOOR = 0.125
#: what `pio-tpu deploy` gives a server that is told nothing, for the keys a
#: configuration's `server` block states (a test holds them to
#: `EngineServer.__init__`'s own defaults)
DEPLOY_DEFAULTS = {
    "max_batch": 64, "max_wait_ms": 2.0, "admission": True, "warmup": True,
}
#: what every configuration's file has, whatever its runner reads
CONFIG_KEYS = (
    "source", "deployment", "published", "assumed", "server", "limits",
    "control", "rehearse", "resident_table_bytes", "floor_note",
)
#: the two configurations pinned to KDD Cup 2011 Track 1 (PR 24), and their cells
KDDCUP_CONFIGS = ("rec-pool-kddcup11", "rec-pool-kddcup11-int8")
KDDCUP_CELLS = ("serve-pool-batch", "serve-pool-single", "serve-pool-int8-batch")
#: the lists of the manifest that a PR appends to
LISTS = ("configs", "workloads", "per_layer")


def load_bench(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_accepted(root: str) -> dict:
    """``{list: names}`` of `data/accepted.json`: what the manifest held at
    the end of the last `benchmark` PR, in its order."""
    with open(os.path.join(root, TESTS, "data", "accepted.json")) as f:
        return json.load(f)


def entry(bench: dict, kind: str, name: str) -> dict:
    return next(e for e in bench[kind] if e["name"] == name)


def chip_dir(bench: dict, root: str) -> str:
    """The directory of the command's program, where the data files lie."""
    return os.path.join(root, os.path.dirname(bench["command"][1]))


def config_body(bench: dict, root: str, name: str) -> dict:
    with open(os.path.join(root, entry(bench, "configs", name)["file"])) as f:
        return json.load(f)


def traffic_body(bench: dict, root: str, name: str) -> dict:
    with open(os.path.join(chip_dir(bench, root), "traffic", name + ".json")) as f:
        return json.load(f)


def cells_of(bench: dict, config: str) -> list[dict]:
    return [w for w in bench["workloads"] if w["config"] == config]


def reports(bench: dict, kind: str, cell: str) -> list[str]:
    """The names of the metrics of ``kind`` that this cell reports."""
    return [m["name"] for m in bench[kind] if cell in m.get("workloads", [cell])]


def runner_config_keys(bench: dict, root: str, runner: str) -> tuple:
    """``CONFIG_KEYS`` of ``runners/<runner>.py``, read from its text: a
    runner declares what it reads of a configuration, and importing one to
    ask may start JAX."""
    path = os.path.join(chip_dir(bench, root), "runners", runner + ".py")
    assert os.path.exists(path), f"no runner {runner!r} under runners/"
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in tree.body:
        if isinstance(node, ast.Assign) and any(
            isinstance(t, ast.Name) and t.id == "CONFIG_KEYS" for t in node.targets
        ):
            return tuple(ast.literal_eval(node.value))
    raise AssertionError(f"runner {runner!r} declares no CONFIG_KEYS")


def signature_defaults(path: str, cls: str, func: str = "__init__") -> dict:
    """``{parameter: default}`` of ``cls.func`` in the file at ``path``, for
    the defaults that are literals; read from its text, as a runner's keys
    are, so that no JAX starts."""
    with open(path) as f:
        tree = ast.parse(f.read())
    owner = next(
        n for n in tree.body if isinstance(n, ast.ClassDef) and n.name == cls
    )
    args = next(
        n for n in owner.body if isinstance(n, ast.FunctionDef) and n.name == func
    ).args
    found = {}
    for arg, default in zip(args.args[len(args.args) - len(args.defaults):], args.defaults):
        try:
            found[arg.arg] = ast.literal_eval(default)
        except ValueError:
            pass
    return found


def check_config_entry(bench: dict, root: str, entry: dict) -> None:
    """The general rule of a configuration: its file says where its widths
    come from and repeats them, says what it assumed and what of the server
    it set, and keeps enough on the device to stand for a deployment."""
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert any(entry["file"].startswith(p + "/") for p in bench["paths"])
    cells = cells_of(bench, entry["name"])
    assert cells, "a configuration is used by some cell"
    body = config_body(bench, root, entry["name"])
    for key in CONFIG_KEYS:
        assert key in body, f"the configuration's file lacks {key!r}"
    assert body["limits"], "no limit to hold the answers to"
    assert set(body["control"]) >= {"precision", "why"}
    published = body["published"]
    assert isinstance(published, dict) and published, "no published width"
    for key, value in published.items():
        assert key in body, f"published width {key!r} is not in the file"
        assert body[key] == value or key in entry["reduced"], (
            f"width {key!r} differs from the published one and is not under reduced"
        )
    # the block says only what the deployment sets: a key that restates
    # deploy's default may stand, any other says so under `assumed` (a
    # deployment may BE a server setting; the query cache is a key deploy
    # leaves to the environment)
    for key, value in body["server"].items():
        default = key in DEPLOY_DEFAULTS and DEPLOY_DEFAULTS[key] == value
        assert default or "server." + key in body["assumed"], (
            f"server setting {key!r} differs from deploy's default and is not under assumed"
        )
    floor = LOWER_FLOOR * CHIP_MEMORY_BYTES * max(c["chips"] for c in cells)
    assert body["resident_table_bytes"] >= floor, (
        "resident_table_bytes is under 12.5% of the memory of the cell's chips"
    )
    note = body["floor_note"]
    assert isinstance(note, str) and re.search(r"\d", note), (
        "floor_note gives the arithmetic of the floor its cells meet"
    )


def check_accepted_config(bench: dict, root: str, entry: dict) -> None:
    """The pin of the two configurations accepted with PR 24: KDD Cup 2011
    Track 1's widths uncut, a quarter of 16 GiB resident, deploy's
    defaults. A new configuration is not held to it."""
    assert entry["name"] in KDDCUP_CONFIGS
    body = config_body(bench, root, entry["name"])
    assert (body["n_users"], body["n_items"], body["rank"]) == (1000990, 624961, 32), (
        "the widths of KDD Cup 2011 Track 1, uncut"
    )
    assert body["resident_table_bytes"] > 0.25 * CHIP_MEMORY_BYTES, (
        "a quarter of 16 GiB resident"
    )
    assert body["server"] == DEPLOY_DEFAULTS, "pio-tpu deploy's defaults"


def check_cell(bench: dict, root: str, cell: dict) -> None:
    """A cell finds its configuration, its traffic mix and the mix's
    runner; the configuration has what that runner reads; the cell reports
    the set-up time, another end-to-end metric and a per-layer one."""
    assert cell["chips"] in (1, 4)
    config = config_body(bench, root, cell["config"])
    traffic = traffic_body(bench, root, cell["traffic"])
    for key in CONFIG_KEYS:
        assert key in config, f"the configuration's file lacks {key!r}"
    for key in runner_config_keys(bench, root, traffic["runner"]):
        assert key in config, f"runner {traffic['runner']!r} reads {key!r}"
    end_to_end = reports(bench, "end_to_end", cell["name"])
    assert "setup_s" in end_to_end and len(end_to_end) >= 2
    assert reports(bench, "per_layer", cell["name"])


def check_per_layer_metric(bench: dict, root: str, metric: dict) -> None:
    """A per-layer entry has a reader's file, moves an end-to-end metric
    that each of its cells reports, and a share of a peak is in percent."""
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    cells = [w["name"] for w in bench["workloads"]]
    target = end_to_end[metric["moves"]]
    assert set(metric) <= {
        "name", "unit", "better", "source", "layer", "moves", "workloads"
    }
    for cell in metric["workloads"]:
        assert cell in cells
        assert cell in target.get("workloads", cells)
    base = metric["name"].split(".", 1)[-1]
    assert any(
        os.path.exists(os.path.join(chip_dir(bench, root), "metrics", stem + ext))
        for stem in (metric["name"], base) for ext in (".json", ".py")
    ), f"no reader for {metric['name']!r} under metrics/"
    if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


def check_accepted_prefix(bench: dict, root: str, accepted: dict) -> None:
    """`configs`, `workloads` and `per_layer` grow at their ends only: the
    accepted names of each, in their order, are its prefix; what follows is
    free."""
    for kind in LISTS:
        names = [e["name"] for e in bench[kind]]
        assert names[:len(accepted[kind])] == accepted[kind], (
            f"the accepted {kind} names come first, in their order"
        )
        assert len(set(names)) == len(names), f"a name stands twice in {kind}"
    setup = entry(bench, "per_layer", "setup_compile_s")
    assert setup["moves"] == "setup_s" and setup["unit"] == "s"
    cells = {w["name"] for w in bench["workloads"]}
    assert set(KDDCUP_CELLS) <= set(setup["workloads"]) <= cells


def check_owned_block(
    bench: dict, root: str, cell: str, names: list[str], after: list[str]
) -> list[dict]:
    """What one PR owns of the manifest, and nothing about what follows it:
    its per-layer entries ``names`` stand in `per_layer` together and in
    their order, behind the entries ``after`` that were accepted before
    them; each is read in ``cell`` alone; the cell stands in `workloads`
    behind the cells those earlier entries are read in, and meets
    `check_cell`. Returns the block's entries, for what else its PR pins
    (their `moves`, the cell's metrics, its reference). A PR's own test
    calls this for its own block and asserts nothing on the lists' ends."""
    listed = [m["name"] for m in bench["per_layer"]]
    assert names and names[0] in listed, f"{names[:1]} is not in per_layer"
    at = listed.index(names[0])
    assert listed[at:at + len(names)] == list(names), (
        f"the entries of {cell!r} stand together and in their order"
    )
    assert set(after) <= set(listed[:at]), (
        f"the entries of {cell!r} come after the ones accepted before them"
    )
    block = bench["per_layer"][at:at + len(names)]
    for metric in block:
        assert metric["workloads"] == [cell], (
            f"{metric['name']!r} is read in {cell!r} alone"
        )
    cells = [w["name"] for w in bench["workloads"]]
    assert cell in cells, f"no cell {cell!r} in workloads"
    earlier = {
        c for m in bench["per_layer"][:at] if m["name"] in after
        for c in m["workloads"]
    }
    assert all(cells.index(c) < cells.index(cell) for c in earlier), (
        f"{cell!r} comes after the cells accepted before it"
    )
    check_cell(bench, root, bench["workloads"][cells.index(cell)])
    return block


def reference_module(bench: dict, root: str, config: str) -> str:
    """The module under the benchmark's directory that judges this
    configuration's answers: `reference` unless its file names another."""
    return config_body(bench, root, config).get("reference", "reference")


def check_control_is_tested(bench: dict, root: str, entry: dict) -> None:
    """Every configuration's control is held against its limits by a
    test: `test_control_comes_out_not_correct` of `test_harness.py` for
    `reference.py`, and for a configuration that names another reference a
    test of that name in `test_control_<module>.py` beside it."""
    module = reference_module(bench, root, entry["name"])
    assert NAME.match(module) and os.path.exists(
        os.path.join(chip_dir(bench, root), module + ".py")
    ), f"the configuration names a reference {module!r} that is not there"
    test = "test_harness.py" if module == "reference" else f"test_control_{module}.py"
    path = os.path.join(root, TESTS, test)
    assert os.path.exists(path), f"no {test} holds {module!r}'s control to the limits"
    with open(path) as f:
        assert "def test_control_comes_out_not_correct(" in f.read(), (
            f"{test} has no test_control_comes_out_not_correct"
        )
