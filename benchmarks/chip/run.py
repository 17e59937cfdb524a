"""One run of one cell of the chip benchmark.

    python benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Finds the cell in BENCHMARK.json, its configuration under configs/, its
traffic mix under traffic/ and the runner the mix names under runners/,
sets up, measures for --seconds, and prints the result as the last line of
standard output. Without a chip it exits non-zero and prints no number;
``--rehearse-cpu`` runs the configuration's tiny size on the CPU, for the
sandbox only, and the line names the device it ran on.
"""

from __future__ import annotations

import time

_T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def load_cell(workload: str, rehearse: bool, root: str = ROOT):
    """(bench, cell, config, traffic) of a workload, by name, in the
    checkout at ``root``."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    with open(os.path.join(root, files[cell["config"]])) as f:
        config = json.load(f)
    here = os.path.join(root, os.path.relpath(HERE, ROOT))
    with open(os.path.join(here, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    if rehearse:
        config = {**config, **config["rehearse"]}
        traffic = {**traffic, **traffic["rehearse"]}
    return bench, cell, config, traffic


def find_device(chips: int, rehearse: bool) -> dict:
    """The device as JAX reports it; no accelerator, or fewer chips than
    the cell asks for, ends the run with no number."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform == "cpu" and not rehearse:
        raise SystemExit("no accelerator: JAX found only the CPU")
    if platform != "cpu" and len(devices) < chips:
        raise SystemExit(f"the cell asks for {chips} chip(s), JAX found {len(devices)}")
    return {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices) if platform != "cpu" else 1,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument(
        "--control", default="", metavar="PRECISION",
        help="also read the control: the reference at this precision "
        "(bf16, fp8, int8, int4) in the program's place; not part of a "
        "benchmark run",
    )
    ap.add_argument(
        "--set", action="append", default=[], metavar="KEY=VALUE",
        help="trial value of a traffic parameter, for sweeps by hand",
    )
    args = ap.parse_args(argv)
    sys.path[:0] = [HERE, ROOT]
    bench, cell, config, traffic = load_cell(args.workload, args.rehearse_cpu)
    for item in args.set:
        key, _, value = item.partition("=")
        traffic[key] = json.loads(value)
    device = find_device(cell["chips"], args.rehearse_cpu)
    runner = importlib.import_module("runners." + traffic["runner"])
    result = runner.run(
        cell, bench, config, traffic, args, _T_PROCESS_START, device
    )
    for name, (value, limit) in result["compared"].items():
        print(f"compared {name} {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
