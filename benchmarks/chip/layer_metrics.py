"""Readers of the per-layer metrics. Each metric has a file of its own,
``metrics/<name>.json`` (a reader kind and its parameters) or
``metrics/<name>.py`` (a function ``read(run)``); a metric ``a.b`` with no
file of that name uses ``metrics/b.*``, so one reader serves the `single.`
and `batch.` prefixes. A reader that finds nothing to read returns None and
the metric is left out of the line.

``run`` is what a traced run gathered: ``before`` and ``after`` (the
program's registry as dictionaries at the window's two ends), ``trace``
(`trace_reduce.reduce`), ``load`` (the generator's own numbers),
``config``, ``traffic``, ``peak`` (the chip's peaks) and
``memory_peak_bytes``.
"""

from __future__ import annotations

import importlib.util
import json
import os

import roofline

_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "metrics")


def samples(snapshot: dict, family: str, labels: dict):
    for sample in (snapshot.get(family) or {}).get("samples", ()):
        if all(sample["labels"].get(k) == v for k, v in labels.items()):
            yield sample


def delta(run: dict, family: str, labels: dict, field: str) -> float:
    def total(snapshot):
        return sum(
            float(s.get(field) or 0.0) for s in samples(snapshot, family, labels)
        )

    return total(run["after"]) - total(run["before"])


def _labels(run: dict, spec: dict) -> dict:
    return {
        k: run["traffic"][v[1:]] if isinstance(v, str) and v.startswith("$") else v
        for k, v in (spec.get("labels") or {}).items()
    }


def histogram_mean(run, spec):
    """Sum over ``families`` of each histogram's mean over the window."""
    labels, total = _labels(run, spec), 0.0
    for family in spec["families"]:
        count = delta(run, family, labels, "count")
        if count <= 0:
            return None
        total += delta(run, family, labels, "sum") / count
    return total * spec.get("scale", 1.0)


def counter_share(run, spec):
    """Counters of ``families`` over the counter ``over``, in percent."""
    over = delta(run, spec["over"], _labels(run, spec), "value")
    if over <= 0:
        return None
    part = sum(delta(run, f, {}, "value") for f in spec["families"])
    return 100.0 * part / over


def load_number(run, spec):
    return run["load"].get(spec["key"])


def memory_gib(run, spec):
    peak = run.get("memory_peak_bytes")
    return peak / 2**30 if peak else None


def idle_share(run, spec):
    trace = run["trace"]
    if not trace or trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def idle_state_share(run, spec):
    """The idle seconds of the state the metric's file names (`state`, one
    of `trace_reduce.IDLE_STATES` or `no_request`, `unattributed`) over the
    traced window, in percent; nothing where the trace holds no stage
    event and the idle time is one lump."""
    trace = run["trace"] or {}
    seconds = dict(trace.get("idle_gaps") or ()).get(spec["state"])
    if seconds is None:
        return None
    return 100.0 * seconds / trace["window_s"]


def _topk_runs(run) -> int:
    runs = run["trace"].get("module_runs") or {}
    return runs.get("jit_" + run["config"]["jit_names"][-1], 0)


def topk_device_ms(run, spec):
    """Device time of the jitted predict programs (`jit_names` of the
    configuration) per batch."""
    if not run["trace"] or not _topk_runs(run):
        return None
    return 1e3 * run["trace"]["module_s"] / _topk_runs(run)


def topk_roofline(run, spec):
    """Least time of the traced batches over their device time. Each batch
    counts at the window's mean occupancy; the item table's bytes bound
    every batch up to 64 rows, so its size moves the result by under 0.1%."""
    runs = _topk_runs(run) if run["trace"] else 0
    occupancy = histogram_mean(run, {"families": ["pio_batch_occupancy"]})
    if not runs or not occupancy:
        return None
    cfg = run["config"]
    row = roofline.table_row_bytes(cfg["rank"], cfg["table_format"])
    least = roofline.roofline_seconds(
        roofline.topk_ops(occupancy, cfg["n_items"], cfg["rank"]),
        roofline.topk_bytes(
            occupancy, cfg["n_items"], cfg["rank"], cfg["num"], row, row
        ),
        run["peak"],
    )
    return roofline.share_percent(least * runs, run["trace"]["module_s"])


def serve_mfu(run, spec):
    """Model operations of the queries the device answered in the traced
    window over window x the chip's bf16 peak."""
    trace, cfg = run["trace"], run["config"]
    if not trace or not run.get("traced_queries"):
        return None
    ops = roofline.topk_ops(run["traced_queries"], cfg["n_items"], cfg["rank"])
    return roofline.share_percent(
        ops / run["peak"]["bf16_flops"], trace["window_s"]
    )


READERS = {
    f.__name__: f for f in (
        histogram_mean, counter_share, load_number, memory_gib, idle_share,
        idle_state_share, topk_device_ms, topk_roofline, serve_mfu,
    )
}


def read(name: str, run: dict):
    """The value of per-layer metric ``name`` in this run, or None."""
    for base in (name, name.split(".", 1)[-1]):
        path = os.path.join(_DIR, base)
        if os.path.exists(path + ".py"):
            spec = importlib.util.spec_from_file_location(base, path + ".py")
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            return module.read(run)
        if os.path.exists(path + ".json"):
            with open(path + ".json") as f:
                spec = json.load(f)
            return READERS[spec["reader"]](run, spec)
    raise FileNotFoundError(f"no reader for per-layer metric {name!r} under metrics/")
