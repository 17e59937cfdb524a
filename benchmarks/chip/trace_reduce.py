"""From the profiler's trace to numbers: device busy time, the time of the
jitted programs by name, and the breakdown.

The traced window is the span of the host event ``chipbench_window``, which
the runner holds open over steady traffic only (no warm-up, no drain), as
the driver's breakdown takes it; device events are clipped to it. Busy is
the union of the intervals in which an operation ran on the device, idle
the rest of the window.
"""

from __future__ import annotations

import glob
import os
import re

WINDOW_EVENT = "chipbench_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load_events(trace_dir: str) -> list[tuple[str, str, str, int, int]]:
    """``(plane, line, name, start_ns, duration_ns)`` of every event on a
    device plane's operation and module lines, and of the window event."""
    from jax.profiler import ProfileData

    paths = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    if not paths:
        return []
    events = []
    for plane in ProfileData.from_file(sorted(paths)[-1]).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if device or ev.name == WINDOW_EVENT:
                    events.append((
                        plane.name, line.name, ev.name,
                        int(ev.start_ns), int(ev.duration_ns),
                    ))
    return events


def short_name(name: str) -> str:
    """An operation's name and result shape from the instruction text the
    trace gives: ``%fusion.1 = (f32[64,16]{...}, ...) fusion(...)`` becomes
    ``fusion.1 f32[64,16]``."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:80]
    shape = re.search(r"\w+\[[\d,]*\]", rest)
    return head.lstrip("%") + (" " + shape.group(0) if shape else "")


def union_seconds(intervals) -> float:
    """Length of the union of ``(start_ns, end_ns)`` intervals."""
    total, reach = 0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total / 1e9


def reduce(events, module_prefixes=()) -> dict:
    """Window, busy seconds averaged over the device planes, the named
    modules' seconds and executions, and the ten longest operations."""
    window = [e for e in events if e[2] == WINDOW_EVENT]
    device = [e for e in events if e[0].startswith("/device:")]
    if not device:
        return {}
    if window:
        lo, hi = window[0][3], window[0][3] + window[0][4]
    else:
        lo = min(e[3] for e in device)
        hi = max(e[3] + e[4] for e in device)
    planes = sorted({e[0] for e in device})
    busy, per_op = [], {}
    module_s, module_runs = 0.0, {}
    for plane in planes:
        spans = []
        for _, line, name, start, dur in (e for e in device if e[0] == plane):
            start, end = max(start, lo), min(start + dur, hi)
            if end <= start:
                continue
            if line == OPS_LINE:
                spans.append((start, end))
                op = short_name(name)
                per_op[op] = per_op.get(op, 0.0) + (end - start) / 1e9
            elif any(name.startswith(p) for p in module_prefixes):
                module_s += (end - start) / 1e9
                key = name.split("(")[0]
                module_runs[key] = module_runs.get(key, 0) + 1
        busy.append(union_seconds(spans))
    window_s = (hi - lo) / 1e9
    busy_s = sum(busy) / len(busy)
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "module_s": module_s / len(planes),
        "module_runs": module_runs,
        "device_ops": [[k, v] for k, v in top],
        "idle_gaps": [["host_between_device_batches", window_s - busy_s]],
    }
