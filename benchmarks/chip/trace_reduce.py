"""From the profiler's trace to numbers: device busy time, the time of the
jitted programs by name, and the breakdown: the longest device operations
by named scope, and the idle time by what the host was doing.

The traced window is the span of the host event ``chipbench_window``, which
the runner holds open over steady traffic only (no warm-up, no drain), as
the driver's breakdown takes it; device and stage events are clipped to it.
Busy is the union of the intervals in which an operation ran on the device,
idle the rest of the window. The program's stages (``tracing.stage``) ride
the trace as host events on the same clock; their names, the states they
make up and the interval arithmetic are written here, so that the yardstick
is not the program's to move.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

WINDOW_EVENT = "chipbench_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

#: the program's stage names (`predictionio_tpu.obs.tracing.STAGES`; a test
#: holds the two equal)
STAGES = (
    "http.read", "http.admit", "engine.decode", "engine.submit",
    "engine.await", "engine.serve", "http.respond", "http.encode",
    "http.write", "batch.window", "batch.backpressure", "predict.prep",
    "predict.enqueue", "predict.device_get", "predict.materialize",
    "batch.settle",
)
#: what the host was doing while the device idled: every idle instant goes
#: to the FIRST state one of whose stages is open on any thread
#: (`utils/profiling.IDLE_STATES`, states and precedence)
IDLE_STATES = (
    ("launch", ("predict.prep", "predict.enqueue")),
    ("device_get", ("predict.device_get",)),
    ("materialize_settle", ("predict.materialize", "batch.settle")),
    ("backpressure", ("batch.backpressure",)),
    ("batch_window", ("batch.window",)),
    ("request_in", ("http.read", "http.admit", "engine.decode", "engine.submit")),
    ("response_out", ("http.respond",)),
)
#: then, with no handler stage open at all, ``no_request``; what is left (a
#: request waits or is served, no named stage runs) is ``unattributed``
HANDLER_STAGES = (
    "http.read", "http.admit", "engine.decode", "engine.submit",
    "engine.await", "engine.serve", "http.respond",
)
#: the one entry of a trace that holds no stage event (a program older than
#: the stages)
LUMP = "host_between_device_batches"
_PROGRAM_ID = re.compile(r"\((\d+)\)$")


def trace_file(trace_dir: str) -> str | None:
    paths = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    return sorted(paths)[-1] if paths else None


def load_events(trace_dir: str) -> list[tuple[str, str, str, int, int, str]]:
    """``(plane, line, name, start_ns, duration_ns, scope)`` of every event
    on a device plane's operation and module lines (an operation with the
    ``jax.named_scope`` its metadata names, else ``""``), of the window
    event, and of the host planes' events that are stages."""
    from jax.profiler import ProfileData

    path = trace_file(trace_dir)
    if path is None:
        return []
    op_names = _op_names(path)
    events = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        host = plane.name.startswith("/host:")
        lines = [
            line for line in plane.lines
            if not device or line.name in (OPS_LINE, MODULES_LINE)
        ]
        programs = sorted(
            (int(ev.start_ns), int(m.group(1)))
            for line in lines if device and line.name == MODULES_LINE
            for ev in line.events if (m := _PROGRAM_ID.search(ev.name))
        )
        starts = [start for start, _program in programs]
        for line in lines:
            for ev in line.events:
                scope = ""
                if not device:
                    if ev.name != WINDOW_EVENT and not (host and ev.name in STAGES):
                        continue
                elif line.name == OPS_LINE:
                    # the program of the module event it ran under
                    at = bisect.bisect_right(starts, int(ev.start_ns)) - 1
                    program = programs[at][1] if at >= 0 else None
                    scope = scope_of(
                        op_names.get((plane.name, program, ev.name), "")
                    )
                events.append((
                    plane.name, line.name, ev.name,
                    int(ev.start_ns), int(ev.duration_ns), scope,
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


def scope_of(op_name: str) -> str:
    """The ``jax.named_scope`` path of a device operation from the name
    XLA's metadata gives it: ``jit(f)/jit(g)/score/dot_general`` is
    ``score``. Transformation wrappers (``jit(...)``) and the trailing
    primitive drop out; no scope left is ``""``."""
    return "/".join(
        part for part in op_name.split("/")[:-1]
        if part and not part.endswith(")")
    )


# -- covers: sorted lists of disjoint (start_ns, end_ns) ----------------------


def union(intervals) -> list[tuple[int, int]]:
    out: list[tuple[int, int]] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def intersect(xs, ys) -> list[tuple[int, int]]:
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        start, end = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if start < end:
            out.append((start, end))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(xs, ys) -> list[tuple[int, int]]:
    """``xs`` without ``ys``."""
    out, j = [], 0
    for start, end in xs:
        while j < len(ys) and ys[j][1] <= start:
            j += 1
        k = j
        while k < len(ys) and ys[k][0] < end:
            if ys[k][0] > start:
                out.append((start, ys[k][0]))
            start = max(start, ys[k][1])
            k += 1
        if start < end:
            out.append((start, end))
    return out


def cover_ns(cover) -> int:
    return sum(end - start for start, end in cover)


def union_seconds(intervals) -> float:
    """Length of the union of ``(start_ns, end_ns)`` intervals."""
    return cover_ns(union(intervals)) / 1e9


def idle_by_state(idle_covers, by_stage: dict) -> list[list]:
    """``[[state, seconds], ...]``, largest first, of the idle time of the
    device planes (one cover of idle intervals each; their mean) by the
    state the host was in. The states' seconds sum to the idle time."""
    open_in = [
        (state, union(s for name in names for s in by_stage.get(name, ())))
        for state, names in IDLE_STATES
    ]
    in_flight = union(
        s for name in HANDLER_STAGES for s in by_stage.get(name, ())
    )
    idle = {state: 0 for state, _names in IDLE_STATES}
    idle.update(no_request=0, unattributed=0)
    for left in idle_covers:
        for state, cover in open_in:
            idle[state] += cover_ns(intersect(left, cover))
            left = subtract(left, cover)
        idle["unattributed"] += cover_ns(intersect(left, in_flight))
        idle["no_request"] += cover_ns(subtract(left, in_flight))
    n = len(idle_covers)
    return sorted(
        ([state, ns / n / 1e9] for state, ns in idle.items()),
        key=lambda kv: -kv[1],
    )


def reduce(events, module_prefixes=()) -> dict:
    """Window, busy seconds averaged over the device planes, the named
    modules' seconds and executions, the ten longest operations (under
    their scope where the trace names one), and the idle time by state."""
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
    busy, per_op, idle_covers = [], {}, []
    module_s, module_runs = 0.0, {}
    for plane in planes:
        spans = []
        for _, line, name, start, dur, *scope in (e for e in device if e[0] == plane):
            start, end = max(start, lo), min(start + dur, hi)
            if end <= start:
                continue
            if line == OPS_LINE:
                spans.append((start, end))
                op = short_name(name)
                if scope and scope[0]:
                    op = (scope[0] + "/" + op)[:80]
                per_op[op] = per_op.get(op, 0.0) + (end - start) / 1e9
            elif any(name.startswith(p) for p in module_prefixes):
                module_s += (end - start) / 1e9
                key = name.split("(")[0]
                module_runs[key] = module_runs.get(key, 0) + 1
        cover = union(spans)
        busy.append(cover_ns(cover) / 1e9)
        idle_covers.append(subtract([(lo, hi)], cover))
    window_s = (hi - lo) / 1e9
    busy_s = sum(busy) / len(busy)
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    by_stage: dict[str, list] = {}
    for plane, _line, name, start, dur, *_ in events:
        if name in STAGES and not plane.startswith("/device:"):
            start, end = max(start, lo), min(start + dur, hi)
            if start < end:
                by_stage.setdefault(name, []).append((start, end))
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "module_s": module_s / len(planes),
        "module_runs": module_runs,
        "device_ops": [[k, v] for k, v in top],
        "idle_gaps": (
            idle_by_state(idle_covers, by_stage) if by_stage
            else [[LUMP, window_s - busy_s]]
        ),
    }


# -- the scopes: XLA's metadata, which the profiler's reader leaves out -------


def _varint(buf, at: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, at
        shift += 7


def _proto_fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a memoryview for a length-delimited or fixed-width field."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        kind = key & 7
        if kind == 0:
            value, at = _varint(buf, at)
        else:
            if kind == 2:
                size, at = _varint(buf, at)
            elif kind in (1, 5):
                size = 8 if kind == 1 else 4
            else:
                raise ValueError(f"unsupported protobuf wire type {kind}")
            value = buf[at:at + size]
            at += size
        yield key >> 3, value


def _op_names(xplane_path: str) -> dict[tuple[str, int, str], str]:
    """``(device plane, program id, instruction text) -> op name`` for
    every operation a device plane's metadata describes: the ``tf_op``
    statistic, which holds ``jit(f)/scope/primitive`` and which
    ``jax.profiler.ProfileData`` does not give. Field numbers are those of
    tsl's ``xplane.proto``."""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    out: dict[tuple[str, int, str], str] = {}
    for field, plane in _proto_fields(space):
        if field != 1:  # XSpace.planes
            continue
        name, stat_names, metadata = "", {}, []
        for field, value in _proto_fields(plane):
            if field == 2:  # XPlane.name
                name = bytes(value).decode()
            elif field == 4:  # event_metadata: map<int64, XEventMetadata>
                metadata.extend(v for f, v in _proto_fields(value) if f == 2)
            elif field == 5:  # stat_metadata: map<int64, XStatMetadata>
                for f, v in _proto_fields(value):
                    if f == 2:
                        meta = dict(_proto_fields(v))
                        stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        if not name.startswith("/device:"):
            continue
        for event in metadata:
            text, program, op_name = "", None, None
            for field, value in _proto_fields(event):
                if field == 2:  # XEventMetadata.name
                    text = bytes(value).decode()
                elif field == 5:  # XEventMetadata.stats
                    stat = dict(_proto_fields(value))
                    stat_name = stat_names.get(stat.get(1))
                    if stat_name == "program_id":
                        program = stat.get(3, stat.get(4))
                    elif stat_name == "tf_op":
                        # a string, or a reference to an interned one
                        op_name = (
                            bytes(stat[5]).decode() if 5 in stat
                            else stat_names.get(stat.get(7), "")
                        )
            if program is not None and op_name is not None:
                out[(name, program, text)] = op_name
    return out
