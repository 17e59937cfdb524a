"""Seconds the pool's one loader thread spent in cold loads and in closing
evicted generations (pio_stage_seconds of pool.load and pool.close) over the
clock of the same two scrapes, in percent: at 100 the loader is the bound.
Nothing on a program that has no such stage."""
import layer_metrics


def read(run):
    clock = layer_metrics.delta(run, "pio_process_clock_seconds_total", {}, "value")
    loads = layer_metrics.delta(run, "pio_stage_seconds", {"stage": "pool.load"}, "count")
    if clock <= 0 or loads <= 0:
        return None
    busy = sum(
        layer_metrics.delta(run, "pio_stage_seconds", {"stage": stage}, "sum")
        for stage in ("pool.load", "pool.close")
    )
    return 100.0 * busy / clock
