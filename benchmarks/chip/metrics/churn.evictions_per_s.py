"""Tenants evicted from the model pool a second of the window
(pio_pool_evictions_total, every tenant, over the clock of the same two
scrapes)."""
import layer_metrics


def read(run):
    clock = layer_metrics.delta(run, "pio_process_clock_seconds_total", {}, "value")
    if clock <= 0 or "pio_pool_evictions_total" not in run["after"]:
        return None
    return layer_metrics.delta(run, "pio_pool_evictions_total", {}, "value") / clock
