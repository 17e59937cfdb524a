"""Programs XLA compiled or loaded from its cache inside the window (the
delta of pio_xla_compiles_total, hits and misses): there should be none."""
import layer_metrics


def read(run):
    if "pio_xla_compiles_total" not in run["after"]:
        return None
    return layer_metrics.delta(run, "pio_xla_compiles_total", {}, "value")
