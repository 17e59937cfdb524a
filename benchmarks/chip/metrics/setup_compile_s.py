"""Seconds inside XLA's compile-or-load since the server was built (the
sum of pio_xla_compile_seconds at the window's end, hits and misses)."""
import layer_metrics


def read(run):
    found = list(layer_metrics.samples(run["after"], "pio_xla_compile_seconds", {}))
    return sum(float(s["sum"]) for s in found) if found else None
