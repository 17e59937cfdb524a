"""Seconds the pool's loader thread spent in cold loads until the server
was built (the sum of pio_stage_seconds{stage=pool.load} in the scrape the
runner takes then, ``run["built"]``): the preload's share of setup_s.
Nothing on a program that has no such stage."""
import layer_metrics


def read(run):
    found = list(layer_metrics.samples(
        run.get("built") or {}, "pio_stage_seconds", {"stage": "pool.load"}
    ))
    return sum(float(s["sum"]) for s in found) if found and found[0]["count"] else None
