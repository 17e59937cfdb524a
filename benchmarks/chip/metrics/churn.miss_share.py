"""Lookups of the model pool that found their tenant not resident, over all
lookups of the window (pio_pool_misses_total over hits + misses, every
tenant), in percent: the share of posts that waited for a cold load."""
import layer_metrics


def read(run):
    misses = layer_metrics.delta(run, "pio_pool_misses_total", {}, "value")
    lookups = misses + layer_metrics.delta(run, "pio_pool_hits_total", {}, "value")
    return 100.0 * misses / lookups if lookups > 0 else None
