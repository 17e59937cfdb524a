"""Entries of the packed seen / black / white lists sent to the device per
query of the window (pio_ecomm_excluded_items_total over
pio_ecomm_queries_total)."""
import layer_metrics


def read(run):
    queries = layer_metrics.delta(run, "pio_ecomm_queries_total", {}, "value")
    if queries <= 0:
        return None
    return layer_metrics.delta(run, "pio_ecomm_excluded_items_total", {}, "value") / queries
