"""Entries of the packed own-items / black / white lists sent to the
device per query and algorithm of the window
(pio_similar_excluded_items_total over pio_similar_queries_total)."""
import layer_metrics


def read(run):
    queries = layer_metrics.delta(run, "pio_similar_queries_total", {}, "value")
    if queries <= 0:
        return None
    return layer_metrics.delta(run, "pio_similar_excluded_items_total", {}, "value") / queries
