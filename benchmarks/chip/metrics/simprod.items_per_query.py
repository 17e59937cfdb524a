"""Items a query of the window named, known or not
(pio_similar_query_items_total over pio_similar_queries_total, both summed
over the algorithms, which each count every query)."""
import layer_metrics


def read(run):
    queries = layer_metrics.delta(run, "pio_similar_queries_total", {}, "value")
    if queries <= 0:
        return None
    return layer_metrics.delta(run, "pio_similar_query_items_total", {}, "value") / queries
