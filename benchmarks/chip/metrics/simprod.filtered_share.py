"""Share of the window's queries that carried a category, a whiteList or
a blackList (pio_similar_filtered_queries_total, all rules, over
pio_similar_queries_total; each algorithm counts both), in percent."""
import layer_metrics


def read(run):
    queries = layer_metrics.delta(run, "pio_similar_queries_total", {}, "value")
    if queries <= 0:
        return None
    return 100.0 * layer_metrics.delta(
        run, "pio_similar_filtered_queries_total", {}, "value"
    ) / queries
