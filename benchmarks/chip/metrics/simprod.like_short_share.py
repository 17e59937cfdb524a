"""Share of the window's queries that the ``like`` algorithm answered
with fewer than ``num`` items or none (pio_similar_queries_total, results
short and empty over all results of that algorithm), in percent."""
import layer_metrics


def read(run):
    like = {"algorithm": "like"}
    queries = layer_metrics.delta(run, "pio_similar_queries_total", like, "value")
    if queries <= 0:
        return None
    short = sum(
        layer_metrics.delta(
            run, "pio_similar_queries_total", {**like, "result": r}, "value"
        )
        for r in ("short", "empty")
    )
    return 100.0 * short / queries
