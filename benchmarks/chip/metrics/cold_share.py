"""Share of the window's queries from users the model does not know (the
similar and popular branches of pio_ecomm_queries_total), in percent."""
import layer_metrics


def read(run):
    queries = layer_metrics.delta(run, "pio_ecomm_queries_total", {}, "value")
    if queries <= 0:
        return None
    cold = sum(
        layer_metrics.delta(run, "pio_ecomm_queries_total", {"branch": b}, "value")
        for b in ("similar", "popular")
    )
    return 100.0 * cold / queries
