"""Device batches dispatched per post answered in the window
(pio_batches_total, all batchers, over the count of the server's request
histogram on the cell's route): 2.0 while each of a tenant's two algorithms
launches for itself."""
import layer_metrics


def read(run):
    posts = layer_metrics.delta(
        run, "pio_http_request_seconds",
        {"service": "engine", "route": run["traffic"]["route"]}, "count",
    )
    if posts <= 0 or "pio_batches_total" not in run["after"]:
        return None
    return layer_metrics.delta(run, "pio_batches_total", {}, "value") / posts
