"""Distinct known cart items the launch sent (pio_cpurchase_items_total)
per query the batchers dispatched (the sum of pio_batch_occupancy) over
the window; nothing where the program counts no items."""
import layer_metrics


def read(run):
    queries = layer_metrics.delta(run, "pio_batch_occupancy", {}, "sum")
    items = layer_metrics.delta(run, "pio_cpurchase_items_total", {}, "value")
    if queries <= 0 or items <= 0:
        return None
    return items / queries
