"""Least time of the traced batches' scoring (roofline_cpurchase.py: the
table rows of each batch's distinct (cart, item) pairs and the summed
scores of its queries, at the window's mean items and queries a batch)
over the device time of the `cpurchase_score` scope in the trace."""
import layer_metrics
import roofline
import roofline_cpurchase


def read(run):
    trace, cfg = run.get("trace") or {}, run["config"]
    runs = (trace.get("module_runs") or {}).get("jit_" + cfg["jit_names"][-1], 0)
    seconds = run.get("scope_s") or 0.0
    batches = layer_metrics.delta(run, "pio_batch_occupancy", {}, "count")
    items = layer_metrics.delta(run, "pio_cpurchase_items_total", {}, "value")
    if not runs or seconds <= 0 or batches <= 0 or items <= 0:
        return None
    queries = layer_metrics.delta(run, "pio_batch_occupancy", {}, "sum") / batches
    least = roofline.roofline_seconds(
        roofline_cpurchase.score_ops(items / batches, cfg["n_items"]),
        roofline_cpurchase.score_bytes(queries, items / batches, cfg["n_items"]),
        run["peak"],
    )
    return roofline.share_percent(least * runs, seconds)
