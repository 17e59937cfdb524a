"""Least time of the traced batches of the masked top-k step
(roofline_ecomm.py's count, unchanged: the kernel is the accepted one and
its roofline reads the same work whoever calls it; at the window's mean
occupancy and mean list entries a batch) over their device time (the
jitted step the configuration names)."""
import layer_metrics
import roofline
import roofline_ecomm


def read(run):
    trace, cfg = run.get("trace"), run["config"]
    runs = ((trace or {}).get("module_runs") or {}).get(
        "jit_" + cfg["jit_names"][-1], 0
    )
    occupancy = layer_metrics.histogram_mean(run, {"families": ["pio_batch_occupancy"]})
    batches = layer_metrics.delta(run, "pio_batch_occupancy", {}, "count")
    if not runs or not occupancy or batches <= 0:
        return None
    entries = layer_metrics.delta(run, "pio_similar_excluded_items_total", {}, "value") / batches
    least = roofline.roofline_seconds(
        roofline_ecomm.masked_topk_ops(occupancy, cfg["n_items"], cfg["rank"]),
        roofline_ecomm.masked_topk_bytes(
            occupancy, cfg["n_items"], cfg["rank"], cfg["num"], entries
        ),
        run["peak"],
    )
    return roofline.share_percent(least * runs, trace["module_s"])
