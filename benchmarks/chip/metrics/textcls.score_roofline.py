"""Least time of the traced batches' scoring (roofline_textcls.py: the
table rows of each batch's distinct pairs, its operands and scores, at the
window's mean pairs, slots and queries a batch) over the device time of
the `textcls_score` scope in the trace."""
import layer_metrics
import roofline
import roofline_textcls


def read(run):
    trace, cfg = run.get("trace") or {}, run["config"]
    runs = (trace.get("module_runs") or {}).get("jit_" + cfg["jit_names"][-1], 0)
    seconds = run.get("scope_s") or 0.0
    batches = layer_metrics.delta(run, "pio_batch_occupancy", {}, "count")
    pairs = layer_metrics.delta(run, "pio_textcls_pairs_total", {}, "value")
    if not runs or seconds <= 0 or batches <= 0 or pairs <= 0:
        return None
    queries = layer_metrics.delta(run, "pio_batch_occupancy", {}, "sum") / batches
    slots = layer_metrics.delta(run, "pio_textcls_pair_slots_total", {}, "value") / batches
    least = roofline.roofline_seconds(
        roofline_textcls.score_ops(pairs / batches, cfg["n_classes"]),
        roofline_textcls.score_bytes(queries, pairs / batches, slots, cfg["n_classes"]),
        run["peak"],
    )
    return roofline.share_percent(least * runs, seconds)
