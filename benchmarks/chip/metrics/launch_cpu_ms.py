"""Thread CPU of one predict launch, in ms: what `predict.prep` and
`predict.enqueue` spent on the batcher's CPU (pio_stage_cpu_seconds) over the
launches that family saw (its count at predict.enqueue). The family counts only
while a profiler runs, so this is a mean over the traced slice; nothing where
it counted none (an untraced run, a program without the family)."""
import layer_metrics

LAUNCH = ("predict.prep", "predict.enqueue")


def read(run):
    launches = layer_metrics.delta(
        run, "pio_stage_cpu_seconds", {"stage": "predict.enqueue"}, "count"
    )
    if launches <= 0:
        return None
    cpu = sum(
        layer_metrics.delta(run, "pio_stage_cpu_seconds", {"stage": s}, "sum")
        for s in LAUNCH
    )
    return 1e3 * cpu / launches
