"""Time off the CPU of one predict launch, in ms: the wall of the profiled
`predict.prep` and `predict.enqueue` (pio_stage_profiled_seconds_total) less
their thread CPU (pio_stage_cpu_seconds), over the launches the profiler saw.
Off the CPU is waiting: for the interpreter lock, in a blocking call, for the
runtime. A mean over the traced slice; nothing where the families counted
none (an untraced run, a program without them)."""
import layer_metrics

LAUNCH = ("predict.prep", "predict.enqueue")


def read(run):
    launches = layer_metrics.delta(
        run, "pio_stage_cpu_seconds", {"stage": "predict.enqueue"}, "count"
    )
    if launches <= 0:
        return None
    off = sum(
        layer_metrics.delta(run, "pio_stage_profiled_seconds_total", {"stage": s}, "value")
        - layer_metrics.delta(run, "pio_stage_cpu_seconds", {"stage": s}, "sum")
        for s in LAUNCH
    )
    return 1e3 * off / launches
