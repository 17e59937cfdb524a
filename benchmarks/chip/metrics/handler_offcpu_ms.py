"""Time off the CPU of the handler's own work on one request or post, in ms:
the wall of the profiled `http.read`, `http.admit`, `engine.decode`,
`engine.submit`, `engine.serve` and `http.respond` (pio_stage_profiled_seconds_total)
less their thread CPU (pio_stage_cpu_seconds), over the requests the profiler
saw (the count at engine.decode). `engine.await` and `pool.wait` are waits by
design and left out. A mean over the traced slice; nothing where the families
counted none (an untraced run, a program without them)."""
import layer_metrics

HANDLER = (
    "http.read", "http.admit", "engine.decode", "engine.submit", "engine.serve",
    "http.respond",
)


def read(run):
    requests = layer_metrics.delta(
        run, "pio_stage_cpu_seconds", {"stage": "engine.decode"}, "count"
    )
    if requests <= 0:
        return None
    off = sum(
        layer_metrics.delta(run, "pio_stage_profiled_seconds_total", {"stage": s}, "value")
        - layer_metrics.delta(run, "pio_stage_cpu_seconds", {"stage": s}, "sum")
        for s in HANDLER
    )
    return 1e3 * off / requests
