"""The device's peak memory less the model pool's byte budget
(memory_stats()[peak_bytes_in_use] after the window, pio_pool_budget_bytes),
in GiB: what the chip held beyond what the pool's ledger allows. One tenant
in flight beside a full pool and the programs' temporaries belong here; a
generation that outlives its eviction does not."""
import layer_metrics


def read(run):
    budget = list(layer_metrics.samples(run["after"], "pio_pool_budget_bytes", {}))
    peak = run.get("memory_peak_bytes")
    if not budget or not peak:
        return None
    return (peak - float(budget[0]["value"])) / 2**30
