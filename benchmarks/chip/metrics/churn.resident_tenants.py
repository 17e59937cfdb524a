"""Tenants resident in the model pool at the window's end
(pio_pool_tenants_resident)."""
import layer_metrics


def read(run):
    found = list(layer_metrics.samples(run["after"], "pio_pool_tenants_resident", {}))
    return float(found[0]["value"]) if found else None
