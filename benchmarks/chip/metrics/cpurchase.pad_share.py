"""Padded pair slots over the pair slots sent, in percent, over the
window: (pio_cpurchase_item_slots_total - pio_cpurchase_items_total) /
pio_cpurchase_item_slots_total. A padding slot gathers a table row like a
real one."""
import layer_metrics


def read(run):
    slots = layer_metrics.delta(run, "pio_cpurchase_item_slots_total", {}, "value")
    if slots <= 0:
        return None
    items = layer_metrics.delta(run, "pio_cpurchase_items_total", {}, "value")
    return 100.0 * (slots - items) / slots
