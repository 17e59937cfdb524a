"""Padded pair slots over the pair slots sent, in percent, over the
window: (pio_textcls_pair_slots_total - pio_textcls_pairs_total) /
pio_textcls_pair_slots_total. The padding to a power-of-two bucket is
gathered and summed like a real pair."""
import layer_metrics


def read(run):
    slots = layer_metrics.delta(run, "pio_textcls_pair_slots_total", {}, "value")
    if slots <= 0:
        return None
    pairs = layer_metrics.delta(run, "pio_textcls_pairs_total", {}, "value")
    return 100.0 * (slots - pairs) / slots
