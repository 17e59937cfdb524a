"""Share of the items answered in the window that both algorithms' lists
held (pio_serving_combined_items_total, lists = 2 over all), in percent."""
import layer_metrics


def read(run):
    items = layer_metrics.delta(run, "pio_serving_combined_items_total", {}, "value")
    if items <= 0:
        return None
    return 100.0 * layer_metrics.delta(
        run, "pio_serving_combined_items_total", {"lists": "2"}, "value"
    ) / items
