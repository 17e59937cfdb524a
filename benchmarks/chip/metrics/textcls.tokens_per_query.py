"""Tokens the launch hashed (pio_textcls_tokens_total) per query the
batchers dispatched (the sum of pio_batch_occupancy) over the window;
nothing where the program counts no tokens."""
import layer_metrics


def read(run):
    queries = layer_metrics.delta(run, "pio_batch_occupancy", {}, "sum")
    tokens = layer_metrics.delta(run, "pio_textcls_tokens_total", {}, "value")
    if queries <= 0 or tokens <= 0:
        return None
    return tokens / queries
