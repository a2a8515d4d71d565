"""Share of the window the training loop spent waiting for the next
batch and dispatching its transfer: the program's spans
``pipeline.data_wait`` + ``pipeline.device_prefetch`` over the window."""

from benchmark import harness

DRIVERS = ("train",)


def read(record):
    if not record["spans"]:
        return None
    window = record["t_window_end"] - record["t_window_start"]
    return 100.0 * harness.span_seconds(
        record, "pipeline.data_wait", "pipeline.device_prefetch") / window
