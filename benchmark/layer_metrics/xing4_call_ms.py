"""Median of the program's ``infer.transcribe`` span inside the window
of a self-drafting served run: one call of ``Inferencer.decode_batch``
from the batch on the device to the ids on the host. With the tracer
on the engine blocks on each prefill sub-batch inside its own span, so
this is dispatch plus the device's whole call."""

import statistics

from benchmark.layer_metrics import _xing4

DRIVERS = _xing4.DRIVERS


def read(record):
    if not _xing4.window_calls(record):
        return None
    spans = _xing4.span_seconds(record, "infer.transcribe")
    return 1e3 * statistics.median(spans) if spans else None
