"""Median of the program's ``infer.transcribe`` span inside the window
of a served run on recordings of minutes: one call of
``Inferencer.decode_batch`` from the batch on the device to the ids on
the host (8 prefill sub-batches, then one loop of about 1,500 steps).
With the tracer on the engine blocks on each prefill sub-batch inside
its own span, so this is dispatch plus the device's whole call."""

import statistics

from benchmark.layer_metrics import _trinity

DRIVERS = _trinity.DRIVERS


def read(record):
    if not _trinity.window_calls(record):
        return None
    spans = _trinity.span_seconds(record, "infer.transcribe")
    return 1e3 * statistics.median(spans) if spans else None
