"""Median of the program's ``infer.transcribe`` span inside the window
of a served run of the hybrid recogniser: one call of
``Inferencer.decode_batch`` from the batch on the device to the ids on
the host (4 prefill sub-batches, then one loop of up to 60 steps). With
the tracer on the engine blocks on each prefill sub-batch inside its own
span, so this is dispatch plus the device's whole call."""

import statistics

from benchmark.layer_metrics import _falcon

DRIVERS = _falcon.DRIVERS


def read(record):
    if not _falcon.window_calls(record):
        return None
    spans = _falcon.span_seconds(record, "infer.transcribe")
    return 1e3 * statistics.median(spans) if spans else None
