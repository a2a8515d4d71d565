"""Median of the program's ``infer.prefill`` span inside the window:
ONE prefill sub-batch (``decode.lm_prefill_rows`` recordings' audio
prefix, 10,500 positions, through the layers' sequence form in query
blocks of 512 and into the rings and the full cache); the engine
blocks on it inside the span when the tracer is on."""

import statistics

from benchmark.layer_metrics import _trinity

DRIVERS = _trinity.DRIVERS


def read(record):
    if not _trinity.window_calls(record):
        return None
    spans = _trinity.span_seconds(record, "infer.prefill")
    return 1e3 * statistics.median(spans) if spans else None
