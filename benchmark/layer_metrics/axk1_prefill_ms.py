"""Median of the program's ``infer.prefill`` span inside the window:
ONE prefill sub-batch (``decode.lm_prefill_rows`` utterances' audio
prefix through the layers' sequence form into the cache); the engine
blocks on it inside the span when the tracer is on."""

import statistics

from benchmark.layer_metrics import _axk1

DRIVERS = _axk1.DRIVERS


def read(record):
    spans = _axk1.span_seconds(record, "infer.prefill")
    return 1e3 * statistics.median(spans) if spans else None
