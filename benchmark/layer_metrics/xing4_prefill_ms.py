"""Median of the program's ``infer.prefill`` span inside the window:
ONE prefill sub-batch (``decode.lm_prefill_rows`` utterances' audio
prefix through the layers' sequence form, four streams wide, and
through the draft module, into the cache); the engine blocks on it
inside the span when the tracer is on."""

import statistics

from benchmark.layer_metrics import _xing4

DRIVERS = _xing4.DRIVERS


def read(record):
    if not _xing4.window_calls(record):
        return None
    spans = _xing4.span_seconds(record, "infer.prefill")
    return 1e3 * statistics.median(spans) if spans else None
