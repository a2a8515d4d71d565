"""Median of the program's ``infer.prefill`` span inside the window:
ONE prefill sub-batch (``decode.lm_prefill_rows`` utterances' audio
prefix, 32 x 212 positions, through six layers' sequence form, the
mixer's in chunks of 128, and into keys, values, state and convolution
inputs); the engine blocks on it inside the span when the tracer is
on."""

import statistics

from benchmark.layer_metrics import _falcon

DRIVERS = _falcon.DRIVERS


def read(record):
    if not _falcon.window_calls(record):
        return None
    spans = _falcon.span_seconds(record, "infer.prefill")
    return 1e3 * statistics.median(spans) if spans else None
