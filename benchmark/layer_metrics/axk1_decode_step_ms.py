"""Milliseconds a decode step: the program's ``infer.decode`` spans in
the window (the on-device loop of a call, up to the ids on the host)
over the steps those loops ran (the program's own counter)."""

from benchmark.layer_metrics import _axk1

DRIVERS = _axk1.DRIVERS


def read(record):
    spans = _axk1.span_seconds(record, "infer.decode")
    steps = sum(c["decode_steps"] for c in _axk1.window_calls(record))
    return 1e3 * sum(spans) / steps if spans and steps else None
