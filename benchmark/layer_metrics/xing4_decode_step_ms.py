"""Milliseconds a drafting step (two verify positions and the draft
module's pass, every stream): the program's ``infer.decode`` spans in
the window (the on-device loop of a call, up to the ids on the host)
over the steps those loops ran (the program's own counter)."""

from benchmark.layer_metrics import _xing4

DRIVERS = _xing4.DRIVERS


def read(record):
    spans = _xing4.span_seconds(record, "infer.decode")
    steps = sum(c["decode_steps"] for c in _xing4.window_calls(record))
    return 1e3 * sum(spans) / steps if spans and steps else None
