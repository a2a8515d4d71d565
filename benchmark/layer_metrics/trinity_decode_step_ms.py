"""Milliseconds a decode step (one new position a stream through five
layers against four rings and a full cache, the head's slice, the
argmax): the program's ``infer.decode`` spans in the window (the
on-device loop of a call, up to the ids on the host) over the steps
those loops ran (the program's own counter)."""

from benchmark.layer_metrics import _trinity

DRIVERS = _trinity.DRIVERS


def read(record):
    spans = _trinity.span_seconds(record, "infer.decode")
    steps = sum(c["decode_steps"] for c in _trinity.window_calls(record))
    return 1e3 * sum(spans) / steps if spans and steps else None
