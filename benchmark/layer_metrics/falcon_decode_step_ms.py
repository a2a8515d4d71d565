"""Milliseconds a decode step (one new position a stream through six
layers: every live stream's state read and written, its cache rows in
reach, the layers' weights, the whole head, the argmax): the program's
``infer.decode`` spans in the window (the on-device loop of a call, up
to the ids on the host) over the steps those loops ran (the program's
own counter)."""

from benchmark.layer_metrics import _falcon

DRIVERS = _falcon.DRIVERS


def read(record):
    spans = _falcon.span_seconds(record, "infer.decode")
    steps = sum(c["decode_steps"] for c in _falcon.window_calls(record))
    return 1e3 * sum(spans) / steps if spans and steps else None
