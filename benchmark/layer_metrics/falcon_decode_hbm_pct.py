"""A decode step's share of the memory roofline: the bytes the window's
decode steps NEED (the program's own ``decode_bytes`` by part: every
layer's weights and the head once a step, each live (stream, layer)'s
float32 state read once and written once, the cache rows in reach) over
the seconds of the ``infer.decode`` spans times the device's published
HBM bandwidth."""

from benchmark.layer_metrics import _falcon

DRIVERS = _falcon.DRIVERS


def read(record):
    calls = _falcon.window_calls(record)
    spans = _falcon.span_seconds(record, "infer.decode")
    if record["peaks"] is None or not spans or not calls:
        return None
    needed = sum(sum(c["decode_bytes"].values()) for c in calls)
    return 100.0 * needed / (
        sum(spans) * record["peaks"]["hbm_bytes_per_s"])
