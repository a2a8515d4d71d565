"""A decode step's share of the memory roofline: the bytes the
window's decode steps NEED (``costs/axk1.decode_step_bytes``: every
weight a position passes through and the head, the matrices of the held
experts that received a pair, the cache rows the active streams attend
to, each once; the program's own counters) over the seconds of the
``infer.decode`` spans times the device's published HBM bandwidth."""

from benchmark.costs import axk1
from benchmark.layer_metrics import _axk1

DRIVERS = _axk1.DRIVERS


def read(record):
    calls = _axk1.window_calls(record)
    spans = _axk1.span_seconds(record, "infer.decode")
    if record["peaks"] is None or not spans or not calls:
        return None
    needed = 0.0
    for c in calls:
        if c.get("experts_hit") is None:
            return None
        steps = c["decode_steps"]
        needed += steps * axk1.decode_step_bytes(
            record["model"], c["experts_hit"] / steps,
            c["cache_rows_read"] / steps)
    return 100.0 * needed / (
        sum(spans) * record["peaks"]["hbm_bytes_per_s"])
