"""A decode step's share of the memory roofline: the bytes the window's
decode steps NEED (``costs/trinity.decode_step_bytes``: every weight a
position passes through and the head's slice, the matrices of the held
experts that received a pair, the cache rows IN REACH of each layer
kind, 4 kB each; the program's own counters) over the seconds of the
``infer.decode`` spans times the device's published HBM bandwidth."""

from benchmark.costs import trinity
from benchmark.layer_metrics import _trinity

DRIVERS = _trinity.DRIVERS


def read(record):
    calls = _trinity.window_calls(record)
    spans = _trinity.span_seconds(record, "infer.decode")
    if record["peaks"] is None or not spans or not calls:
        return None
    needed = 0.0
    for c in calls:
        steps = c["decode_steps"]
        rows = c["rows_attended_window"] + c["rows_attended_global"]
        needed += steps * trinity.decode_step_bytes(
            record["model"], c["experts_hit"] / steps, rows / steps)
    return 100.0 * needed / (
        sum(spans) * record["peaks"]["hbm_bytes_per_s"])
