"""A drafting step's share of the memory roofline: the bytes the
window's decode steps NEED (``costs/xing4.decode_step_bytes``: every
weight a position of the model and of the draft module passes through,
the head twice, the matrices of the experts that received a pair, the
cache rows the emitted tokens attend to in every array; the program's
own counters) over the seconds of the ``infer.decode`` spans times the
device's published HBM bandwidth."""

from benchmark.costs import xing4
from benchmark.layer_metrics import _xing4

DRIVERS = _xing4.DRIVERS


def read(record):
    calls = _xing4.window_calls(record)
    spans = _xing4.span_seconds(record, "infer.decode")
    if record["peaks"] is None or not spans or not calls:
        return None
    needed = 0.0
    for c in calls:
        steps = c["decode_steps"]
        needed += steps * xing4.decode_step_bytes(
            record["model"], c["experts_hit"] / steps,
            c["cache_rows_read"] / steps)
    return 100.0 * needed / (
        sum(spans) * record["peaks"]["hbm_bytes_per_s"])
