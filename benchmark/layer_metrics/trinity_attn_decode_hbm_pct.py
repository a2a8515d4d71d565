"""The decode attention's share of the memory roofline: the cache bytes
the window's steps NEED (``costs/trinity.decode_attention_bytes``: the
rows IN REACH of each layer kind, keys and values, 4 kB a row; the
program's counters ``rows_attended_window`` + ``rows_attended_global``)
over the device time ``trinity_attn_decode_ms`` finds, over the
device's published HBM bandwidth. This is the bound a later change to
the decode attention is held to, whatever implements it: a full cache
read where a ring would do, or rows past a stream's position, count as
time and not as need."""

from benchmark.costs import trinity
from benchmark.layer_metrics import _trinity
from benchmark.layer_metrics.trinity_attn_decode_ms import read as read_ms

DRIVERS = _trinity.DRIVERS


def read(record):
    ms = read_ms(record)
    calls = _trinity.window_calls(record)
    if record["peaks"] is None or not ms or not calls:
        return None
    needed = sum(trinity.decode_attention_bytes(
        record["model"],
        c["rows_attended_window"] + c["rows_attended_global"])
        for c in calls)
    seconds = 1e-3 * ms * record["units"]
    return 100.0 * needed / (seconds * record["peaks"]["hbm_bytes_per_s"])
