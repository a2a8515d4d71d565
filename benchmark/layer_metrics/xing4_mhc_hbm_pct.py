"""The hyper-connections' share of the memory roofline: the bytes they
NEED (``costs/xing4.mhc_bytes``: each of the 16 sub-layers reads the
four streams of a valid position once and writes them once; valid
prefix positions and emitted tokens of the window's calls) over the
device time of the operations ``xing4_mhc_ms`` finds, times the
device's published HBM bandwidth. Today's passes read the streams
three times a sub-layer (norm and product, read mix, write-back), so
two thirds is what unfused code can reach; a later PR that fuses them
is held to this bound."""

from benchmark.costs import xing4
from benchmark.layer_metrics import _xing4

DRIVERS = _xing4.DRIVERS


def read(record):
    ms = _xing4.classified_ms_per_call(record, _xing4.is_mhc)
    calls = _xing4.window_calls(record)
    if record["peaks"] is None or not ms or not calls:
        return None
    positions = sum(p["valid_positions"] for c in calls
                    for p in (c["prefill"], c["decode"]))
    needed = xing4.mhc_bytes(record["model"], positions)
    seconds = 1e-3 * ms * record["units"]
    return 100.0 * needed / (seconds * record["peaks"]["hbm_bytes_per_s"])
