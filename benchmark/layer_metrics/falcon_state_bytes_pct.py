"""The recurrent state's share of the bytes the window's decode steps
NEED to move, from the program's own counters (``decode_bytes``:
``state`` over ``weights`` + ``head`` + ``state`` + ``rows``): what a
step waits for is its streams' states as much as its weights."""

from benchmark.layer_metrics import _falcon

DRIVERS = _falcon.DRIVERS


def read(record):
    calls = _falcon.window_calls(record)
    total = sum(sum(c["decode_bytes"].values()) for c in calls)
    if not total:
        return None
    return 100.0 * sum(c["decode_bytes"]["state"] for c in calls) / total
