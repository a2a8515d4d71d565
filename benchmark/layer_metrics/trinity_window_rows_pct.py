"""Cache rows the decode steps attended to in the windowed layers over
what those layers would have read from a full cache (the rows the
global layers' steps attended to, a layer): 100 where no stream has
passed the window, ``window / position`` of it far past it. The
program's own counters ``rows_attended_window`` and
``rows_attended_global``."""

from benchmark.layer_metrics import _trinity

DRIVERS = _trinity.DRIVERS


def read(record):
    kinds = record["model"].lfm_layer_types
    sliding = sum(k == "sliding_attention" for k in kinds)
    full = sum(k == "full_attention" for k in kinds)
    calls = _trinity.window_calls(record)
    if not calls or not sliding or not full:
        return None
    window = sum(c["rows_attended_window"] for c in calls) / sliding
    everything = sum(c["rows_attended_global"] for c in calls) / full
    return 100.0 * window / everything if everything else None
