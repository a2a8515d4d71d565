"""Bytes of the key/value cache the engine holds, GB (10^9): per stream
a ring of ``lfm_window`` rows for each sliding layer and ``cache_rows``
rows for each global layer, 4 kB a row, as allocated (the program's
``lm_cache_bytes``; ``lm_cache_bytes_window`` / ``_global`` are kept in
the run's counters)."""

from benchmark.layer_metrics import _trinity

DRIVERS = _trinity.DRIVERS


def read(record):
    if not _trinity.window_calls(record):
        return None
    return record["counters"]["cache_bytes"] / 1e9
