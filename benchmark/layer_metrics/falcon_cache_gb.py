"""Bytes of the whole cache the engine holds, GB (10^9): keys and
values (288 rows of 2 kB a stream and layer), the recurrent state and
the convolution's last three inputs, as allocated."""

from benchmark.layer_metrics import _falcon

DRIVERS = _falcon.DRIVERS


def read(record):
    if not _falcon.window_calls(record):
        return None
    return record["counters"]["cache_bytes"] / 1e9
