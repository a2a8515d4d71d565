"""Bytes of recurrent state the engine holds, GB (10^9): per stream and
layer 32 heads x 256 x 128 float32 = 4.19 MB, as allocated (the
program's gauge ``lm_cache_bytes_state``)."""

from benchmark.layer_metrics import _falcon

DRIVERS = _falcon.DRIVERS


def read(record):
    if not _falcon.window_calls(record):
        return None
    return record["counters"]["cache_bytes_state"] / 1e9
