"""Bytes of the latent cache the engine holds, GB (10^9): streams x
cache rows x (kv rank + rope) values x layers, as allocated (the
program's ``lm_cache_bytes``)."""

from benchmark.layer_metrics import _axk1

DRIVERS = _axk1.DRIVERS


def read(record):
    if not _axk1.window_calls(record):
        return None
    return record["counters"]["cache_bytes"] / 1e9
