"""Bytes of the latent cache the engine holds, GB (10^9): streams x
cache rows x (kv rank + rope) values x (layers + the draft module), as
allocated (the program's ``lm_cache_bytes``)."""

from benchmark.layer_metrics import _xing4

DRIVERS = _xing4.DRIVERS


def read(record):
    if not _xing4.window_calls(record):
        return None
    return record["counters"]["cache_bytes"] / 1e9
