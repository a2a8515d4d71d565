"""Device milliseconds a call in the kernel ``gqa_attn_decode``: one
query a stream against its 288-row cache (one row tile), six layers a
decode step, found by the kernel's name in the device trace."""

from benchmark.layer_metrics import _falcon

DRIVERS = _falcon.DRIVERS


def read(record):
    return _falcon.kernel_ms_per_call(record, "gqa_attn_decode")
