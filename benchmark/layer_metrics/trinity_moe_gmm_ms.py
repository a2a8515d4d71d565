"""Device milliseconds a call in the grouped matrix products of the
expert layers (32 groups a call), found by kernel name (``moe_gmm``):
the prefill sub-batches' and every decode step's."""

from benchmark.layer_metrics import _kernel_id, _trinity

DRIVERS = _trinity.DRIVERS


def read(record):
    if not _trinity.window_calls(record):
        return None
    return _kernel_id.ms_per_step(record, _trinity.is_moe_kernel)
