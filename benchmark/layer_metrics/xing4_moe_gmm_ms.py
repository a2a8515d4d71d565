"""Device milliseconds a call in the grouped matrix products of the
expert layers (64 groups a call), found by kernel name (``moe_gmm``):
the prefill sub-batches' and every drafting step's, the draft module's
expert layer included."""

from benchmark.layer_metrics import _kernel_id, _xing4

DRIVERS = _xing4.DRIVERS


def read(record):
    if not _xing4.window_calls(record):
        return None
    return _kernel_id.ms_per_step(record, _xing4.is_moe_kernel)
