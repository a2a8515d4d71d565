"""Device milliseconds a call in the grouped matrix products of the
expert layers, found by kernel name (``moe_gmm``): the prefill
sub-batches' and every decode step's."""

from benchmark.layer_metrics import _axk1, _kernel_id

DRIVERS = _axk1.DRIVERS


def read(record):
    return _kernel_id.ms_per_step(record, _axk1.is_moe_kernel)
