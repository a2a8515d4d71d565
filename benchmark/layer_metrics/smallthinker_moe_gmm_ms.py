"""Device milliseconds a step in the grouped matrix products of the
expert layers, found by kernel name: ``moe_gmm`` (forward and the
gradient to the rows) and ``moe_tgmm`` (the weights' gradients)."""

from benchmark.layer_metrics import _kernel_id, _lfm2, _smallthinker

DRIVERS = _smallthinker.DRIVERS


def read(record):
    return _kernel_id.ms_per_step(record, _lfm2.is_moe_kernel)
