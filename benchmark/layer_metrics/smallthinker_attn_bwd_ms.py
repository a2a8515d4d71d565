"""Device milliseconds a step in the attention's backward kernels,
``gqa_attn_bwd_dq`` + ``gqa_attn_bwd_dkv`` by name."""

from benchmark.layer_metrics import _kernel_id, _smallthinker

DRIVERS = _smallthinker.DRIVERS


def read(record):
    return _kernel_id.ms_per_step(
        record, lambda k: k in _smallthinker.ATTN_BWD)
