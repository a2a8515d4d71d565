"""Device milliseconds a step in the attention's forward kernel,
``gqa_attn_fwd`` by name: once a layer where the rematerialised layer
keeps its result, twice where it does not."""

from benchmark.layer_metrics import _kernel_id, _smallthinker

DRIVERS = _smallthinker.DRIVERS


def read(record):
    return _kernel_id.ms_per_step(
        record, lambda k: k in _smallthinker.ATTN_FWD)
