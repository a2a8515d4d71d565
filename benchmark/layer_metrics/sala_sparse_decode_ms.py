"""Device milliseconds a call and chip in the kernel
``gqa_attn_select_decode``: one query a stream against the SELECTED
blocks of its cache, fetched by index, every decode step; found by the
kernel's name in the device trace."""

from benchmark.layer_metrics import _kernel_id


def read(record):
    return _kernel_id.ms_per_step(
        record, lambda k: k == "gqa_attn_select_decode")
