"""Device milliseconds a call and chip in the kernel
``gqa_attn_select_fwd``: the sparse layer's sequence form, every query
under its own selection, sixteen prefill sub-batches a call; found by
the kernel's name in the device trace."""

from benchmark.layer_metrics import _kernel_id


def read(record):
    return _kernel_id.ms_per_step(
        record, lambda k: k == "gqa_attn_select_fwd")
