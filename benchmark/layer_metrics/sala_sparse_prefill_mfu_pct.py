"""``gqa_attn_select_fwd``'s share of the compute peak: the operations
the SELECTED (query, key) pairs of the window's valid prefix positions
need (the adapter's ``select_fwd_flops``: ``4 x heads x head`` a pair)
over the kernel's device time by name, over the device's published
bf16 peak. A tile computed whose pairs the selection masks, and padded
positions, count as time and not as need."""

from benchmark.layer_metrics import _kernel_id, by_driver


def read(record):
    found = _kernel_id.calls_of(
        record, lambda k: k == "gqa_attn_select_fwd")
    flops = by_driver.ask(record, "select_fwd_flops")
    if found is None or record["peaks"] is None or not flops:
        return None
    return 100.0 * flops / (found[0] * record["peaks"]["bf16_flops"])
