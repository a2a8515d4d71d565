"""``ssd_chunk_scan``'s share of its roofline: the least time the chip
could take for what the window's prefix positions NEED
(``costs/falcon_h1.prefill_scan_cost``: on VALID positions, the causal
half of a chunk's products, a position's read of and write to the
carried state; the larger of operations over the bf16 peak and bytes
over the HBM peak) over the kernel's device time by name. Padded
positions, the masked half of a chunk and the decays' vector work count
as time and not as need."""

from benchmark.costs import falcon_h1
from benchmark.layer_metrics import _falcon

DRIVERS = _falcon.DRIVERS


def read(record):
    seconds = _falcon.kernel_seconds(record, "ssd_chunk_scan")
    if record["peaks"] is None or not seconds:
        return None
    flops = moved = 0
    for c in _falcon.window_calls(record):
        f, b = falcon_h1.prefill_scan_cost(record["model"],
                                           c["valid_frames"])
        flops, moved = flops + f, moved + b
    least, _ = falcon_h1.roofline_seconds(
        {"flops": flops, "bytes": moved}, record["peaks"]["bf16_flops"],
        record["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
