"""``ssd_chunk_scan``'s share of its roofline in the linear-attention
layers: the least time the chip could take for what the window's prefix
positions NEED (the adapter's ``scan_cost``:
``costs/minicpm_sala.prefill_scan_cost``, on VALID positions, the
causal half of a chunk's products, a position's read of and write to
the carried state; the larger of operations over the bf16 peak and
bytes over the HBM peak) over the kernel's device time by name."""

from benchmark.costs import minicpm_sala
from benchmark.layer_metrics import _kernel_id, by_driver


def read(record):
    cost = by_driver.ask(record, "scan_cost")
    seconds = _kernel_id.kernel_seconds(record, "ssd_chunk_scan")
    if record["peaks"] is None or not seconds or not cost \
            or not by_driver.ask(record, "select_calls"):
        return None
    least, _ = minicpm_sala.roofline_seconds(
        {"flops": cost[0], "bytes": cost[1]},
        record["peaks"]["bf16_flops"], record["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
