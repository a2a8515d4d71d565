"""``gqa_attn_select_decode``'s share of the memory roofline: the bytes
the window's steps NEED (the adapter's ``select_decode_bytes``: the
SELECTED rows, keys and values, from the program's counter
``select_rows_read``) over the kernel's device time by name, over the
device's published HBM bandwidth. A block fetched that the selection
did not choose, or the whole cache, counts as time and not as need."""

from benchmark.layer_metrics import _kernel_id, by_driver


def read(record):
    found = _kernel_id.calls_of(
        record, lambda k: k == "gqa_attn_select_decode")
    needed = by_driver.ask(record, "select_decode_bytes")
    if found is None or record["peaks"] is None or not needed:
        return None
    return 100.0 * needed / (
        found[0] * record["peaks"]["hbm_bytes_per_s"])
