"""The LSTM-with-projection scan kernels' share of their roofline: the
least time the chip could take for the operations and bytes each call
needs (``costs/rnnt.lstmp_scan_cost``, from the call's own facts
``t``, ``b``, ``h``, ``p``) over the device time of those kernels,
found by name (``lstmp_scan_fwd``, ``lstmp_scan_bwd``)."""

from benchmark.costs import rnnt
from benchmark.layer_metrics import _kernel_id

DRIVERS = ("train_rnnt",)


def read(record):
    named = _kernel_id.named_kernels(record)
    if named is None or record["peaks"] is None:
        return None
    least = spent = 0.0
    bounds = {}
    for facts, seconds, _ in named:
        if not facts["kernel"].startswith("lstmp_scan_"):
            continue
        cost = rnnt.lstmp_scan_cost(
            record["model"], int(facts["h"]), int(facts["b"]),
            int(facts["t"]), backward=facts["kernel"].endswith("_bwd"))
        t, bound = rnnt.roofline_seconds(
            cost, record["peaks"]["bf16_flops"],
            record["peaks"]["hbm_bytes_per_s"])
        least += t
        spent += seconds
        bounds[bound] = bounds.get(bound, 0) + 1
    record["counters"]["rnnt_lstmp_bound_by"] = bounds
    return 100.0 * least / spent if spent > 0 else None
