"""The tiled joint + loss's share of its roofline: the least time the
chip could take for the operations and bytes the joint of the window's
steps needs (``costs/rnnt.joint_step_cost``: the output layer forward
and its two gradients over every node computed, recomputation not
counted; operands read once, results written once, no logits) over the
device time of its operations in the trace (``rnnt_joint_ms``)."""

from benchmark.costs import rnnt
from benchmark.layer_metrics import _rnnt

DRIVERS = _rnnt.DRIVERS


def read(record):
    spent = _rnnt.ms_per_step(record, "joint")
    if not spent or record["peaks"] is None:
        return None
    f = _rnnt.facts(record)
    cost = rnnt.joint_step_cost(record["model"], f["b"], f["t"], f["u1"])
    least, bound = rnnt.roofline_seconds(
        cost, record["peaks"]["bf16_flops"],
        record["peaks"]["hbm_bytes_per_s"])
    record["counters"]["rnnt_joint_bound_by"] = bound
    return 100.0 * least * 1e3 / spent
