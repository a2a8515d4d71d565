"""The grouped products' share of their roofline: the least time the
chip could take for the operations and bytes the window's steps need
of them (``costs/lfm2.gmm_call_cost``: each kind of call, told by its
facts ``k``, ``n``, ``groups``, ``transpose_rhs``, once per expert
layer and step over the rows that step's routing sent to that layer;
the rows of the static capacity past them, and a call made twice, need
nothing) over the device time of ``moe_gmm`` + ``moe_tgmm`` by name."""

from benchmark.costs import lfm2
from benchmark.layer_metrics import _kernel_id, _lfm2

DRIVERS = _lfm2.DRIVERS


def read(record):
    named = _kernel_id.named_kernels(record)
    if named is None or record["peaks"] is None:
        return None
    kinds, spent = set(), 0.0
    for facts, seconds, _ in named:
        if _lfm2.is_moe_kernel(facts["kernel"]):
            spent += seconds
            kinds.add((facts["kernel"], int(facts["k"]), int(facts["n"]),
                       int(facts["groups"]),
                       int(facts.get("transpose_rhs", 0))))
    if not spent > 0:
        return None
    least, bounds = 0.0, {}
    for step in _lfm2.window_routing(record):
        for layer in step["expert_pairs"]:
            for kernel, k, n, groups, _ in kinds:
                t, bound = lfm2.roofline_seconds(
                    lfm2.gmm_call_cost(kernel, k, n, groups, sum(layer)),
                    record["peaks"]["bf16_flops"],
                    record["peaks"]["hbm_bytes_per_s"])
                least += t
                bounds[bound] = bounds.get(bound, 0) + 1
    record["counters"]["lfm2_moe_gmm_bound_by"] = bounds
    return 100.0 * least / spent
