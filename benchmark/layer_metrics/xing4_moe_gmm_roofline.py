"""The grouped products' share of their roofline: the least time the
chip could take for the operations and bytes the window's calls need of
them (``costs/xing4.gmm_call_cost``, 64 groups) over the device time of
``moe_gmm`` by name. Each kind of call is told by its facts: ``k`` and
``n`` (the up or the down product), ``m`` (the static rows: a prefill
sub-batch's or a drafting step's). A prefill call needs its sub-batch's
mean share of the call's prefill pairs on that layer and all 64
experts' matrices; a step's call needs the step's mean share of the
decode pairs and the matrices of the experts that received one
(bandwidth-bound: 32 rows an expert at most). The draft module's expert
layer is a layer like the others here: its products run, by the same
kernel, whatever the draft is worth. Rows of the static capacity past
the routed ones need nothing."""

from benchmark.costs import xing4
from benchmark.layer_metrics import _kernel_id, _xing4

DRIVERS = _xing4.DRIVERS


def read(record):
    named = _kernel_id.named_kernels(record)
    calls = _xing4.window_calls(record)
    if named is None or record["peaks"] is None or not calls:
        return None
    kinds, spent = set(), 0.0
    for facts, seconds, _ in named:
        if _xing4.is_moe_kernel(facts["kernel"]):
            spent += seconds
            kinds.add((int(facts["m"]), int(facts["k"]), int(facts["n"])))
    if not spent > 0:
        return None
    peaks = record["peaks"]
    least, bounds = 0.0, {}
    sub = -(-record["counters"]["rows_per_call"]
            // record["counters"]["prefill_rows"])
    for c in calls:
        for m, k, n in kinds:
            if m == c["decode"].get("rows_capacity"):
                part, times = c["decode"], c["decode_steps"]
                hit = c["experts_hit"] / times / len(part["expert_pairs"])
            elif m == c["prefill"].get("rows_capacity"):
                part, times = c["prefill"], sub
                hit = None
            else:
                continue
            for layer in part["expert_pairs"]:
                t, bound = xing4.roofline_seconds(
                    xing4.gmm_call_cost(
                        k, n, len(layer) if hit is None else hit,
                        sum(layer) / times),
                    peaks["bf16_flops"], peaks["hbm_bytes_per_s"])
                least += times * t
                key = ("decode " if hit is not None else "prefill ") + bound
                bounds[key] = bounds.get(key, 0) + 1
    record["counters"]["xing4_moe_gmm_bound_by"] = bounds
    return 100.0 * least / spent
