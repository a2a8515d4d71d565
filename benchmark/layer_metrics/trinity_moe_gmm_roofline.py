"""The grouped products' share of their roofline: the least time the
chip could take for the operations and bytes the window's calls need of
them (``costs/trinity.gmm_call_cost``, 32 groups) over the device time
of ``moe_gmm`` by name, prefill and decode calls weighed as
``axk1_moe_gmm_roofline`` does. Each kind of call is told by its facts:
``k`` and ``n`` (the up or the down product), ``m`` (the static rows: a
prefill sub-batch's or a decode step's). Bytes of TOUCHED groups only:
a prefill call needs its sub-batch's mean share of the call's prefill
pairs on that layer and the matrices of the held experts that have a
row (all 32, but for the program's count of empty ones); a step's call
needs the step's mean share of the decode pairs (about 2 a layer) and
the matrices of the experts that received one, 18.9 MB each
(bandwidth-bound). Rows of the static capacity past the routed ones and
groups without a row need nothing."""

from benchmark.costs import trinity
from benchmark.layer_metrics import _kernel_id, _trinity

DRIVERS = _trinity.DRIVERS


def read(record):
    named = _kernel_id.named_kernels(record)
    calls = _trinity.window_calls(record)
    if named is None or record["peaks"] is None or not calls:
        return None
    kinds, spent = set(), 0.0
    for facts, seconds, _ in named:
        if _trinity.is_moe_kernel(facts["kernel"]):
            spent += seconds
            kinds.add((int(facts["m"]), int(facts["k"]), int(facts["n"])))
    if not spent > 0:
        return None
    peaks = record["peaks"]
    least, bounds = 0.0, {}
    sub = -(-record["counters"]["rows_per_call"]
            // record["counters"]["prefill_rows"])
    for c in calls:
        empty = c["empty_groups"]
        for m, k, n in kinds:
            if m == c["decode"].get("rows_capacity"):
                name, part, times = "decode ", c["decode"], c["decode_steps"]
                hit = c["experts_hit"] / times / len(part["expert_pairs"])
            elif m == c["prefill"].get("rows_capacity"):
                name, part, times = "prefill ", c["prefill"], sub
                hit = empty["groups"] - empty["prefill"] / max(
                    empty["prefill_calls"], 1)
            else:
                continue
            for layer in part["expert_pairs"]:
                t, bound = trinity.roofline_seconds(
                    trinity.gmm_call_cost(k, n, hit, sum(layer) / times),
                    peaks["bf16_flops"], peaks["hbm_bytes_per_s"])
                least += times * t
                bounds[name + bound] = bounds.get(name + bound, 0) + 1
    record["counters"]["trinity_moe_gmm_bound_by"] = bounds
    return 100.0 * least / spent
