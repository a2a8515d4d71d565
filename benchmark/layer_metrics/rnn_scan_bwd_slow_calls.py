"""Backward scan calls a step and chip that take more than 1.25 times
the fastest call with identical facts (same kernel, variant, direction
and sizes: the same work). PERF.md's finding: of 14 such calls a step,
some take 25 ms and the others 14.7 ms for the same shapes, and how
many are slow differs between one chip and four.

Which calls they are is kept in the run's counters
(``rnn_scan_bwd_call_sites``): one entry per call site of the step in
its order of time, with its instruction name (``%gru_scan_bwd.17``),
``reverse``, the median of its calls, whether it counts as slow, and
whether XLA placed the recurrent weights in VMEM before the call (the
operand ``[h, >= gates*h]`` carries ``S(1)`` in its layout) or left
them in HBM."""

import re
import statistics

from benchmark.layer_metrics import _kernel_id

DRIVERS = ("train",)

SLOW = 1.25


def read(record):
    named = _kernel_id.named_kernels(record)
    if named is None or not record["units"]:
        return None
    sites = {}  # instruction text -> (facts, seconds of each call)
    for facts, seconds, text in named:
        if _kernel_id.is_scan_bwd(facts["kernel"]):
            sites.setdefault(text, (facts, []))[1].append(seconds)
    fastest = {}  # the same work -> its fastest call
    for facts, calls in sites.values():
        work = tuple(sorted(facts.items()))
        fastest[work] = min(calls + [fastest.get(work, calls[0])])

    def limit(facts):
        return SLOW * fastest[tuple(sorted(facts.items()))]

    record["counters"]["rnn_scan_bwd_call_sites"] = [
        {"instruction": text.split(" = ", 1)[0],
         "reverse": facts.get("reverse"), "calls": len(calls),
         "median_ms": 1e3 * statistics.median(calls),
         "slow": statistics.median(calls) > limit(facts),
         "weights_in_vmem": weights_in_vmem(text, facts)}
        for text, (facts, calls) in sites.items()]
    slow = sum(s > limit(facts)
               for facts, calls in sites.values() for s in calls)
    return slow / record["chips"] / record["units"]


def weights_in_vmem(text: str, facts: dict):
    """Whether the call's weight operand is annotated ``S(1)``; None
    where the text shows no such operand."""
    if not facts.get("h", "").isdigit():
        return None
    operands = text.partition(" custom-call(")[2].partition(
        "), custom_call_target")[0]
    m = re.search(rf"\w+\[{facts['h']},\d+\]\{{([^}}]*)\}}", operands)
    return None if m is None else "S(1)" in m.group(1)
