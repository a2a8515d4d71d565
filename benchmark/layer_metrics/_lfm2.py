"""What the ``lfm2_*`` readers share (the underscore keeps ``--detail``
from taking this module for a reader).

The routing counters are the program's own: every ``train_step`` event
of an ``objective="lm"`` run carries, per expert layer, the pairs on
each held expert and the pairs routed elsewhere, and the step's valid
and padded positions (``deepspeech_tpu/obs/routing.py``); the driver
keeps those of the window's steps under ``counters["routing"]``. A
program without them (or another driver's record) has no such key:
every function here then finds nothing, and the readers return None.

The grouped products are named kernels (``moe_gmm``, ``moe_tgmm``).
The routing around them is XLA code without an identity, so it is told
from the rest of the step by the SHAPES in an event's short name
(``reduce/xplane.short_name``: instruction, opcode, result shapes), as
``_rnnt.classify`` does, with N = rows x positions computed, k = top-k,
E = the router's width, M = the dispatch's static rows, D = hidden:

  route  a result shaped [N, E] or [N, k] (scores, top-k, weights and
         their gradients), [N*k] or [N*k, G+1] (the pairs' keys, their
         sort, the count per expert), [M] (the rows' positions and
         weights), [M, D] (the rows gathered into expert order, the
         weighted rows on their way back, and both gradients) or the
         two-dimensional [N, D] (the scatter-add into positions and the
         scatter-add of the gathered rows' gradient: the rest of the
         model keeps its activations as [B, S, D])

Not counted here or under the kernels: the experts' element-wise work
between the two grouped products ([M, F] and [M, 2F] results).

Control-flow instructions span their bodies' events and are skipped.
"""

from benchmark.layer_metrics._rnnt import parse

DRIVERS = ("train_lfm2",)

_CONTROL = ("while", "conditional", "call")


def is_moe_kernel(kernel: str) -> bool:
    return kernel in ("moe_gmm", "moe_tgmm")


def window_routing(record) -> list:
    """The routing counters of the window's steps, or [] where the
    program reported none."""
    if record.get("driver") not in DRIVERS:
        return []
    steps = record["counters"].get("routing") or []
    return [s for s in steps if s.get("expert_pairs") is not None]


def pairs_held(step: dict) -> int:
    return sum(sum(layer) for layer in step["expert_pairs"])


def facts(record) -> dict:
    c, m = record["counters"], record["model"]
    steps = window_routing(record)
    n = c["rows_per_step"] * c["seq_positions"]
    return {"n": n, "k": m.lfm_top_k, "e": m.lfm_experts,
            "g": m.experts_held, "d": m.lfm_hidden,
            "m": steps[0]["rows_capacity"] if steps else None}


def classify(key: str, f: dict):
    """'route' or None for an event's short name."""
    opcode, shapes = parse(key)
    if opcode in _CONTROL:
        return None
    n, k, e, g, d, m = (f[x] for x in "nkegdm")
    route = ((n, e), (n, k), (n * k,), (n * k, g + 1), (m,), (m, d),
             (n, d))
    return "route" if any(s in route for s in shapes) else None


def route_ms_per_step(record):
    tr = record["trace"]
    if tr is None or not record["units"] or not window_routing(record):
        return None
    f = facts(record)
    seconds = sum(s for key, s in tr["op_seconds"].items()
                  if classify(key, f) == "route")
    return 1e3 * seconds / record["units"]
