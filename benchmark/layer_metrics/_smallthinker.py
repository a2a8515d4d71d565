"""What the ``smallthinker_*`` readers share (the underscore keeps
``--detail`` from taking this module for a reader).

The driver ``train_long`` keeps, for each step of the window, the
program's own routing counters (``deepspeech_tpu/obs/routing.py``:
pairs on each held expert per layer, pairs elsewhere, valid and padded
positions, and, where the layers have a window, the (query, key) pairs
in reach of the valid positions in one layer of each kind) under
``counters["routing"]``. A program without them, or another driver's
record, has no such key: every function here then finds nothing, and
the readers return None.

The attention's three kernels and the grouped products' two are named
(``ops/kernel_id.py``); a call's layer kind is its fact ``window`` (0:
the layer sees all). The routing around the grouped products is XLA
code without an identity and is told by result shape, as
``_lfm2.classify`` tells it (the router of this family reads the
layer's input BEFORE attention, scope ``moe_route_pre_attn``; its
arrays have the same shapes)."""

from benchmark.costs import smallthinker
from benchmark.layer_metrics import _kernel_id, _lfm2

DRIVERS = ("train_long",)

ATTN_FWD = ("gqa_attn_fwd",)
ATTN_BWD = ("gqa_attn_bwd_dq", "gqa_attn_bwd_dkv")


def as_lfm2(record) -> dict:
    """This driver's record as the ``lfm2_*`` readers take one: it
    holds ``train_lfm2``'s fields under the same names (the routing
    counters, ``rows_per_step``, ``seq_positions``), so the readers of
    routing, grouped products and padding are theirs, on this view.
    Another driver's record stays another's."""
    if record.get("driver") not in DRIVERS:
        return record
    return dict(record, driver=_lfm2.DRIVERS[0])


def window_routing(record) -> list:
    """The routing counters of the window's steps, or [] where the
    program reported none."""
    return _lfm2.window_routing(as_lfm2(record))


def attn_mfu_pct(record, kernels: tuple):
    """The operations the window's calls of ``kernels`` need
    (``costs/smallthinker.attn_call_cost`` from each call's own facts)
    over their device seconds and the bf16 peak, in per cent; None
    where no such call is named."""
    named = _kernel_id.named_kernels(record)
    if named is None or record["peaks"] is None:
        return None
    seconds = flops = 0.0
    for facts, s, _ in named:
        if facts["kernel"] in kernels:
            seconds += s
            flops += smallthinker.attn_call_cost(facts)["flops"]
    if not seconds > 0:
        return None
    return 100.0 * flops / (seconds * record["peaks"]["bf16_flops"])
