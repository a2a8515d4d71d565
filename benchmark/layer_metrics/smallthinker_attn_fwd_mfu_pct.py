"""The forward attention kernel's share of the compute peak: the
operations its calls NEED (``costs/smallthinker.attn_call_cost``: two
products of ``2 x head`` a (query, key) pair IN REACH of the call's
positions and query head, by the call's own facts: all causal pairs in
a layer that sees all, ``min(i + 1, window)`` keys a query in a sliding
one) over the device time of ``gqa_attn_fwd`` by name, over the
device's published bf16 peak. The masked part of a tile on the diagonal
or the window's edge counts for nothing."""

from benchmark.layer_metrics import _smallthinker

DRIVERS = _smallthinker.DRIVERS


def read(record):
    return _smallthinker.attn_mfu_pct(record, _smallthinker.ATTN_FWD)
