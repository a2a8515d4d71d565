"""The backward attention kernels' share of the compute peak: the
operations their calls NEED (``costs/smallthinker.attn_call_cost``:
four products of ``2 x head`` a (query, key) pair in reach and query
head: dp and dq in ``gqa_attn_bwd_dq``, dv and dk in
``gqa_attn_bwd_dkv``) over the two kernels' device time by name, over
the device's published bf16 peak. Each kernel computes a tile's scores
again (and ``dkv`` its dp): that, and a tile's masked part, count for
nothing, so the kernels' own arithmetic is 7 / 4 of what is counted."""

from benchmark.layer_metrics import _smallthinker

DRIVERS = _smallthinker.DRIVERS


def read(record):
    return _smallthinker.attn_mfu_pct(record, _smallthinker.ATTN_BWD)
