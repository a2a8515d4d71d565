"""The driver ``transcribe_sparse`` (served recordings of 15-20 minutes:
one attention layer under a block selection beside linear-attention
layers that carry a state) for the readers: a unit is a served call,
what it needs is ``costs/minicpm_sala.py``'s and the program's own
``decode_bytes``.

A call's counters hold what ``transcribe_lm``'s do (without routing:
the stack has no expert layer) and ``select_rows_read`` /
``select_rows_held`` (cache rows the decode steps' selections read, and
the rows their streams held), ``select_windows_read`` and
``pooled_key_writes`` (pooled keys ranked and written),
``state_updates`` ((live stream, linear layer) states read and written)
and ``decode_bytes`` (what the steps needed to move, by part:
``weights``, ``head``, ``state``, ``rows``, ``select``;
``deepspeech_tpu/decode/lm_greedy.py`` ``step_bytes``). A program
without a selection has no ``select_rows_read``: the window is then
empty and the readers return None.

The four kernels of the path are named (``gqa_attn_select_fwd`` and
``ssd_chunk_scan`` in prefill, ``gqa_attn_select_decode`` and
``ssd_state_step`` in every decode step)."""

import functools

from benchmark.costs import minicpm_sala
from benchmark.layer_metrics.by_driver import _calls

INPUT_SPANS = _calls.INPUT_SPANS
CALL_SPAN = _calls.CALL_SPAN
PREFILL_SPAN = _calls.PREFILL_SPAN
DECODE_SPAN = _calls.DECODE_SPAN

# the counter by which a call of this program is known
_mine = dict(key="select_rows_read")
window = select_calls = functools.partial(_calls.window, **_mine)
decode_steps = functools.partial(_calls.decode_steps, **_mine)
cache_bytes = functools.partial(_calls.cache_bytes, **_mine)
positions = functools.partial(_calls.positions, **_mine)
slot_steps = functools.partial(_calls.slot_steps, **_mine)


def flops_needed(record):
    """Every stream's valid prefix positions and emitted tokens through
    the mixers' projections, the MLP and the head; the sparse layer's
    mixing over the SELECTED (query, key) pairs and its ranking over the
    pooled keys in reach; the linear layers' chunked recurrence on valid
    positions and their state updates; padding and idle slots count for
    nothing."""
    calls = window(record)
    if not calls:
        return None
    return sum(minicpm_sala.call_flops_valid(
        record["model"], c["valid_frames"], c["max_tokens"],
        record["counters"]["num_features"]) for c in calls)


def decode_bytes_needed(record):
    """Every layer's weights and the head once a step, each live
    (stream, linear layer)'s float32 state read once and written once,
    the selected cache rows, the pooled keys ranked."""
    return sum(sum(c["decode_bytes"].values()) for c in window(record))


def select_decode_bytes(record):
    """The SELECTED cache rows, keys and values (the program's
    ``decode_bytes["rows"]``)."""
    return sum(c["decode_bytes"]["rows"] for c in window(record))


def select_fwd_flops(record):
    """``costs/minicpm_sala.prefill_select_flops``: ``4 x heads x head``
    a SELECTED (query, key) pair of the valid prefix positions."""
    return sum(minicpm_sala.prefill_select_flops(
        record["model"], c["valid_frames"]) for c in window(record))


def scan_cost(record):
    """``costs/minicpm_sala.prefill_scan_cost`` over the window's calls:
    (flops, bytes) the linear layers' sequence form needs."""
    flops = moved = 0
    for c in window(record):
        f, b = minicpm_sala.prefill_scan_cost(record["model"],
                                              c["valid_frames"])
        flops, moved = flops + f, moved + b
    return flops, moved
