"""What the ``falcon_*`` readers share (the underscore keeps ``--detail``
from taking this module for a reader).

The counters are the program's own: a served call whose layers hold a
recurrent state beside a key/value cache (``decode.mode="lm_greedy"``,
layer kind "ssm_attention") returns what ``_axk1`` describes (without
routing: the stack has no expert layer) and ``state_updates`` ((live
stream, layer) states its decode steps read and wrote),
``rows_attended_global`` and ``decode_bytes`` (what the steps needed to
move, by part: ``weights``, ``head``, ``state``, ``rows``;
``deepspeech_tpu/decode/lm_greedy.py`` ``step_bytes``); the driver
``transcribe_hybrid`` keeps those of the window's calls under
``counters["calls"]``. A program without them (the parent of the PR
that added the state, or another driver's record) has no such key:
every function here then finds nothing, and the readers return None.

The three kernels of the path are NAMED (``ssd_chunk_scan`` in prefill,
``ssd_state_step`` and ``gqa_attn_decode`` in every decode step): a
reader finds their device time by name (``_kernel_id``), not by shape.
"""

from benchmark.layer_metrics import _axk1, _kernel_id

DRIVERS = ("transcribe_hybrid",)

span_seconds = _axk1.span_seconds


def window_calls(record) -> list:
    """The counters of the window's calls, or [] where the program
    reported no call with a recurrent state."""
    if record.get("driver") not in DRIVERS:
        return []
    calls = record["counters"].get("calls") or []
    return [c for c in calls if c.get("state_updates") is not None]


def kernel_seconds(record, kernel: str):
    """Device seconds of the window in the named kernel, or None where
    the trace names no kernel at all."""
    named = _kernel_id.named_kernels(record)
    if named is None or not window_calls(record):
        return None
    return sum(s for facts, s, _ in named if facts["kernel"] == kernel)


def kernel_ms_per_call(record, kernel: str):
    seconds = kernel_seconds(record, kernel)
    if seconds is None or not record["units"]:
        return None
    return 1e3 * seconds / record["units"]
