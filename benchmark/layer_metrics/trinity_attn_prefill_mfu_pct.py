"""The prefill attention's share of the compute peak: the mixing
operations the window's prefix positions NEED
(``costs/trinity.prefill_attention_flops``: ``4 x heads x head`` a key
IN REACH, ``p + 1`` keys in a global layer and ``min(p + 1, window)``
in a sliding one; valid positions only) over the device time
``trinity_attn_prefill_ms`` finds, over the device's published bf16
peak. Blocks of 512 queries compute up to 511 masked keys a row beside
the needed ones, and padded positions count for nothing."""

from benchmark.costs import trinity
from benchmark.layer_metrics import _trinity
from benchmark.layer_metrics.trinity_attn_prefill_ms import read as read_ms

DRIVERS = _trinity.DRIVERS


def read(record):
    ms = read_ms(record)
    calls = _trinity.window_calls(record)
    if record["peaks"] is None or not ms or not calls:
        return None
    flops = sum(trinity.prefill_attention_flops(
        record["model"], c["valid_frames"]) for c in calls)
    seconds = 1e-3 * ms * record["units"]
    return 100.0 * flops / (seconds * record["peaks"]["bf16_flops"])
