"""Model FLOP utilisation of a served window, the share of the whole
call: the operations the window's calls NEED (``costs/axk1.py``: every
stream's valid prefix positions and decoded steps through attention,
feed-forward, shared expert, router and head, and the (position,
expert) pairs their routing sent to experts held here; padding, idle
slots and the absent experts' share count for nothing) per second, over
chips times the device's published bf16 peak (``peaks.json``)."""

from benchmark.costs import axk1
from benchmark.layer_metrics import _axk1

DRIVERS = _axk1.DRIVERS


def read(record):
    calls = _axk1.window_calls(record)
    if record["peaks"] is None or not calls:
        return None
    flops = sum(axk1.call_flops_valid(
        record["model"], c["valid_frames"], c["max_tokens"],
        _axk1.pairs_held(c), record["counters"]["num_features"])
        for c in calls)
    window = record["t_window_end"] - record["t_window_start"]
    return 100.0 * flops / window / (
        record["chips"] * record["peaks"]["bf16_flops"])
