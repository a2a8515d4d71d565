"""Model FLOP utilisation of a self-drafting served window, the share
of the whole call: the operations the window's calls NEED
(``costs/xing4.py``: every stream's valid prefix positions and emitted
tokens through the model's attention, feed-forward, hyper-connection
products, shared expert, router and head, and the (position, expert)
pairs of the model's expert layers; padding, idle slots, rejected
drafts' verify positions and the whole draft pass count for nothing)
per second, over chips times the device's published bf16 peak."""

from benchmark.costs import xing4
from benchmark.layer_metrics import _xing4

DRIVERS = _xing4.DRIVERS


def read(record):
    calls = _xing4.window_calls(record)
    if record["peaks"] is None or not calls:
        return None
    flops = sum(xing4.call_flops_valid(
        record["model"], c["valid_frames"], c["max_tokens"],
        _xing4.model_pairs(c), record["counters"]["num_features"])
        for c in calls)
    window = record["t_window_end"] - record["t_window_start"]
    return 100.0 * flops / window / (
        record["chips"] * record["peaks"]["bf16_flops"])
