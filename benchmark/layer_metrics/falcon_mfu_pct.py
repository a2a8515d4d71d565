"""Model FLOP utilisation of a served window of the hybrid recogniser,
the share of the whole call: the operations the window's calls NEED
(``costs/falcon_h1.py``: every stream's valid prefix positions and
emitted tokens through the mixer's and attention's projections, the MLP
and the head, attention's mixing over the keys before a position, the
mixer's chunked recurrence on valid positions and its state updates;
padding and idle slots count for nothing) per second, over chips times
the device's published bf16 peak."""

from benchmark.costs import falcon_h1
from benchmark.layer_metrics import _falcon

DRIVERS = _falcon.DRIVERS


def read(record):
    calls = _falcon.window_calls(record)
    if record["peaks"] is None or not calls:
        return None
    flops = sum(falcon_h1.call_flops_valid(
        record["model"], c["valid_frames"], c["max_tokens"],
        record["counters"]["num_features"]) for c in calls)
    window = record["t_window_end"] - record["t_window_start"]
    return 100.0 * flops / window / (
        record["chips"] * record["peaks"]["bf16_flops"])
