"""Model FLOP utilisation of a served window on recordings of minutes,
the share of the whole call: the operations the window's calls NEED
(``costs/trinity.py``: every stream's valid prefix positions and
emitted tokens through attention's projections and gate, the
feed-forwards, shared expert, router and head, attention's mixing over
the KEYS IN REACH of each layer kind, and the (position, expert) pairs
on the experts held here; padding, idle slots and the absent experts'
share count for nothing) per second, over chips times the device's
published bf16 peak."""

from benchmark.costs import trinity
from benchmark.layer_metrics import _trinity

DRIVERS = _trinity.DRIVERS


def read(record):
    calls = _trinity.window_calls(record)
    if record["peaks"] is None or not calls:
        return None
    flops = sum(trinity.call_flops_valid(
        record["model"], c["valid_frames"], c["max_tokens"],
        _trinity.pairs_held(c), record["counters"]["num_features"])
        for c in calls)
    window = record["t_window_end"] - record["t_window_start"]
    return 100.0 * flops / window / (
        record["chips"] * record["peaks"]["bf16_flops"])
