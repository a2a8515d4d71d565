"""(Query, key) pairs in reach of the window's valid positions in a
windowed layer over those in a layer that sees all (every causal
pair): 100 where no sequence has passed the window, towards ``2 x
window / length`` far past it. The program's own counters
``reach_pairs_window`` and ``reach_pairs_global`` (one layer of each
kind a step)."""

from benchmark.layer_metrics import _smallthinker

DRIVERS = _smallthinker.DRIVERS


def read(record):
    steps = [s for s in _smallthinker.window_routing(record)
             if s.get("reach_pairs_global")]
    if not steps:
        return None
    return 100.0 * sum(s["reach_pairs_window"] for s in steps) / sum(
        s["reach_pairs_global"] for s in steps)
