"""Share of the positions a call computes that earn nothing: padded
prefix positions of the prefill sub-batches (an utterance shorter than
the 1,696-frame bucket) and idle slots of the decode steps, over all
computed positions, from the program's own counters."""

from benchmark.layer_metrics import _falcon

DRIVERS = _falcon.DRIVERS


def read(record):
    valid = padded = 0
    for c in _falcon.window_calls(record):
        for p in (c["prefill"], c["decode"]):
            valid += p["valid_positions"]
            padded += p["padded_positions"]
    return 100.0 * padded / (valid + padded) if valid + padded else None
