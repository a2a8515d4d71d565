"""Share of the positions a call computes that earn nothing: padded
prefix positions of the prefill sub-batches (a recording shorter than
the 42,000-frame bucket) and idle slots of the decode steps, over all
computed positions, from the program's own counters."""

from benchmark.layer_metrics import _trinity

DRIVERS = _trinity.DRIVERS


def read(record):
    valid = padded = 0
    for c in _trinity.window_calls(record):
        for p in (c["prefill"], c["decode"]):
            valid += p["valid_positions"]
            padded += p["padded_positions"]
    return 100.0 * padded / (valid + padded) if valid + padded else None
