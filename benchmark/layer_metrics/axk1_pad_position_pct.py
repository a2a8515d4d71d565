"""Share of the positions a call computes that are padding: padded
prefix positions of the prefill sub-batches and idle slots of the
decode steps over all computed positions, from the program's own
counters. They cost every replicated layer's time and are not routed."""

from benchmark.layer_metrics import _axk1

DRIVERS = _axk1.DRIVERS


def read(record):
    valid = padded = 0
    for c in _axk1.window_calls(record):
        for p in (c["prefill"], c["decode"]):
            valid += p["valid_positions"]
            padded += p["padded_positions"]
    return 100.0 * padded / (valid + padded) if valid + padded else None
