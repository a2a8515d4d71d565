"""Share of the positions a call computes that earn nothing: padded
prefix positions of the prefill sub-batches, and of the 2 x streams
positions of every drafting step those whose token was not emitted
(idle slots, rejected drafts, the second position of a stream's last
token), over all computed positions, from the program's own counters."""

from benchmark.layer_metrics import _xing4

DRIVERS = _xing4.DRIVERS


def read(record):
    valid = padded = 0
    for c in _xing4.window_calls(record):
        for p in (c["prefill"], c["decode"]):
            valid += p["valid_positions"]
            padded += p["padded_positions"]
    return 100.0 * padded / (valid + padded) if valid + padded else None
