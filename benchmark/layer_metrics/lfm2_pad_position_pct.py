"""Share of the positions a step computes that are padding: padded
over valid + padded positions of the window's steps, from the
program's own counters. Padded positions cost every replicated layer's
matmul time, are not routed and earn nothing."""

from benchmark.layer_metrics import _lfm2

DRIVERS = _lfm2.DRIVERS


def read(record):
    valid = padded = 0
    for step in _lfm2.window_routing(record):
        valid += step["valid_positions"]
        padded += step["padded_positions"]
    return 100.0 * padded / (valid + padded) if valid + padded else None
