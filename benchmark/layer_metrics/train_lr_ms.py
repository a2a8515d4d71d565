"""Median of the program's ``train.lr`` span inside the window: the
learning-rate schedule evaluated for the log line and read back as a
float, at every logged step (the cells log every step)."""

from benchmark.layer_metrics import _host_turn

DRIVERS = _host_turn.TRAIN


def read(record):
    return _host_turn.span_median_ms(record, "train.lr")
