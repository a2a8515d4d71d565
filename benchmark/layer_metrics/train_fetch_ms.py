"""Median of the program's ``train.fetch`` span inside the window: the
logged step's loss and gradient norm read back as floats and, in the
``lm`` objective, the routing counters fetched and counted
(``obs.observe_routing``)."""

from benchmark.layer_metrics import _host_turn

DRIVERS = _host_turn.TRAIN


def read(record):
    return _host_turn.span_median_ms(record, "train.fetch")
