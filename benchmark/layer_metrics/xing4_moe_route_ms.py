"""Device milliseconds a call in the expert layers' routing around the
grouped products, prefill and decode, the draft module's layer
included: router scores, top-4 of score + bias, the sort of the pairs
by expert, the gather of the rows into expert order, the weighted
scatter-add back, told from the rest by result shape
(``_xing4.is_route``)."""

from benchmark.layer_metrics import _xing4

DRIVERS = _xing4.DRIVERS


def read(record):
    return _xing4.classified_ms_per_call(record, _xing4.is_route)
