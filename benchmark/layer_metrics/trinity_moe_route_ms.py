"""Device milliseconds a call in the expert layers' routing around the
grouped products, prefill and decode: router scores over 256 experts,
top-4 of score + bias, the sort of the pairs by expert, the gather of
the rows into expert order, the weighted scatter-add back, told from
the rest by result shape (``_trinity.is_route``)."""

from benchmark.layer_metrics import _trinity

DRIVERS = _trinity.DRIVERS


def read(record):
    return _trinity.classified_ms_per_call(record, _trinity.is_route)
