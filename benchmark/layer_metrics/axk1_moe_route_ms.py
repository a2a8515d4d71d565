"""Device milliseconds a call in the expert layers' routing around the
grouped products, prefill and decode: router scores, group selection,
top-8, the sort of the pairs by expert, the gather of the rows into
expert order, the weighted scatter-add back, told from the rest by
result shape (``_axk1.is_route``)."""

from benchmark.layer_metrics import _axk1

DRIVERS = _axk1.DRIVERS


def read(record):
    return _axk1.classified_ms_per_call(record, _axk1.is_route)
