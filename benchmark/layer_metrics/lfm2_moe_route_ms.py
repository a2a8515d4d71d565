"""Device milliseconds a step in the sparse layers' routing around the
grouped products: router scores, top-4, the sort of the pairs by
expert, the gather of the rows into expert order, the weighted
scatter-add back (and their gradients), told from the rest of the step
by result shape (``_lfm2.classify``)."""

from benchmark.layer_metrics import _lfm2

DRIVERS = _lfm2.DRIVERS


def read(record):
    return _lfm2.route_ms_per_step(record)
