"""Device milliseconds a step in the expert layers' routing around the
grouped products (``lfm2_moe_route_ms``'s reading, for the driver
``train_long``): the router's logits from the layer's input (before
attention), top-6, the softmax over the chosen, the sort of the pairs
by expert, the gather of the rows into expert order, the weighted
scatter-add back (and their gradients), told from the rest of the step
by result shape (``_lfm2.classify``)."""

from benchmark.layer_metrics import _lfm2, _smallthinker

DRIVERS = _smallthinker.DRIVERS


def read(record):
    return _lfm2.route_ms_per_step(_smallthinker.as_lfm2(record))
