"""Device milliseconds a step in the tiled joint + loss, forward and
backward: the operations shaped like a tile of the joint
(``_rnnt.classify``: hidden layer, logits, softmax, their gradients and
the accumulators carried over tiles)."""

from benchmark.layer_metrics import _rnnt

DRIVERS = _rnnt.DRIVERS


def read(record):
    return _rnnt.ms_per_step(record, "joint")
