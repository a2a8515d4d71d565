"""Device milliseconds a step in the lattice recursions (alpha in the
forward pass, beta and the occupancies in the backward pass): the
operations whose results are rows of the lattice
(``_rnnt.classify``)."""

from benchmark.layer_metrics import _rnnt

DRIVERS = _rnnt.DRIVERS


def read(record):
    return _rnnt.ms_per_step(record, "lattice")
