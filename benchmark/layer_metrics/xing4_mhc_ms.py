"""Device milliseconds a call in the hyper-connections of all 16
sub-layers (7 layers and the draft module, two each), prefill and
decode: the coefficient path (the norm over the four streams, the
[N, 14336] x [14336, 24] product, sigmoids, 20 Sinkhorn rounds on
[4, 4, N]) and the mixes over the streams, told from the rest by result
shape (``_xing4.is_mhc``). The read mix is missing where the compiler
fuses it into the sub-layer's own norm."""

from benchmark.layer_metrics import _xing4

DRIVERS = _xing4.DRIVERS


def read(record):
    return _xing4.classified_ms_per_call(record, _xing4.is_mhc)
