"""Device milliseconds a call in latent attention, both forms (the
decode form over a drafting step's two positions), without its output
projection, the draft module's layer included; told from the rest by
result shape (``_xing4.is_mla``)."""

from benchmark.layer_metrics import _xing4

DRIVERS = _xing4.DRIVERS


def read(record):
    return _xing4.classified_ms_per_call(record, _xing4.is_mla)
