"""Device milliseconds a step and chip in the optimizer (the scopes
``optimizer``: clipping, the update rule, ``apply_updates``, a layout
copy of its state; and ``grad_norm``, which XLA mostly merges into the
clipping's own norm), by the program's layer table (``_layers.py``)."""

from benchmark.layer_metrics import _layers


def read(record):
    return _layers.ms(record, ["optimizer", "grad_norm"])
