"""Device milliseconds a step and chip in the backward of the
recurrent layers' hoisted input projections (the module ``wx``: the
weight gradient ``dW_x``, the input gradient ``dX`` and the bias
gradient's sums), by the program's layer table (``_layers.py``)."""

from benchmark.layer_metrics import _layers


def read(record):
    return _layers.ms(record, ["rnn_wx"], ("bwd",))
