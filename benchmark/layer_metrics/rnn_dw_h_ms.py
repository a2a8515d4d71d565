"""Device milliseconds a step and chip in the recurrent weight
gradients (the scope ``dw_h``: ``ops/scan_pallas.recurrent_dw``'s
contraction over T x B outside the time loop, one a direction and
layer; the LSTM-with-projection layer's two), by the program's layer
table (``_layers.py``)."""

from benchmark.layer_metrics import _layers


def read(record):
    return _layers.ms(record, ["rnn_dw_h"])
