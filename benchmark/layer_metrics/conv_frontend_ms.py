"""Device milliseconds a step and chip in the conv frontend
(``models/conv.py`` ``ConvFrontend``: the convolutions and their
gradients, its batch norms, clipped ReLU, masks and layout copies), all
directions, by the program's layer table (``_layers.py``). None where
the cell's model has no frontend."""

from benchmark.layer_metrics import _layers


def read(record):
    return _layers.ms(record, ["conv_frontend"])
