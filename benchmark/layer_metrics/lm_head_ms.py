"""Device milliseconds a unit (a training step, a served call) and
chip under the output head (the scope ``lm_head``: the logits product,
the training loss's log-softmax and picks, a decode step's argmax
passes), by the program's layer table (``_layers.py``)."""

from benchmark.layer_metrics import _layers


def read(record):
    return _layers.ms(record, ["lm_head"])
