"""Device milliseconds a call and chip under the sparse layer's
SELECTION (the scope ``sparse_select``: pooled keys, the queries'
scores against them, pooling to blocks, top-k, the decode kernel's
index list), prefill and every decode step, by the program's layer
table (``_layers.py``). A program from before the layer had its name
(``obs.layers.LAYERS`` lacks it: the parent of the PR that added it)
has nothing to read: None, not an error."""

from benchmark.layer_metrics import _layers


def read(record):
    try:
        return _layers.ms(record, ["sparse_select"])
    except ValueError:          # the program does not know the layer
        return None
