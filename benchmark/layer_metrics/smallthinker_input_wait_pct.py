"""Share of the window a long-recording training loop spent waiting for
the next batch and dispatching its transfer (``input_wait_pct``'s
reading, for the driver ``train_long``): the program's spans
``pipeline.data_wait`` + ``pipeline.device_prefetch`` over the window.
A batch is 108 MB of features."""

from benchmark.layer_metrics import input_wait_pct

DRIVERS = ("train_long",)

read = input_wait_pct.read
