"""Share of the window a decoder-only training loop spent waiting for
the next batch and dispatching its transfer (``input_wait_pct``'s
reading, for the driver ``train_lfm2``): the program's spans
``pipeline.data_wait`` + ``pipeline.device_prefetch`` over the window."""

from benchmark.layer_metrics import input_wait_pct

DRIVERS = ("train_lfm2",)

read = input_wait_pct.read
