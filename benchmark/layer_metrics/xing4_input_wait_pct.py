"""Share of the window a self-drafting served loop spent waiting for
the next batch and dispatching its transfer (``input_wait_pct``'s
reading, for the driver ``transcribe_mtp``): the program's spans
``pipeline.data_wait`` + ``pipeline.device_prefetch`` over the window."""

from benchmark.layer_metrics import input_wait_pct

DRIVERS = ("transcribe_mtp",)

read = input_wait_pct.read
