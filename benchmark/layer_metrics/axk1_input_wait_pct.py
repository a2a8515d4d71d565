"""Share of the window a served decoder-only loop spent waiting for the
next batch and dispatching its transfer (``input_wait_pct``'s reading,
for the driver ``transcribe_lm``): the program's spans
``pipeline.data_wait`` + ``pipeline.device_prefetch`` over the window."""

from benchmark.layer_metrics import input_wait_pct

DRIVERS = ("transcribe_lm",)

read = input_wait_pct.read
