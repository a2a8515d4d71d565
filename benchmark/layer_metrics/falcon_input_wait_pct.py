"""Share of the window a served loop of the hybrid recogniser spent
waiting for the next batch and dispatching its transfer
(``input_wait_pct``'s reading, for the driver ``transcribe_hybrid``):
the program's spans ``pipeline.data_wait`` + ``pipeline.device_prefetch``
over the window. A batch is 140 MB of features."""

from benchmark.layer_metrics import input_wait_pct

DRIVERS = ("transcribe_hybrid",)

read = input_wait_pct.read
