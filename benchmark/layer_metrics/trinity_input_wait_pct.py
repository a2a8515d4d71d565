"""Share of the window a served loop on recordings of minutes spent
waiting for the next batch and dispatching its transfer
(``input_wait_pct``'s reading, for the driver ``transcribe_long``): the
program's spans ``pipeline.data_wait`` + ``pipeline.device_prefetch``
over the window. A batch is 433 MB of features."""

from benchmark.layer_metrics import input_wait_pct

DRIVERS = ("transcribe_long",)

read = input_wait_pct.read
