"""Mosaic kernel events in the window of a long-recording training run
whose ``kernel_metadata`` names no ``kernel``
(``unnamed_kernel_calls``'s reading, for the driver ``train_long``): 0
while the attention's three kernels and the grouped products' two are
built through ``deepspeech_tpu/ops/kernel_id.py``. Above 0, the
readers that find kernels by name miss that much device time."""

from benchmark.layer_metrics import unnamed_kernel_calls

DRIVERS = ("train_long",)

read = unnamed_kernel_calls.read
