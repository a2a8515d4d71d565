"""Mosaic kernel events in the window of a served run on recordings of
minutes whose ``kernel_metadata`` names no ``kernel``
(``unnamed_kernel_calls``'s reading, for the driver
``transcribe_long``): 0 while ``moe_gmm`` is built through
``deepspeech_tpu/ops/kernel_id.py``. Above 0, ``trinity_moe_gmm_ms``
and ``trinity_moe_gmm_roofline`` miss that much device time."""

from benchmark.layer_metrics import unnamed_kernel_calls

DRIVERS = ("transcribe_long",)

read = unnamed_kernel_calls.read
