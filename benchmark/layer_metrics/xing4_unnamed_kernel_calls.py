"""Mosaic kernel events in the window of a self-drafting served run
whose ``kernel_metadata`` names no ``kernel``
(``unnamed_kernel_calls``'s reading, for the driver
``transcribe_mtp``): 0 while ``moe_gmm`` is built through
``deepspeech_tpu/ops/kernel_id.py``. Above 0, ``xing4_moe_gmm_ms`` and
``xing4_moe_gmm_roofline`` miss that much device time."""

from benchmark.layer_metrics import unnamed_kernel_calls

DRIVERS = ("transcribe_mtp",)

read = unnamed_kernel_calls.read
