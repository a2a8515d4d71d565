"""Mosaic kernel events in the window of a served decoder-only run
whose ``kernel_metadata`` names no ``kernel``
(``unnamed_kernel_calls``'s reading, for the driver ``transcribe_lm``):
0 while ``moe_gmm`` is built through
``deepspeech_tpu/ops/kernel_id.py``. Above 0, ``axk1_moe_gmm_ms`` and
``axk1_moe_gmm_roofline`` miss that much device time."""

from benchmark.layer_metrics import unnamed_kernel_calls

DRIVERS = ("transcribe_lm",)

read = unnamed_kernel_calls.read
