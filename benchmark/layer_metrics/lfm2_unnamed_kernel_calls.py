"""Mosaic kernel events in the window of a decoder-only training run
whose ``kernel_metadata`` names no ``kernel``
(``unnamed_kernel_calls``'s reading, for the driver ``train_lfm2``): 0
while ``moe_gmm`` and ``moe_tgmm`` are built through
``deepspeech_tpu/ops/kernel_id.py``. Above 0, ``lfm2_moe_gmm_ms`` and
``lfm2_moe_gmm_roofline`` miss that much device time."""

from benchmark.layer_metrics import unnamed_kernel_calls

DRIVERS = ("train_lfm2",)

read = unnamed_kernel_calls.read
