"""Mosaic kernel events in the window of a served run of the hybrid
recogniser whose ``kernel_metadata`` names no ``kernel``
(``unnamed_kernel_calls``'s reading, for the driver
``transcribe_hybrid``): 0 while ``ssd_chunk_scan``, ``ssd_state_step``
and ``gqa_attn_decode`` are built through
``deepspeech_tpu/ops/kernel_id.py``. Above 0, the readers that find
them by name miss that much device time."""

from benchmark.layer_metrics import unnamed_kernel_calls

DRIVERS = ("transcribe_hybrid",)

read = unnamed_kernel_calls.read
