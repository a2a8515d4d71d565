"""Mosaic kernel events in the window whose ``kernel_metadata`` names no
``kernel``: 0 while every Pallas kernel of the program is built through
``deepspeech_tpu/ops/kernel_id.py``. Above 0, the readers that find
kernels by name miss that much device time; a program from before the
identities reads as all of its Mosaic events."""

from benchmark.layer_metrics import _kernel_id

DRIVERS = ("train",)


def read(record):
    tr = record["trace"]
    if tr is None:
        return None
    return sum(1 for text, _ in tr["kernels"]
               if "kernel" not in _kernel_id.kernel_facts(text))
