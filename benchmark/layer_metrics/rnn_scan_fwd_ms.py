"""Device milliseconds a step and chip in the forward recurrent scan
kernels, found by name: Mosaic events whose ``kernel_metadata`` names a
``*_scan_fwd``, ``*_scan_stream``, ``*_scan_q_fwd`` or
``*_scan_q_stream`` kernel (``deepspeech_tpu/ops/kernel_id.py``),
events wholly inside the window, over chips and completed steps."""

from benchmark.layer_metrics import _kernel_id

DRIVERS = ("train",)


def read(record):
    return _kernel_id.ms_per_step(record, _kernel_id.is_scan_fwd)
