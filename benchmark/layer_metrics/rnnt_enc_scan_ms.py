"""Device milliseconds a step in the LSTM-with-projection scan kernels
(encoder and prediction net, forward and backward), found by name:
Mosaic events whose ``kernel_metadata`` names ``lstmp_scan_fwd`` or
``lstmp_scan_bwd`` (``deepspeech_tpu/ops/kernel_id.py``)."""

from benchmark.layer_metrics import _kernel_id

DRIVERS = ("train_rnnt",)


def read(record):
    return _kernel_id.ms_per_step(
        record, lambda kernel: kernel.startswith("lstmp_scan_"))
