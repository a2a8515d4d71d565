"""Device milliseconds a step and chip in the Pallas CTC kernels, found
by name: Mosaic events whose ``kernel_metadata`` names ``ctc_alpha``,
``ctc_alpha_loss`` or ``ctc_gamma``
(``deepspeech_tpu/ops/kernel_id.py``). The XLA work around them (the
log-softmax, the gather to extended labels, the scatter of gamma back
to the vocabulary) is not a kernel and is not counted here."""

from benchmark.layer_metrics import _kernel_id

DRIVERS = ("train",)


def read(record):
    return _kernel_id.ms_per_step(record, _kernel_id.is_ctc)
