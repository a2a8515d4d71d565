"""Device milliseconds a call in the kernel ``ssd_chunk_scan``: the
mixers' sequence form over the prefix, six layers x four prefill
sub-batches, found by the kernel's name in the device trace."""

from benchmark.layer_metrics import _falcon

DRIVERS = _falcon.DRIVERS


def read(record):
    return _falcon.kernel_ms_per_call(record, "ssd_chunk_scan")
