"""Device milliseconds a call in the kernel ``ssd_state_step``: every
live stream's state read and written in place, six layers a decode
step, found by the kernel's name in the device trace."""

from benchmark.layer_metrics import _falcon

DRIVERS = _falcon.DRIVERS


def read(record):
    return _falcon.kernel_ms_per_call(record, "ssd_state_step")
