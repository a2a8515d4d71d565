"""Device milliseconds a call and chip in the kernel ``ssd_state_step``
as the linear-attention layers run it (several single-head groups a
grid step): every live stream's state read and written in place, three
layers a decode step; found by the kernel's name in the device trace,
in a call of the driver whose program has a selection."""

from benchmark.layer_metrics import _kernel_id, by_driver


def read(record):
    if not by_driver.ask(record, "select_calls"):
        return None
    return _kernel_id.ms_per_step(record, lambda k: k == "ssd_state_step")
