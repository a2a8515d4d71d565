"""``ssd_state_step``'s share of the memory roofline: the bytes the
window's steps NEED (``costs/falcon_h1.step_bytes``: a live (stream,
layer)'s float32 state read once and written once; the program's
counter ``state_updates``) over the kernel's device time by name, over
the device's published HBM bandwidth. A state moved for a stream that
is not live, or moved twice, counts as time and not as need."""

from benchmark.costs import falcon_h1
from benchmark.layer_metrics import _falcon

DRIVERS = _falcon.DRIVERS


def read(record):
    seconds = _falcon.kernel_seconds(record, "ssd_state_step")
    if record["peaks"] is None or not seconds:
        return None
    needed = falcon_h1.step_bytes(record["model"]) * sum(
        c["state_updates"] for c in _falcon.window_calls(record))
    return 100.0 * needed / (seconds * record["peaks"]["hbm_bytes_per_s"])
