"""Host share of ``step()`` per tick: the benchmark's span around
``StreamingSessionManager.step`` minus the device's busy time
(``chunk_device_ms``), mean over the ticks — batch assembly, upload,
dispatch, the host read and the Python collapse loop."""

from benchmark import harness

DRIVERS = ("stream",)


def read(record):
    tr = record["trace"]
    if tr is None or not record["units"]:
        return None
    step = harness.span_seconds(record, "step")
    return 1e3 * (step - tr["busy_s"]) / record["units"]
