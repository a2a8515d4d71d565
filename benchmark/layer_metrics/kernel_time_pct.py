"""Share of the device's busy time spent in Mosaic (Pallas) kernels:
trace events whose instruction holds the ``tpu_custom_call`` target."""


def read(record):
    tr = record["trace"]
    if tr is None or not tr["busy_s"] > 0:
        return None
    return 100.0 * tr["kernel_s"] / tr["busy_s"]
