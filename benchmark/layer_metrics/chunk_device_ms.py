"""Device busy time per tick of the streaming engine: the trace's busy
union over the ticks completed in the traced window."""

DRIVERS = ("stream",)


def read(record):
    tr = record["trace"]
    if tr is None or not record["units"]:
        return None
    return 1e3 * tr["busy_s"] / record["units"]
