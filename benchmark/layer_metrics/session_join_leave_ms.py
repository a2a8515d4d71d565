"""Host wall per tick spent in the session manager's ``join()`` and
``leave()`` (benchmark spans around the calls), mean over the ticks."""

from benchmark import harness

DRIVERS = ("stream",)


def read(record):
    if not record["units"]:
        return None
    return 1e3 * harness.span_seconds(record, "join", "leave") \
        / record["units"]
