"""Share of the traced window in which no operation ran on the chip
(1 - union of the device-op intervals over the window), averaged over
the chips."""


def read(record):
    tr = record["trace"]
    return None if tr is None else tr["idle_pct"]
