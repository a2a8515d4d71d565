"""Share of the traced window in which a collective runs on a chip
while no other operation does (averaged over the chips): the part of
the gradient exchange that compute does not hide."""

DRIVERS = ("train",)


def read(record):
    tr = record["trace"]
    if tr is None or record["chips"] < 2:
        return None
    return 100.0 * tr["collective_exposed_s"] / tr["window_s"]
