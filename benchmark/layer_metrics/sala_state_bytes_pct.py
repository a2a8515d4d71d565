"""The linear layers' recurrent state's share of the bytes the window's
decode steps NEED to move, from the program's own counters
(``decode_bytes``: ``state`` over all parts), in a call whose program
has a selection."""

from benchmark.layer_metrics import by_driver


def read(record):
    calls = by_driver.ask(record, "select_calls") or []
    total = sum(sum(c["decode_bytes"].values()) for c in calls)
    if not total:
        return None
    return 100.0 * sum(c["decode_bytes"]["state"] for c in calls) / total
