"""The new mechanisms' share of the bytes the window's decode steps
NEED to move, from the program's own counters (``decode_bytes``:
``select`` + ``rows`` + ``state`` over all parts: the pooled keys
ranked, the SELECTED rows, the states read and written, against the
weights and the head every step reads)."""

from benchmark.layer_metrics import by_driver


def read(record):
    calls = by_driver.ask(record, "select_calls") or []
    total = sum(sum(c["decode_bytes"].values()) for c in calls)
    if not total:
        return None
    return 100.0 * sum(c["decode_bytes"][k] for c in calls
                       for k in ("select", "rows", "state")) / total
