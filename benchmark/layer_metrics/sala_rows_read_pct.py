"""Cache rows the decode steps' selections READ over the rows their
streams HELD in the sparse layer (the program's counters
``select_rows_read`` and ``select_rows_held``): 100 where every stream
is under ``dense_len``, ``97 blocks / rows held`` past it. What a dense
layer would read is the denominator."""

from benchmark.layer_metrics import by_driver


def read(record):
    calls = by_driver.ask(record, "select_calls")
    if not calls:
        return None
    held = sum(c["select_rows_held"] for c in calls)
    return 100.0 * sum(c["select_rows_read"] for c in calls) / held \
        if held else None
