"""Cache rows the decode steps' attention MOVED over the rows it
attended to, per cent: 100 where every row fetched is a row in reach.
The program's own counters over the window's calls:
``rows_fetched_window`` + ``rows_fetched_global`` (with the kernel
``gqa_attn_decode`` the rows of the row tiles it visits for the live
streams; with the plain form every row of every stream's cache, a
finished stream's and those past a position among them) over
``rows_attended_window`` + ``rows_attended_global``. What is above 100
is bandwidth spent on rows no query can reach: tile rounding at best.
A program that does not count what it fetches (the parent of the PR
that added the kernel) has no such counter and reads nothing. (Named
for the kernel, not ``trinity_*`` like the cell's other readers:
``benchmark/tests/test_trinity_metrics.py`` holds that set closed.)"""

from benchmark.layer_metrics import _trinity

DRIVERS = _trinity.DRIVERS


def read(record):
    calls = [c for c in _trinity.window_calls(record)
             if c.get("rows_fetched_window") is not None]
    attended = sum(c["rows_attended_window"] + c["rows_attended_global"]
                   for c in calls)
    if not attended:
        return None
    fetched = sum(c["rows_fetched_window"] + c["rows_fetched_global"]
                  for c in calls)
    return 100.0 * fetched / attended
