"""Share of the decode loops' slot-steps in which the slot's stream had
already finished: the program's ``lm_idle_slot_steps`` over steps x
streams of the window's calls. The loop runs until the longest
recording's last token (1,503 steps), the shortest ends after 1,189: a
finished stream's slot is computed and not routed, and writes no cache
row."""

from benchmark.layer_metrics import _trinity

DRIVERS = _trinity.DRIVERS


def read(record):
    calls = _trinity.window_calls(record)
    slots = sum(c["decode_steps"] * c["rows"] for c in calls)
    idle = sum(c["idle_slot_steps"] for c in calls)
    return 100.0 * idle / slots if slots else None
