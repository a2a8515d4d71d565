"""Share of the decode loops' slot-steps in which the slot's stream had
already finished: the program's ``lm_idle_slot_steps`` over steps x
streams of the window's calls. The loop runs until the longest
utterance's last token (60 steps), the shortest ends after 44: a
finished stream's slot is computed, but its state is not moved and no
cache row is written."""

from benchmark.layer_metrics import _falcon

DRIVERS = _falcon.DRIVERS


def read(record):
    calls = _falcon.window_calls(record)
    slots = sum(c["decode_steps"] * c["rows"] for c in calls)
    idle = sum(c["idle_slot_steps"] for c in calls)
    return 100.0 * idle / slots if slots else None
