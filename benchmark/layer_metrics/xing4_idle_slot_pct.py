"""Share of the drafting loops' slot-steps in which the slot's stream
had already finished: the program's ``lm_idle_slot_steps`` over steps
x streams of the window's calls. The loop runs until the slowest stream
ends; streams now finish after different numbers of STEPS as well as of
tokens, and a finished stream's slot is computed and not routed."""

from benchmark.layer_metrics import _xing4

DRIVERS = _xing4.DRIVERS


def read(record):
    calls = _xing4.window_calls(record)
    slots = sum(c["decode_steps"] * c["rows"] for c in calls)
    idle = sum(c["idle_slot_steps"] for c in calls)
    return 100.0 * idle / slots if slots else None
