"""Tokens an ACTIVE stream emits a step of the drafting loop: the
emitted tokens of the window's calls over their active slot-steps
(steps x streams - idle slots). 1.0 with every draft rejected, 2.0 with
every draft accepted."""

from benchmark.layer_metrics import _xing4

DRIVERS = _xing4.DRIVERS


def read(record):
    calls = _xing4.window_calls(record)
    active = sum(c["decode_steps"] * c["rows"] - c["idle_slot_steps"]
                 for c in calls)
    tokens = sum(c["decode"]["valid_positions"] for c in calls)
    return tokens / active if active else None
