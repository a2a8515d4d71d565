"""Milliseconds a unit (a training step, a served call) the host spends
inside the jitted programs' calls, from the call to its return: the
unit's ``train.dispatch`` span, or a served call's
``infer.prefill.dispatch`` spans and its ``infer.decode.dispatch``
(argument conversion included), summed; median over the window's
units. Part of ``host_turn_ms``."""

from benchmark.layer_metrics import _host_turn

DRIVERS = _host_turn.DRIVERS


def read(record):
    return _host_turn.unit_median_ms(record, "dispatch_s")
