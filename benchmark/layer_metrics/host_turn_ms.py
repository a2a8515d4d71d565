"""Milliseconds of the host's turn a unit (a training step, a served
call), median over the window's units: from the end of a ``*.wait`` /
``*.fetch`` span (the host has seen the program done) to the end of the
next ``*.dispatch`` span (the next program handed over), summed over the
unit's programs: one in training; a served call's prefill sub-batches
and decode loop, the hand-over from the call before included. In a
traced run the chip idles for about this long a unit; what the device's
own idle time adds is the runtime's launch and wake-up latency."""

from benchmark.layer_metrics import _host_turn

DRIVERS = _host_turn.DRIVERS


def read(record):
    return _host_turn.unit_median_ms(record, "turn_s")
