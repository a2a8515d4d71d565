"""Share of the valid positions' (position, expert) pairs that land on
experts held here, over the window's steps and expert layers: 100 *
held / (held + elsewhere). Under even routing it is held / experts =
12.5 for 8 of 64; what is above it is work this chip does that an
even router would have sent to another chip."""

from benchmark.layer_metrics import _lfm2

DRIVERS = _lfm2.DRIVERS


def read(record):
    held = elsewhere = 0
    for step in _lfm2.window_routing(record):
        held += _lfm2.pairs_held(step)
        elsewhere += sum(step["pairs_elsewhere"])
    return 100.0 * held / (held + elsewhere) if held + elsewhere else None
