"""Share of the valid positions' (position, expert) pairs that land on
experts held here, over the window's calls (prefill and decode) and
expert layers: 100 * held / (held + elsewhere). Under even routing it
is held / experts = 12.5 for 32 of 256; what is above it is work this
chip does that an even router would have sent to another chip."""

from benchmark.layer_metrics import _trinity

DRIVERS = _trinity.DRIVERS


def read(record):
    held = elsewhere = 0
    for c in _trinity.window_calls(record):
        held += _trinity.pairs_held(c)
        elsewhere += sum(sum(p["pairs_elsewhere"])
                         for p in _trinity.parts(c))
    return 100.0 * held / (held + elsewhere) if held + elsewhere else None
