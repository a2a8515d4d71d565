"""Share of the groups of the decode steps' ``moe_gmm`` calls that have
no row: held experts of an expert layer that received no pair in a
step, over steps x layers x 32 (the program's ``empty_groups``). At 16
streams a step sends about 8 pairs to 32 held experts, so most groups
are empty and the kernel's metadata path for them is what runs."""

from benchmark.layer_metrics import _trinity

DRIVERS = _trinity.DRIVERS


def read(record):
    empty = calls = 0
    for c in _trinity.window_calls(record):
        groups = c.get("empty_groups")
        if groups:
            empty += groups["decode"]
            calls += groups["decode_calls"] * groups["groups"]
    return 100.0 * empty / calls if calls else None
