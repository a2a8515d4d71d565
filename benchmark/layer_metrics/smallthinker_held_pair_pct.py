"""Share of the valid positions' (position, expert) pairs that land on
experts held here (``lfm2_held_pair_pct``'s reading, for the driver
``train_long``), over the window's steps and layers: 100 * held /
(held + elsewhere). Under even routing it is held / experts = 25 for 16
of 64; what is above it is work this chip does that an even router
would have sent to another chip."""

from benchmark.layer_metrics import _smallthinker, lfm2_held_pair_pct

DRIVERS = _smallthinker.DRIVERS


def read(record):
    return lfm2_held_pair_pct.read(_smallthinker.as_lfm2(record))
