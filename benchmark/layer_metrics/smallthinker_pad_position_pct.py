"""Share of the positions a step computes that are padding
(``lfm2_pad_position_pct``'s reading, for the driver ``train_long``):
padded over valid + padded positions of the window's steps, from the
program's own counters. Recordings of 5.5-7 minutes in one bucket of
6,784 positions leave a tenth or so."""

from benchmark.layer_metrics import _smallthinker, lfm2_pad_position_pct

DRIVERS = _smallthinker.DRIVERS


def read(record):
    return lfm2_pad_position_pct.read(_smallthinker.as_lfm2(record))
