"""Units of the window that took over 1.25 times its median unit: a
training step from its ``train.step`` span's start to the next one's, a
served call its ``infer.transcribe`` span. Must be 0: a late unit is a
stall (a collection, a retrace, the machine), and one in an untraced run
is a low reading of the rate. The lower of two middle units is the
median, so that one late unit of two still reads 1. Only a program with
the host-turn spans is read (``_host_turn.units``)."""

import statistics

from benchmark.layer_metrics import _host_turn

DRIVERS = _host_turn.DRIVERS

LATE = 1.25


def read(record):
    if not _host_turn.units(record):
        return None
    names = _host_turn.NAMES[record["driver"]]
    spans = _host_turn.in_window(record, names.unit)
    if record["driver"] in _host_turn.TRAIN:
        took = [b[0] - a[0] for a, b in zip(spans, spans[1:])]
    else:
        took = [b - a for a, b in spans]
    if not took:
        return None
    limit = LATE * statistics.median_low(took)
    return sum(1 for t in took if t > limit)
