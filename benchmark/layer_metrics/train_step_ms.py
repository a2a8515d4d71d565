"""Median of the program's ``train.step`` span inside the window. With
the tracer on the loop blocks on the loss inside that span, so it is
dispatch plus the device's whole step."""

import statistics

DRIVERS = ("train",)


def read(record):
    lo, hi = record["t_window_start"], record["t_window_end"]
    durs = [(b - a) * 1e3 for n, a, b in record["spans"]
            if n == "train.step" and a >= lo and b <= hi]
    return statistics.median(durs) if durs else None
