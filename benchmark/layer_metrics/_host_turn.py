"""What the readers of the host's turn share (the underscore keeps
``--detail`` from taking this module for a reader).

The host's turn is the interval between two device programs: from the
moment the host has seen a program done to the moment the next one is
handed over. The program opens it up with child spans
(``deepspeech_tpu/obs/trace.py``); a record keeps their names and times
(``drivers/train.SpanSink``), not their attributes, so a span is put to
its unit by time. One name per metric across drivers: the span names
come from ``record["driver"]``:

  driver                        unit              handed over     seen done
  train, train_rnnt, train_lfm2 train.step        train.dispatch  train.wait
  transcribe_lm                 infer.transcribe  infer.prefill.dispatch,
                                                  infer.decode.dispatch
                                                                  infer.prefill.wait,
                                                                  infer.decode.fetch

A unit's turn is the sum, over the programs handed over inside the unit
(one in training, a served call's prefill sub-batches and its decode
loop), of end-of-``dispatch`` minus the end of the last ``wait`` /
``fetch`` before it: the hand-over from the unit before is the first
turn of a unit. A unit whose first turn began before the window is left
out (the window opens while the warm-up unit's log line is written).

A program without the child spans (the parent of the PR that added
them), or another driver's record, has none of these names: ``units``
is then empty and every reader returns None.
"""

import bisect
import statistics
from typing import List, NamedTuple, Optional, Tuple

TRAIN = ("train", "train_rnnt", "train_lfm2")
DRIVERS = TRAIN + ("transcribe_lm",)


class Names(NamedTuple):
    unit: str
    dispatch: Tuple[str, ...]
    done: Tuple[str, ...]


_TRAIN = Names("train.step", ("train.dispatch",), ("train.wait",))
NAMES = {
    **{d: _TRAIN for d in TRAIN},
    "transcribe_lm": Names(
        "infer.transcribe",
        ("infer.prefill.dispatch", "infer.decode.dispatch"),
        ("infer.prefill.wait", "infer.decode.fetch")),
}


class Unit(NamedTuple):
    dispatch_s: float               # its dispatch spans, summed
    turn_s: float                   # its turns, summed


def in_window(record, *names: str) -> List[Tuple[float, float]]:
    """(start, end) of the named spans that lie inside the window,
    by start."""
    lo, hi = record["t_window_start"], record["t_window_end"]
    return sorted((a, b) for n, a, b in record["spans"]
                  if n in names and a >= lo and b <= hi)


def units(record) -> List[Unit]:
    """The window's units whose every turn lies inside it."""
    names = NAMES.get(record.get("driver"))
    if names is None:
        return []
    lo = record["t_window_start"]
    # Every seen-done moment, the warm-up's included: where a turn began.
    done = sorted(b for n, a, b in record["spans"] if n in names.done)
    dispatches = in_window(record, *names.dispatch)
    out = []
    for start, end in in_window(record, names.unit):
        handed = [(a, b) for a, b in dispatches if start <= a and b <= end]
        began = [bisect.bisect_right(done, a) for a, _ in handed]
        if not handed or 0 in began or done[began[0] - 1] < lo:
            continue
        out.append(Unit(
            sum(b - a for a, b in handed),
            sum(b - done[i - 1] for (_, b), i in zip(handed, began))))
    return out


def unit_median_ms(record, field: str) -> Optional[float]:
    """Median over the window's units of one of a ``Unit``'s sums."""
    found = units(record)
    if not found:
        return None
    return 1e3 * statistics.median(getattr(u, field) for u in found)


def span_median_ms(record, name: str) -> Optional[float]:
    """Median duration of the named span inside the window."""
    spans = in_window(record, name)
    return (1e3 * statistics.median(b - a for a, b in spans)
            if spans else None)
