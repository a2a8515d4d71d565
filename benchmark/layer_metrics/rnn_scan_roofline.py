"""The recurrent scan kernels' share of their roofline: the least time
the chip could take for the operations and bytes the scans of the
window need (``costs/ds2.py``: per call, the larger of operations over
the bf16 peak and bytes over the HBM peak, every operand read once and
every result written once) over the device time of those kernels in
the trace. Which peak bounds the calls is kept in the run's counters
(``rnn_scan_bound_by``).

Today's trace does not name kernels (every Mosaic call is
``%<jit name>.<n> = ... custom-call``), so the scan calls are told from
CTC by their result shapes: a forward scan returns f32[T',b,H], a
backward scan two f32[T',b,3H]. Stable names are the ``tracing``
issue's job (PERF.md, Open questions)."""

import re

from benchmark.costs import ds2

DRIVERS = ("train",)

_RESULT = re.compile(r"f32\[(\d+),(\d+),(\d+)\]")


def classify(text: str, hidden: int, gates: int):
    """('fwd'|'bwd', steps, rows) of a scan kernel's instruction, or
    None for another kernel."""
    head = text.split(" custom-call(", 1)[0]
    shapes = [tuple(map(int, m)) for m in _RESULT.findall(head)]
    if len(shapes) == 1 and shapes[0][2] == hidden:
        return "fwd", shapes[0][0], shapes[0][1]
    if len(shapes) == 2 and all(s[2] == gates * hidden for s in shapes):
        return "bwd", shapes[0][0], shapes[0][1]
    return None


def read(record):
    tr = record["trace"]
    if tr is None or record["peaks"] is None:
        return None
    model = record["model"]
    least = spent = 0.0
    bounds = {}
    for text, seconds in tr["kernels"]:
        kind = classify(text, model.rnn_hidden, ds2.n_gates(model))
        if kind is None:
            continue
        cost = ds2.gru_scan_cost(model, kind[2], kind[1],
                                 backward=kind[0] == "bwd")
        t, bound = ds2.roofline_seconds(
            cost, record["peaks"]["bf16_flops"],
            record["peaks"]["hbm_bytes_per_s"])
        least += t
        spent += seconds
        bounds[bound] = bounds.get(bound, 0) + 1
    record["counters"]["rnn_scan_bound_by"] = bounds
    record["counters"]["rnn_scan_device_s"] = spent
    return 100.0 * least / spent if spent > 0 else None
