"""What the ``rnnt_*`` readers share (the underscore keeps ``--detail``
from taking this module for a reader).

The tiled joint + loss and the lattice recursions of
``deepspeech_tpu/ops/transducer.py`` are XLA code under a
``custom_vjp``: their device operations carry no kernel identity
(``named_scope`` does not reach the trace, PERF.md section 7), so they
are told from the rest of the step by the SHAPES in an event's short
name (``reduce/xplane.short_name``: instruction, opcode, result
shapes), as ``rnn_scan_roofline.classify`` tells scans from CTC. The
shapes come from the run's own counters: rows B, encoder frames T',
prefix rows U+1, the joint's tile of T' (``joint_tile_frames``) and the
model's J and V.

  joint    an operation one of whose results is shaped like a tile of
           the joint: the dims {B, tile, U+1} alone or with J or V
           (hidden layer, logits, their gradients, the three picked
           scores), {B, tile, J} (the tile of e and its gradient),
           {B, U+1, J} (the gradient of p accumulated over tiles) or
           {J, V} / {V} (the output layer's gradients accumulated over
           tiles; the optimizer's few operations of that shape ride
           along, microseconds a step)
  lattice  an operation all of whose results are rows or stacks of rows
           of the alpha/beta recursions: [B, k] with 2 <= k <= U+1 (the
           associative scan halves the row again and again), or three
           dims holding B, T' (or T'-1) and U+1 (or U): the stacked
           rows, the score tensors and the occupancies

Control-flow instructions (``while``, ``conditional``, ``call``) span
their bodies' events and are skipped, so nothing is counted twice.
"""

import re

DRIVERS = ("train_rnnt",)

_SHAPE = re.compile(r"[a-z]+\d*\[([\d,]*)\]")
_CONTROL = ("while", "conditional", "call")


def facts(record) -> dict:
    c, m = record["counters"], record["model"]
    return {"b": c["rows_per_step"], "t": c["enc_frames"],
            "u1": c["max_label_len"] + 1, "tile": c["joint_tile_frames"],
            "j": m.rnnt_joint_dim, "v": m.vocab_size}


def parse(key: str):
    """(opcode, [dims of each result]) of a short name."""
    parts = key.split(" ", 2)
    opcode = parts[1] if len(parts) > 1 else ""
    shapes = [tuple(int(d) for d in m.split(",") if d)
              for m in _SHAPE.findall(parts[2] if len(parts) > 2 else "")]
    return opcode, shapes


def _is(dims, *want) -> bool:
    return sorted(dims) == sorted(want)


def is_joint(shapes, f) -> bool:
    b, tile, u1, j, v = f["b"], f["tile"], f["u1"], f["j"], f["v"]
    return any(
        _is(s, b, tile, u1) or _is(s, b, tile, u1, j)
        or _is(s, b, tile, u1, v) or _is(s, b, tile, j)
        or _is(s, b, u1, j) or _is(s, j, v) or _is(s, v)
        for s in shapes)


def is_lattice(shapes, f) -> bool:
    b, t, u1 = f["b"], f["t"], f["u1"]

    def row(s):
        if len(s) == 2:
            return s[0] == b and 2 <= s[1] <= u1
        return any(_is(s, b, tt, uu) for tt in (t, t - 1)
                   for uu in (u1, u1 - 1))

    return bool(shapes) and all(row(s) for s in shapes)


def classify(key: str, f: dict):
    """'joint', 'lattice' or None for an event's short name."""
    opcode, shapes = parse(key)
    if opcode in _CONTROL:
        return None
    if is_joint(shapes, f):
        return "joint"
    if is_lattice(shapes, f):
        return "lattice"
    return None


def ms_per_step(record, kind: str):
    """Device milliseconds a step and chip in the operations of
    ``kind``; None without a trace."""
    tr = record["trace"]
    if tr is None or not record["units"]:
        return None
    f = facts(record)
    seconds = sum(s for key, s in tr["op_seconds"].items()
                  if classify(key, f) == kind)
    return 1e3 * seconds / record["units"]
