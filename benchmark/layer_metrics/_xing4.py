"""What the ``xing4_*`` readers share (the underscore keeps ``--detail``
from taking this module for a reader).

The counters are the program's own: a served call that drafts for
itself (``decode.mode="lm_greedy"`` with ``model.lm_draft_layers`` 1)
returns everything ``_axk1`` describes, with one more row of pairs per
expert for the draft module's expert layer (the LAST row, in prefill
and in decode), and ``verify_positions``, ``draft_positions``,
``draft_accepted``, ``rejected_rows_overwritten`` and ``drafts``
(``deepspeech_tpu/obs/routing.py`` ``observe_lm_call``); the driver
``transcribe_mtp`` keeps those of the window's calls under
``counters["calls"]``. A program without them (the parent of the PR
that added the loop, or another driver's record) has no such key: every
function here then finds nothing, and the readers return None.

The grouped products are the named kernel ``moe_gmm``. The rest is XLA
code without an identity, told apart by the SHAPES in an event's short
name as ``_axk1`` does, with N = positions a program computes at once
(a prefill sub-batch's rows x prefix positions, or 2 x the streams of a
drafting step):

  mhc    the hyper-connections: a result whose last two dimensions are
         (n, D) (the streams: the write-back, the fan-out), (n, n) or
         (n, 1), or whose last is n*D or n*(n+2), or N behind one of
         the coefficient path's row counts (1, n, 2n, n*n, n*(n+2): it
         runs with the positions last, a Sinkhorn round's sums are
         [1, N]); H_pre and H_post where one fusion gives both as
         [N, n] (the router's [N, k], k = n here, comes alone); the n
         streams given one by one as [.., 1, D]. NOT the read mix
         where the compiler fuses it into the sub-layer's own norm: its
         result [N, D] has the shape of every other.
  route  ``_axk1``'s shapes for either N (no groups here)
  mla    ``_axk1.is_mla``, and a drafting step's scores
         [streams, heads, 2, rows]

Control-flow instructions span their bodies' events and are skipped.
"""

from benchmark.layer_metrics import _axk1
from benchmark.layer_metrics._rnnt import parse

DRIVERS = ("transcribe_mtp",)

_CONTROL = ("while", "conditional", "call")

parts = _axk1.parts
span_seconds = _axk1.span_seconds
is_moe_kernel = _axk1.is_moe_kernel


def window_calls(record) -> list:
    """The counters of the window's calls, or [] where the program
    reported no drafting call."""
    if record.get("driver") not in DRIVERS:
        return []
    calls = record["counters"].get("calls") or []
    return [c for c in calls if c.get("draft_positions") is not None]


def model_pairs(call: dict) -> int:
    """Pairs on the MODEL's expert layers (every row but the last,
    which is the draft module's)."""
    return sum(sum(layer) for p in parts(call)
               for layer in p["expert_pairs"][:-1])


def programs(record) -> dict:
    """Positions each of the two programs computes at once."""
    c, m = record["counters"], record["model"]
    prefix = -(-c["bucket_frames"] // m.frame_stack)
    return {"prefill": min(c["prefill_rows"], c["rows_per_call"]) * prefix,
            "decode": 2 * c["rows_per_call"]}


def is_mhc(shapes, record) -> bool:
    m = record["model"]
    n, d = m.hc_streams, m.lfm_hidden
    rows = {1, n, 2 * n, n * n, n * (n + 2)}
    counts = set(programs(record).values())
    # H_pre and H_post leave one fusion together, [N, n] each (the
    # router's [N, k] leaves alone); the write-back may give its n
    # streams one by one
    if len(shapes) >= 2 and all(
            len(s) == 2 and s[0] in counts and s[1] == n for s in shapes):
        return True
    if len(shapes) == n and all(tuple(s[-2:]) == (1, d) for s in shapes):
        return True
    for s in shapes:
        if len(s) >= 2 and tuple(s[-2:]) in ((n, d), (n, n), (n, 1)):
            return True
        if len(s) >= 2 and s[-1] in (n * d, n * (n + 2)):
            return True
        if len(s) >= 2 and s[-1] in counts and s[-2] in rows:
            return True      # the coefficient path, positions last
    return False


def is_route(shapes, record) -> bool:
    if is_mhc(shapes, record):
        return False
    m = record["model"]
    call = window_calls(record)[0]
    k, e, g, d = m.lfm_top_k, m.lfm_experts, m.experts_held, m.lfm_hidden
    for part, n in programs(record).items():
        rows = call[part].get("rows_capacity")
        own = [(n, e), (n, k), (n * k,), (n * k, g + 1), (rows,), (rows, d)]
        if part == "prefill":
            own.append((n, d))
        if any(s in own for s in shapes):
            return True
    return False


def is_mla(shapes, record) -> bool:
    if is_mhc(shapes, record):
        return False
    if _axk1.is_mla(shapes, record):
        return True
    c, nh = record["counters"], record["model"].lfm_heads
    return any(len(s) == 4 and s[1] == nh and s[2] == 2
               and s[3] == c["cache_rows"] for s in shapes)


def classified_ms_per_call(record, wanted):
    tr = record["trace"]
    if tr is None or not record["units"] or not window_calls(record):
        return None
    seconds = 0.0
    for key, s in tr["op_seconds"].items():
        opcode, shapes = parse(key)
        if opcode not in _CONTROL and wanted(shapes, record):
            seconds += s
    return 1e3 * seconds / record["units"]
