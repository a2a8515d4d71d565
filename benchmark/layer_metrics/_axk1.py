"""What the ``axk1_*`` readers share (the underscore keeps ``--detail``
from taking this module for a reader).

The counters are the program's own: every served call
(``decode.mode="lm_greedy"``) returns, for its prefill sub-batches and
for its decode loop, per expert layer the pairs on each held expert
and the pairs routed elsewhere, the valid and padded positions, the
decode steps, the idle slots, the held experts that received a pair
and the cache rows read (``deepspeech_tpu/obs/routing.py``
``observe_lm_call``); the driver keeps those of the window's calls
under ``counters["calls"]``. A program without them (or another
driver's record) has no such key: every function here then finds
nothing, and the readers return None.

The grouped products are the named kernel ``moe_gmm``; a call's facts
(``m``, the static rows) tell a prefill call from a decode step's. The
rest is XLA code without an identity, told apart by the SHAPES in an
event's short name (``reduce/xplane.short_name``), as ``_lfm2.classify``
does, with N = positions a program computes at once (a prefill
sub-batch's rows x prefix positions, or the streams of a decode step):

  route  the ``_lfm2`` shapes for either N, plus group selection's
         [N, groups, E/groups], [N, groups] and [N, kept]; a decode
         step's two-dimensional [N, D] is left out (its embedding and
         its last hidden state have that shape too)
  mla    a result whose last dimension is one of latent attention's own
         (the query rank, heads x (nope+rope), the cache row, the
         latent rank, heads x (nope+v), heads x v) or whose last two are
         (heads, one of a head's sizes), and the scores [b, heads, q, k]:
         both forms' low-rank paths, rotations, expansion or absorption,
         scores, softmax, mixing and the cache's update. NOT the output
         projection: its result has the shape of every [N, D] result.

Control-flow instructions span their bodies' events and are skipped.
"""

from benchmark.layer_metrics._rnnt import parse

DRIVERS = ("transcribe_lm",)

_CONTROL = ("while", "conditional", "call")


def window_calls(record) -> list:
    """The counters of the window's calls, or [] where the program
    reported none."""
    if record.get("driver") not in DRIVERS:
        return []
    calls = record["counters"].get("calls") or []
    return [c for c in calls if c.get("decode_steps") is not None]


def parts(call: dict) -> list:
    """A call's prefill and decode counters that carry routing."""
    return [call[p] for p in ("prefill", "decode")
            if call[p].get("expert_pairs") is not None]


def pairs_held(call: dict) -> int:
    return sum(sum(layer) for p in parts(call)
               for layer in p["expert_pairs"])


def span_seconds(record, name: str) -> list:
    """Durations of the named program spans that lie in the window."""
    lo, hi = record["t_window_start"], record["t_window_end"]
    return [b - a for n, a, b in record["spans"]
            if n == name and a >= lo and b <= hi + 1e-3]


def programs(record) -> dict:
    """Positions each of the two programs computes at once."""
    c, m = record["counters"], record["model"]
    prefix = -(-c["bucket_frames"] // m.frame_stack)
    return {"prefill": min(c["prefill_rows"], c["rows_per_call"]) * prefix,
            "decode": c["rows_per_call"]}


def is_route(shapes, record) -> bool:
    m = record["model"]
    call = window_calls(record)[0]
    k, e, g, d = m.lfm_top_k, m.lfm_experts, m.experts_held, m.lfm_hidden
    for part, n in programs(record).items():
        rows = call[part].get("rows_capacity")
        own = [(n, e), (n, k), (n * k,), (n * k, g + 1), (rows,), (rows, d),
               (n, m.moe_groups, e // m.moe_groups), (n, m.moe_groups),
               (n, m.moe_groups_kept)]
        if part == "prefill":
            own.append((n, d))
        if any(s in own for s in shapes):
            return True
    return False


def is_mla(shapes, record) -> bool:
    m = record["model"]
    nh = m.lfm_heads
    dn, dr, dv, rkv = (m.mla_nope_dim, m.mla_rope_dim, m.mla_v_dim,
                       m.mla_kv_rank)
    last = {m.mla_q_rank, nh * (dn + dr), rkv + dr, rkv,
            nh * (dn + dv), nh * dv}
    head = {dn + dr, dn + dv, rkv + dr, rkv, dn, dr, dv}
    for s in shapes:
        if len(s) >= 2 and s[-1] in last:
            return True
        if len(s) >= 3 and s[-2] == nh and s[-1] in head:
            return True
        if len(s) == 4 and s[1] == nh and s[2] == s[3]:
            return True      # prefill scores [b, heads, q, k]
        if len(s) == 3 and s[1] == nh and s[2] == \
                record["counters"]["cache_rows"]:
            return True      # a decode step's scores [b, heads, rows]
    return False


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


def is_moe_kernel(kernel: str) -> bool:
    return kernel == "moe_gmm"
