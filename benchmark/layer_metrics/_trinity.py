"""What the ``trinity_*`` readers share (the underscore keeps ``--detail``
from taking this module for a reader).

The counters are the program's own: a served call whose layers are
grouped-query attention with a cache per layer kind
(``decode.mode="lm_greedy"``) returns everything ``_axk1`` describes
and ``rows_attended_window`` / ``rows_attended_global`` (cache rows the
decode steps attended to in the windowed layers and in those that see
all), ``ring_wraps``, ``experts_hit_by_layer`` and ``empty_groups``
(``deepspeech_tpu/obs/routing.py`` ``observe_lm_call``); the driver
``transcribe_long`` keeps those of the window's calls under
``counters["calls"]``. A program without them (the parent of the PR
that added the caches, or another driver's record) has no such key:
every function here then finds nothing, and the readers return None.

The grouped products are the named kernel ``moe_gmm``. The rest is XLA
code without an identity. The program puts its attention's scores,
softmax, mixing and cache update under ``jax.named_scope`` (``gqa_attn_
window`` / ``gqa_attn_global``), but a trace event carries its
instruction's text without ``op_name`` metadata (the profiler runs with
the HLO proto off), so the scope's operations are told apart by the
SHAPES in an event's short name, as ``_axk1`` does, with N = rows a
program attends for at once (a prefill sub-batch's rows, or the streams
of a decode step):

  attn   a result [N, ...] that holds the key/value heads and the query
         heads a key/value head serves as neighbouring dimensions (kv,
         rep), or (kv, head, rep): scores and probabilities [N, kv,
         rep, queries, keys], their reductions, the mixed heads [N,
         queries, kv, rep, head]; the key spans a query block reads [N,
         keys, kv, head] with fewer keys than the prefix; in the loop
         also the cache itself [N, rows, 2 kv | kv, head] (the row's
         write and the slices the scores read). NOT the projections,
         norms, rotation, gate and output projection.
  route  ``_axk1``'s shapes for either program's positions (no groups)

Control-flow instructions span their bodies' events and are skipped.
"""

from benchmark.costs.trinity import head_dim
from benchmark.layer_metrics import _axk1
from benchmark.layer_metrics._rnnt import parse

DRIVERS = ("transcribe_long",)

_CONTROL = ("while", "conditional", "call")

parts = _axk1.parts
pairs_held = _axk1.pairs_held
span_seconds = _axk1.span_seconds
is_moe_kernel = _axk1.is_moe_kernel


def window_calls(record) -> list:
    """The counters of the window's calls, or [] where the program
    reported no call with a cache per layer kind."""
    if record.get("driver") not in DRIVERS:
        return []
    calls = record["counters"].get("calls") or []
    return [c for c in calls if c.get("rows_attended_window") is not None]


def programs(record) -> dict:
    """Rows each of the two programs attends for at once, and the
    positions it computes at once."""
    c, m = record["counters"], record["model"]
    prefix = -(-c["bucket_frames"] // m.frame_stack)
    rows = min(c["prefill_rows"], c["rows_per_call"])
    return {"prefill": (rows, rows * prefix),
            "decode": (c["rows_per_call"], c["rows_per_call"])}


def is_route(shapes, record) -> bool:
    m = record["model"]
    call = window_calls(record)[0]
    k, e, g, d = m.lfm_top_k, m.lfm_experts, m.experts_held, m.lfm_hidden
    for part, (_, n) in programs(record).items():
        rows = call[part].get("rows_capacity")
        own = [(n, e), (n, k), (n * k,), (n * k, g + 1), (rows,), (rows, d)]
        if part == "prefill":
            own.append((n, d))
        if any(s in own for s in shapes):
            return True
    return False


def is_attn(shapes, record, part: str) -> bool:
    """Whether an event of these result shapes is the attention's own
    work in the program ``part`` ("prefill" | "decode")."""
    c, m = record["counters"], record["model"]
    nkv, rep, hd = m.lfm_kv_heads, m.lfm_heads // m.lfm_kv_heads, \
        head_dim(m)
    rows = programs(record)[part][0]
    other = programs(record)["decode" if part == "prefill"
                             else "prefill"][0]
    prefix = -(-c["bucket_frames"] // m.frame_stack)
    cache_rows = (c["cache_rows"], c.get("ring_rows"))
    for s in shapes:
        if len(s) < 3 or s[0] != rows or rows == other:
            continue
        rest = tuple(s[1:])
        pairs = list(zip(rest, rest[1:]))
        if (nkv, rep) in pairs:
            return True
        if len(rest) >= 3 and any(
                rest[i:i + 3] == (nkv, hd, rep)
                for i in range(len(rest) - 2)):
            return True
        if part == "decode" and len(s) == 4 and s[1] in cache_rows \
                and s[2] in (nkv, 2 * nkv) and s[3] == hd:
            return True
        if part == "prefill" and len(s) in (4, 5) and s[1] < prefix \
                and tuple(s[2:4]) == (nkv, hd) and s[1] > 1:
            return True
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
