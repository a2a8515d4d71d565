"""Tier-aware rung-ladder sizing: HBM headroom → batch height.

The serving plane's throughput knob is the B rung ladder — how many
concurrent utterance rows one replica decodes per flush. What bounds
it is resident HBM: the parameter tree (constant per replica) plus
per-row activation/state buffers (linear in B). Weight-only int8 PTQ
(``utils/quantize.py``) shrinks the parameter term ~3.1x on the
composed serve program (``tools/aot_infer_r5.jsonl``: 278 MB int8 vs
864 MB bf16), and every byte it frees is budget for more rows — the
HBM headroom → throughput conversion this module prices.

:func:`max_batch_for_budget` answers "what is the tallest power-of-two
B rung whose footprint fits this budget", and
:func:`tier_max_batches` applies it per tier from a PTQ report's
measured byte counts, producing the ``tier_max_batch`` map the
:class:`~.scheduler.MicroBatchScheduler` flushes by.
``tests/test_quantize.py``
``test_scenario_two_tier_pool_quantizes_once_and_keeps_tiers_apart``
asserts the int8 tier's rung strictly exceeds the bf16 tier's under
the same synthetic budget.

Beyond the resident footprint, replicas past the residency budget
also RESERVE bandwidth-backed working bytes: when the recurrent
matrices miss it, the kernel copies them into its VMEM once a scan or
re-streams them from HBM every timestep (the route's ``pinned`` /
``blocked`` builds), and pre-blocked-q int8 replicas had to hold (and stream) a
full-precision working copy — a per-replica constant that competed
with batch rows for the same budget. :func:`recurrent_stream_bytes`
prices that term per regime (0 once resident; the stored-width matrix
otherwise), and ``tier_max_batches(..., stream_bytes=...)`` charges it
before sizing the rung. With the s8-streaming kernels the bulk tier's
term drops 4× (or to zero where int8 newly fits residency), which is
how in-kernel dequant converts to a taller bulk ladder
(``tests/test_ops_quant_blocked.py`` ``test_stream_ladder_bulk_rises``).
"""

from __future__ import annotations

from typing import Dict, Mapping, Optional


def max_batch_for_budget(param_bytes: int, per_row_bytes: int,
                         budget_bytes: int, *,
                         ceiling: int = 1024) -> int:
    """Tallest power-of-two ``B <= ceiling`` with
    ``param_bytes + B * per_row_bytes <= budget_bytes``; 0 when even
    a single row does not fit (the tier cannot be hosted at all)."""
    if param_bytes < 0 or per_row_bytes <= 0 or ceiling < 1:
        raise ValueError("need param_bytes >= 0, per_row_bytes > 0, "
                         "ceiling >= 1")
    if param_bytes + per_row_bytes > budget_bytes:
        return 0
    b = 1
    while (b * 2 <= ceiling
           and param_bytes + 2 * b * per_row_bytes <= budget_bytes):
        b *= 2
    return b


def recurrent_stream_bytes(hidden: int, n_gates: int, weight_bytes: int,
                           *, layers: int = 1,
                           directions: int = 1) -> int:
    """Recurrent weight bytes a forward moves beside its parameters.

    0 where the route (ops/scan_pallas.scan_route) names a resident
    build (the ``n_gates * H^2`` matrix at ``weight_bytes``/element
    fits the VMEM residency budget and is fetched once per scan), else
    the full matrix at its stored width: past the budget it is copied
    into the call's VMEM once a scan or re-streamed in column blocks
    each step. Scaled by ``layers * directions`` matrices.
    ``weight_bytes`` is the STORED element size: 1 for the int8 q
    kernels, the dot dtype's size for the fp kernels (including the fp
    working copy that pre-blocked-q int8 replicas materialized).
    """
    from ..ops.scan_pallas import scan_route

    if hidden < 1 or n_gates < 1 or weight_bytes < 1:
        raise ValueError("need hidden, n_gates, weight_bytes >= 1")
    # resident or not does not depend on the rows: any will do
    route = scan_route("gru" if n_gates == 3 else "lstm", "pallas", rows=1,
                       hidden=hidden, dot_bytes=weight_bytes,
                       int8=weight_bytes == 1)
    if route.variant.startswith("resident"):
        return 0
    return n_gates * hidden * hidden * weight_bytes * layers * directions


def tier_max_batches(report: Mapping[str, int], per_row_bytes: int,
                     budget_bytes: int, *, ceiling: int = 1024,
                     premium: str = "premium",
                     bulk: str = "bulk",
                     stream_bytes: Optional[Mapping[str, int]] = None,
                     ) -> Dict[str, int]:
    """Per-tier ladder heights from a PTQ report's measured footprints.

    ``report`` is ``quantize_params``'s report dict: ``bytes_before``
    is the full-precision parameter footprint (the premium/bf16
    tier), ``bytes_after`` the quantized one (the bulk/int8 tier).
    ``stream_bytes`` optionally maps tier -> per-replica streamed-
    working-bytes reservation (:func:`recurrent_stream_bytes`), a
    B-independent term charged alongside the parameter footprint.
    Returns ``{premium: B, bulk: B}`` suitable as
    ``MicroBatchScheduler(tier_max_batch=...)``; a tier that does not
    fit at all maps to 0 (caller decides whether to host it).
    """
    stream = stream_bytes or {}
    return {
        premium: max_batch_for_budget(
            int(report["bytes_before"]) + int(stream.get(premium, 0)),
            per_row_bytes, budget_bytes, ceiling=ceiling),
        bulk: max_batch_for_budget(
            int(report["bytes_after"]) + int(stream.get(bulk, 0)),
            per_row_bytes, budget_bytes, ceiling=ceiling),
    }
