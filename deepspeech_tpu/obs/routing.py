"""Counters of the sparse expert layers, fed from a training step's
own outputs.

``objective="lm"`` returns each expert layer's routing counters with
the step's loss (one fetch, no second sync); :func:`observe_routing`
puts them into the process-wide registry and returns the fields the
step's log line carries:

  moe_expert_pairs{layer,expert}  (position, expert) pairs computed on
                                  each held expert, per expert layer
  moe_pairs_elsewhere             valid positions' pairs whose expert
                                  lives on another chip (left out)
  moe_dropped_pairs               pairs that landed here and did not
                                  fit the static row capacity, over
                                  EVERY step since the last logged
                                  one: 0, or the run ends
  moe_experts_hit                 held experts that received a pair, over
                                  the logged steps' expert layers
  moe_rows_high_water (gauge)     most rows any layer of any step used
  moe_rows_capacity (gauge)       the static rows of the dispatch
  lm_valid_positions / lm_padded_positions

and, where the trained layers are grouped-query attention with a
window (the step then counts them: ``models/lfm2.reach_pairs``):

  lm_reach_pairs_window           (query, key) pairs in reach of the
                                  step's valid positions in ONE windowed
                                  layer (``min(i + 1, lfm_window)`` keys
                                  for query i)
  lm_reach_pairs_global           the same in ONE layer that sees all
                                  (every causal pair)

and from a served call's (``decode.mode="lm_greedy"``;
:func:`observe_lm_call`): the same, over the call's prefill sub-batches
and decode steps, and

  moe_groups_used                 groups the chosen experts of the valid
                                  positions lie in (never more than
                                  ``moe_groups_kept`` a position)
  moe_experts_hit                 held experts that received a pair, over
                                  the decode steps and expert layers
                                  (whose weights a step had to read)
  lm_decode_steps                 steps of the calls' decode loops
  lm_idle_slot_steps              steps a finished stream still occupied
                                  its slot of the batch
  lm_cache_rows_read              cache rows the active streams' steps
                                  attended to: in one layer where all
                                  layers see the same rows, else summed
                                  over the layers (the two below)
  lm_cache_bytes (gauge)          bytes of the cache, a draft module's
                                  array included (set where it is
                                  allocated)
  moe_empty_groups                held experts of an expert layer's call
                                  (a prefill sub-batch's, a decode
                                  step's) that received no pair: groups
                                  of its grouped products with no row

and, where layers are grouped-query attention with caches per kind
(a ring of ``lfm_window`` rows, or every row):

  lm_rows_attended_window         cache rows the steps attended to in
                                  the windowed layers (``lfm_window`` a
                                  stream and layer at most)
  lm_rows_attended_global         the same in the layers that see all
  lm_rows_fetched_window / _global
                                  cache rows those layers MOVED to do
                                  so: the row tiles the kernel
                                  ``gqa_attn_decode`` visits for the
                                  active streams, or every row of every
                                  stream where the plain form runs
  lm_ring_wraps                   streams of the calls whose position
                                  passed the window (their rings wrapped)
  lm_cache_bytes_window / _global (gauges) the cache's bytes per kind

and, where layers hold a recurrent state beside their cache rows (a
state-space mixer beside attention):

  lm_state_updates                (live stream, layer) states the decode
                                  steps read once and wrote once in
                                  place; a finished stream's is not
                                  moved
  lm_cache_bytes_state / _conv    (gauges) the cache's bytes in float32
                                  states and in convolution inputs

and, where the loop drafts for itself (``model.lm_draft_layers``):

  lm_verify_positions             positions the model ran in the steps
                                  (an active stream's token and the
                                  draft behind it)
  lm_draft_positions              drafts put to the test
  lm_draft_accepted               of them, those the model's argmax
                                  confirmed (or a forced input replaced)
  lm_rejected_rows_overwritten    cache rows a rejected draft wrote that
                                  the stream's next step wrote again
  lm_drafts                       drafts the loop used: prefill's first
                                  and one for each stream that went on
                                  after a step (one an active stream a
                                  step)
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

import numpy as np

from .metrics import registry


def check_dropless(dropped: Sequence, capacity: int = 0) -> int:
    """``dropped``: the ``dropped`` counters of the steps since the
    last sync, still on the device. The dispatch is dropless; a stated
    row bound that the traffic exceeds is a wrong statement, not a
    slower step, so any dropped pair ends the run."""
    import jax

    n = int(sum(np.sum(x) for x in jax.device_get(list(dropped))))
    registry().count("moe_dropped_pairs", n)
    if n:
        rows = f"{capacity} rows" if capacity else "rows"
        raise RuntimeError(
            f"{n} routed pairs did not fit the expert layer's {rows} "
            f"(model.moe_rows_bound is too low for this traffic)")
    return n


def observe_routing(routing: Dict, dropped: Sequence = ()
                    ) -> Dict[str, Any]:
    """``routing``: the logged step's counters; ``dropped``: every
    step's ``dropped`` counter since the last logged step, this one's
    included (the loop keeps them; unlogged steps are checked too)."""
    import jax

    r = jax.device_get(routing)
    reg = registry()
    out = {"valid_positions": int(r["valid_positions"]),
           "padded_positions": int(r["padded_positions"])}
    reg.count("lm_valid_positions", out["valid_positions"])
    reg.count("lm_padded_positions", out["padded_positions"])
    for k in ("reach_pairs_window", "reach_pairs_global"):
        if k in r:
            out[k] = int(r[k])
            reg.count("lm_" + k, out[k])
    if "expert_pairs" not in r:   # a stack without sparse layers
        return out
    pairs = np.asarray(r["expert_pairs"])               # [layers, held]
    for layer, row in enumerate(pairs):
        for expert, n in enumerate(row):
            reg.count("moe_expert_pairs", int(n),
                      labels={"layer": layer, "expert": expert})
    out.update(
        expert_pairs=pairs.tolist(),
        experts_hit_by_layer=np.sum(pairs > 0, axis=1).tolist(),
        pairs_elsewhere=np.asarray(r["pairs_elsewhere"]).tolist(),
        rows_high_water=int(np.max(r["rows_high_water"])),
        rows_capacity=int(np.max(r["rows_capacity"])))
    reg.count("moe_experts_hit", sum(out["experts_hit_by_layer"]))
    reg.count("moe_pairs_elsewhere", sum(out["pairs_elsewhere"]))
    reg.gauge("moe_rows_capacity", out["rows_capacity"])
    reg.gauge("moe_rows_high_water", max(
        out["rows_high_water"],
        int(reg.gauges.get("moe_rows_high_water", 0))))
    out["dropped_pairs"] = check_dropless(
        dropped or [r["dropped"]], out["rows_capacity"])
    return out


def observe_lm_call(prefill: Sequence[Dict], decode: Dict, rows: int
                    ) -> Dict[str, Any]:
    """``prefill``: the counters of each prefill sub-batch of one served
    call; ``decode``: what its decode loop accumulated; both already on
    the host. Counts them, ends the call on a dropped pair, and returns
    the call's fields: ``prefill`` and ``decode`` each with the pairs
    on every held expert per expert layer and the pairs elsewhere, the
    positions, steps and idle slots."""
    reg = registry()

    def part(c: Dict) -> Dict[str, Any]:
        out = {"valid_positions": int(np.sum(c["valid_positions"])),
               "padded_positions": int(np.sum(c["padded_positions"]))}
        if "expert_pairs" in c:
            out.update(
                expert_pairs=np.asarray(c["expert_pairs"]).tolist(),
                pairs_elsewhere=np.asarray(c["pairs_elsewhere"]).tolist(),
                rows_high_water=int(np.max(c["rows_high_water"])),
                rows_capacity=int(np.max(c["rows_capacity"])),
                dropped=int(np.sum(c["dropped"])))
        if "groups_used" in c:
            out["groups_used"] = int(np.sum(c["groups_used"]))
        return out

    summed = {k: np.sum([np.asarray(c[k]) for c in prefill], axis=0)
              for k in prefill[0]
              if k not in ("rows_high_water", "rows_capacity")}
    for k in ("rows_high_water", "rows_capacity"):
        if k in prefill[0]:
            summed[k] = np.max([np.max(c[k]) for c in prefill])
    steps, idle = int(decode["steps"]), int(decode["idle_slot_steps"])
    # A step computes 1 position a stream, or 2 where it drafts; the
    # valid ones are those whose token was emitted.
    emitted = int(np.sum(decode["tokens"]))
    width = 2 if "draft_positions" in decode else 1
    decode = dict(decode, valid_positions=emitted,
                  padded_positions=steps * rows * width - emitted)
    out = {"prefill": part(summed), "decode": part(decode),
           "decode_steps": steps, "idle_slot_steps": idle,
           "cache_rows_read": int(decode["cache_rows_read"]), "rows": rows}
    if "experts_hit" in decode:
        hit = np.asarray(decode["experts_hit"])
        out["experts_hit"] = int(np.sum(hit))
        out["experts_hit_by_layer"] = hit.tolist()
        reg.count("moe_experts_hit", out["experts_hit"])
        # Groups without a row: per decode step and layer the held
        # experts that were not hit, per prefill sub-batch and layer
        # those with no pair.
        held = np.shape(decode["expert_pairs"])[-1]
        out["empty_groups"] = {
            "decode": int(steps * held * hit.size - np.sum(hit)),
            "prefill": int(sum(np.sum(np.asarray(c["expert_pairs"]) == 0)
                               for c in prefill)),
            "decode_calls": steps * hit.size,
            "prefill_calls": len(prefill) * hit.size, "groups": held}
        reg.count("moe_empty_groups", out["empty_groups"]["decode"]
                  + out["empty_groups"]["prefill"])
    for k in ("rows_attended_window", "rows_attended_global",
              "rows_fetched_window", "rows_fetched_global", "ring_wraps",
              "state_updates", "select_rows_read", "select_rows_held",
              "select_windows_read", "pooled_key_writes"):
        if k in decode:
            # a selection's rows come a stream: their sum passes 2^31
            out[k] = int(np.sum(decode[k], dtype=np.int64))
            reg.count("lm_" + k, out[k])
    reg.count("lm_decode_steps", steps)
    reg.count("lm_idle_slot_steps", idle)
    reg.count("lm_cache_rows_read", out["cache_rows_read"])
    for k in ("verify_positions", "draft_positions", "draft_accepted",
              "rejected_rows_overwritten", "drafts"):
        if k in decode:
            out[k] = int(decode[k])
            reg.count("lm_" + k, out[k])
    dropped = 0
    for p in (out["prefill"], out["decode"]):
        reg.count("lm_valid_positions", p["valid_positions"])
        reg.count("lm_padded_positions", p["padded_positions"])
        if "expert_pairs" not in p:
            continue
        for layer, row in enumerate(p["expert_pairs"]):
            for expert, n in enumerate(row):
                reg.count("moe_expert_pairs", int(n),
                          labels={"layer": layer, "expert": expert})
        reg.count("moe_pairs_elsewhere", sum(p["pairs_elsewhere"]))
        reg.count("moe_groups_used", p.get("groups_used", 0))
        reg.gauge("moe_rows_capacity", p["rows_capacity"])
        reg.gauge("moe_rows_high_water", max(
            p["rows_high_water"],
            int(reg.gauges.get("moe_rows_high_water", 0))))
        dropped += p["dropped"]
    out["dropped_pairs"] = dropped
    reg.count("moe_dropped_pairs", dropped)
    if dropped:
        raise RuntimeError(
            f"{dropped} routed pairs did not fit the expert layer's rows "
            f"(model.moe_rows_bound is too low for this traffic)")
    return out
