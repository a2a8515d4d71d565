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
  moe_rows_high_water (gauge)     most rows any layer of any step used
  moe_rows_capacity (gauge)       the static rows of the dispatch
  lm_valid_positions / lm_padded_positions
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
    if "expert_pairs" not in r:   # a stack without sparse layers
        return out
    pairs = np.asarray(r["expert_pairs"])               # [layers, held]
    for layer, row in enumerate(pairs):
        for expert, n in enumerate(row):
            reg.count("moe_expert_pairs", int(n),
                      labels={"layer": layer, "expert": expert})
    out.update(
        expert_pairs=pairs.tolist(),
        pairs_elsewhere=np.asarray(r["pairs_elsewhere"]).tolist(),
        rows_high_water=int(np.max(r["rows_high_water"])),
        rows_capacity=int(np.max(r["rows_capacity"])))
    reg.count("moe_pairs_elsewhere", sum(out["pairs_elsewhere"]))
    reg.gauge("moe_rows_capacity", out["rows_capacity"])
    reg.gauge("moe_rows_high_water", max(
        out["rows_high_water"],
        int(reg.gauges.get("moe_rows_high_water", 0))))
    out["dropped_pairs"] = check_dropless(
        dropped or [r["dropped"]], out["rows_capacity"])
    return out
