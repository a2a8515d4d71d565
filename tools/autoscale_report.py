#!/usr/bin/env python3
"""Timeline view of an autoscaling run's event log.

Reads JSONL (``serve.py --autoscale`` prints one ``{"autoscale": ...}``
line per controller event; a postmortem sink adds one
``kind="autoscale"`` record per scaling episode; a telemetry
``emit_jsonl`` snapshot may ride along) and renders the fleet's
history as humans debug it: a time-ordered timeline of episodes,
hold-offs, drains (including cancelled ones) and vertical actuator
steps, then a summary — scale-ups/downs split horizontal vs vertical
(the ``actuator`` column: ``horizontal`` | ``ladder`` | ``tier_mix``),
drain cancels, fleet size range, re-pins charged to resizes, and
approximate replica-seconds (fleet size integrated over the event
span: the cost axis on which an autoscaled fleet is compared with a
static one). Drains show a handoff-vs-drain mode column
(a ``handoff`` drain live-migrated its pinned sessions,
``serving/migration.py``), and ``kind="migration"`` postmortems fold
into migration counts in the summary.

Usage:
    python tools/autoscale_report.py autoscale.jsonl [more.jsonl ...]
    python -m deepspeech_tpu.serve --autoscale ... | \\
        python tools/autoscale_report.py -
"""

from __future__ import annotations

import argparse
import sys
from typing import List, Optional

import _obs_common


def load_records(lines) -> List[dict]:
    # serve.py wraps controller events as {"autoscale": {...}} —
    # unwrap them; everything else is the shared tolerant loader.
    return _obs_common.load_records(lines, unwrap=("autoscale",))


def _is_event(rec: dict) -> bool:
    return rec.get("event") == "autoscale" and "action" in rec


def _is_episode(rec: dict) -> bool:
    return rec.get("event") == "postmortem" \
        and rec.get("kind") == "autoscale"


def _is_migration(rec: dict) -> bool:
    return rec.get("event") == "postmortem" \
        and rec.get("kind") == "migration"


def aggregate(records: List[dict]) -> dict:
    """Fold the log into the report's data model: ``{"timeline":
    [...events...], "episodes": [...postmortems...], "ups", "downs",
    "holdoffs", "repins", "size_min", "size_max",
    "replica_seconds"}``. Replica-seconds integrates the piecewise-
    constant fleet size between the first and last event — an
    approximation (the fleet existed before/after the log), good for
    comparing two runs over the same window."""
    events = sorted((r for r in records if _is_event(r)),
                    key=lambda r: r.get("t", 0.0))
    episodes = [r for r in records if _is_episode(r)]
    # Live-migration postmortems (serving/migration.py): one per
    # session handoff or fallback-to-drain.
    migrations = [r for r in records if _is_migration(r)]
    handoffs = sum(1 for m in migrations
                   if m.get("outcome") == "handoff")
    mig_fallbacks = sum(1 for m in migrations
                        if m.get("outcome") == "fallback_drain")
    ups = sum(1 for e in events if e.get("action") == "scale_up")
    downs = sum(1 for e in events if e.get("action") == "scale_down")
    vertical_ups = sum(1 for e in events
                       if e.get("action") == "vertical_up")
    vertical_downs = sum(1 for e in events
                         if e.get("action") == "vertical_down")
    drain_cancels = sum(1 for e in events
                        if e.get("action") == "drain_cancel")
    holdoffs = sum(1 for e in events if e.get("action") == "holdoff")
    repins = sum(int(e.get("repins") or 0) for e in events
                 if e.get("action") in ("scale_up", "scale_down"))

    size: Optional[int] = None
    size_min = size_max = None
    t_prev = None
    replica_seconds = 0.0
    for e in events:
        t = e.get("t")
        if e.get("action") == "init":
            size = e.get("replicas")
        elif e.get("action") in ("scale_up", "scale_down"):
            if size is not None and t_prev is not None \
                    and isinstance(t, (int, float)):
                replica_seconds += size * max(0.0, t - t_prev)
            size = e.get("to_replicas", size)
        else:
            continue
        if isinstance(size, int):
            size_min = size if size_min is None else min(size_min, size)
            size_max = size if size_max is None else max(size_max, size)
        if isinstance(t, (int, float)):
            t_prev = t
    return {
        "timeline": events, "episodes": episodes,
        "ups": ups, "downs": downs,
        "vertical_ups": vertical_ups,
        "vertical_downs": vertical_downs,
        "drain_cancels": drain_cancels,
        "holdoffs": holdoffs,
        "repins": repins, "size_min": size_min, "size_max": size_max,
        "replica_seconds": round(replica_seconds, 3),
        "migrations": handoffs, "migration_fallbacks": mig_fallbacks,
    }


def _fmt_event(e: dict, t0: float) -> str:
    t = e.get("t")
    rel = f"{t - t0:9.3f}s" if isinstance(t, (int, float)) \
        else "        ?"
    action = e.get("action", "?")
    if action == "init":
        detail = (f"fleet={e.get('replicas')} "
                  f"bounds=[{e.get('min')}..{e.get('max')}]")
    elif action in ("scale_up", "scale_down"):
        arrow = "^" if action == "scale_up" else "v"
        detail = (f"{arrow} {e.get('from_replicas')} -> "
                  f"{e.get('to_replicas')} replica={e.get('replica')} "
                  f"pressure={e.get('pressure')} "
                  f"repins={e.get('repins')}")
    elif action in ("vertical_up", "vertical_down"):
        arrow = "^" if action == "vertical_up" else "v"
        extra = ""
        if "to_max_batch" in e:
            extra = (f" max_batch {e.get('from_max_batch')} -> "
                     f"{e.get('to_max_batch')}")
        elif "tier_shift" in e:
            extra = f" tier_shift={e.get('tier_shift')}"
        detail = (f"{arrow} actuator={e.get('actuator')}"
                  f"{extra} pressure={e.get('pressure')}"
                  + (" (in horizontal cooldown)"
                     if e.get("in_horizontal_cooldown") else ""))
    elif action == "drain_begin":
        # handoff-vs-drain column: a handoff drain live-migrates its
        # pinned sessions; a plain drain waits them out. Older logs
        # don't carry the flag — show them as the legacy drain.
        mode = "handoff" if e.get("handoff") else "drain"
        detail = (f"draining {e.get('replica')} mode={mode} "
                  f"pressure={e.get('pressure')}")
    elif action == "drain_cancel":
        detail = (f"cancelled drain of {e.get('replica')}: "
                  f"{e.get('reason')}")
    elif action == "holdoff":
        detail = f"held off: {e.get('reason')}"
    else:
        detail = " ".join(f"{k}={v}" for k, v in sorted(e.items())
                          if k not in ("event", "action", "t"))
    # Multi-model logs (one controller per ModelGroup) tag events
    # with the group's model id; older logs simply don't carry it.
    if e.get("model"):
        detail = f"model={e['model']} {detail}"
    return f"  {rel}  {action:<12} {detail}"


def render(agg: dict) -> str:
    lines = ["autoscale timeline"]
    events = agg["timeline"]
    if not events:
        lines.append("  (no autoscale events in input)")
    else:
        t0 = next((e["t"] for e in events
                   if isinstance(e.get("t"), (int, float))), 0.0)
        for e in events:
            lines.append(_fmt_event(e, t0))
    if agg["episodes"]:
        lines.append("")
        lines.append("episodes (postmortems)")
        for ep in agg["episodes"]:
            sig = ep.get("signals") or {}
            model = (f"model={ep['model']} " if ep.get("model")
                     else "")
            # Episodes before the vertical actuators simply don't
            # carry the column; show them as horizontal.
            actuator = ep.get("actuator") or "horizontal"
            lines.append(
                f"  {ep.get('direction', '?'):<6} "
                f"{actuator:<10} "
                f"{ep.get('from_replicas')} -> {ep.get('to_replicas')} "
                f"{model}replica={ep.get('replica')} "
                f"trigger={ep.get('trigger')} "
                f"pressure_max={sig.get('max')}")
    lines.append("")
    lines.append("summary")
    lines.append(f"  scale_ups={agg['ups']} scale_downs={agg['downs']} "
                 f"holdoffs={agg['holdoffs']} repins={agg['repins']}")
    lines.append(f"  vertical_ups={agg['vertical_ups']} "
                 f"vertical_downs={agg['vertical_downs']} "
                 f"drain_cancels={agg['drain_cancels']}")
    lines.append(f"  migrations={agg['migrations']} "
                 f"migration_fallbacks={agg['migration_fallbacks']}")
    lines.append(f"  fleet_size=[{agg['size_min']}..{agg['size_max']}] "
                 f"replica_seconds~{agg['replica_seconds']}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="render an autoscale event log as a timeline")
    ap.add_argument("paths", nargs="+",
                    help="JSONL file(s) to read ('-' = stdin)")
    args = ap.parse_args(argv)
    records: List[dict] = []
    for path in args.paths:
        records.extend(load_records(_obs_common.read_lines(path)))
    print(render(aggregate(records)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
