#!/usr/bin/env python3
"""Per-request critical-path breakdown of a request-trace stream.

Reads the JSONL ``{"event": "trace", ...}`` records the gateway's
request tracing writes (``obs/context.py`` via the tracer sink, the
same stream span records ride) and answers the question the aggregate
histograms can't: for the requests that WERE slow, where did the time
go?

Three sections:

- **critical path**: total time across all finished requests
  attributed to each phase (queue / breaker_defer / retry_backoff /
  decode), with the share of total request time — the fleet-level
  answer to "what should we fix first";
- **slowest N**: the highest-latency requests, each with its status,
  attributed cause (the phase that ate the most time) and full phase
  breakdown — the per-request answer an SLO page needs;
- **alerts**: any ``kind="slo_burn"`` postmortem records found in the
  same stream (window, burn rate, trigger), so a single file tells the
  whole episode's story.

When the stream carries ``model`` / ``tenant`` attributes (the
multi-model multi-tenant gateway, ``serving/registry.py`` /
``serving/tenancy.py``), per-model and per-tenant attainment sections
are added (requests, ok count, SLO %, p95) — the isolation evidence
the tenancy scenario asserts on. Mixed-era streams are fine: records
without the keys simply don't join those sections.

Rescore-pass traces (``kind="rescore"``, the async LM second pass's
own ledger — ``serving/rescoring.py``) are deliberately EXCLUDED from
every first-pass section above: the second pass is off the critical
path, so folding its latencies into the request percentiles would
corrupt exactly the number the fast-path/slow-path split protects.
They get their own **rescoring** section instead (jobs, revisions,
p95, cumulative queue/compute split), present only when such records
exist — pre-rescoring streams render unchanged.

The ledger invariant (phases sum to ``latency_ms``, see
``TraceContext``) is re-checked here and reported as
``complete_pct`` — a reader of an old or foreign trace learns
immediately whether the attribution can be trusted.

Usage:
    python tools/slo_report.py traces.jsonl
    python tools/slo_report.py --slowest 20 --json traces.jsonl
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List

from _obs_common import load_records, read_lines  # shared loader

# Tolerance for the telescoping re-check, in ms (float adds only).
_EPS_MS = 1e-3


def aggregate(records: List[dict], slowest: int = 10) -> dict:
    """Fold trace/postmortem records into the report's data model."""
    traces = [r for r in records if r.get("event") == "trace"]
    # The second pass keeps its own ledger (kind="rescore") — folding
    # it into the first-pass sections would corrupt the very
    # percentiles the async split protects (module docstring).
    rescore = [r for r in traces if r.get("kind") == "rescore"]
    traces = [r for r in traces if r.get("kind") != "rescore"]
    finished = [r for r in traces
                if isinstance(r.get("latency_ms"), (int, float))]

    phase_ms: Dict[str, float] = {}
    statuses: Dict[str, int] = {}
    causes: Dict[str, int] = {}
    complete = 0
    for r in finished:
        statuses[str(r.get("status"))] = \
            statuses.get(str(r.get("status")), 0) + 1
        phases = r.get("phases") or {}
        for name, ms in phases.items():
            if isinstance(ms, (int, float)):
                phase_ms[name] = phase_ms.get(name, 0.0) + float(ms)
        cause = r.get("cause")
        if cause:
            causes[cause] = causes.get(cause, 0) + 1
        if abs(sum(v for v in phases.values()
                   if isinstance(v, (int, float)))
               - r["latency_ms"]) <= _EPS_MS:
            complete += 1

    total_ms = sum(phase_ms.values())
    lats = sorted(r["latency_ms"] for r in finished)

    def _pct(p: float):
        if not lats:
            return None
        k = min(len(lats) - 1,
                max(0, round(p / 100.0 * (len(lats) - 1))))
        return round(lats[k], 3)

    rows = sorted(finished, key=lambda r: -r["latency_ms"])[:slowest]
    slowest_rows = [{
        "rid": r.get("rid"),
        "status": r.get("status"),
        "latency_ms": round(r["latency_ms"], 3),
        "cause": r.get("cause"),
        "phases": {k: round(float(v), 3)
                   for k, v in (r.get("phases") or {}).items()
                   if isinstance(v, (int, float))},
        **{k: r[k] for k in ("tier", "replica", "attempts",
                             "model", "tenant")
           if k in r},
    } for r in rows]

    # Per-model (and per-tenant) attainment: the multi-model gateway
    # tags trace records with "model"/"tenant" (serving/registry.py,
    # serving/tenancy.py); mixed-era streams where only some records
    # carry them group the rest under the absent key being skipped.
    def group_by(attr: str) -> Dict[str, dict]:
        groups: Dict[str, dict] = {}
        g_lats: Dict[str, List[float]] = {}
        for r in finished:
            key = r.get(attr)
            if key is None:
                continue
            key = str(key)
            g = groups.setdefault(key, {"requests": 0, "ok": 0,
                                        "slo_ok": 0})
            g["requests"] += 1
            if r.get("status") == "ok":
                g["ok"] += 1
            if r.get("slo_ok"):
                g["slo_ok"] += 1
            g_lats.setdefault(key, []).append(float(r["latency_ms"]))
        for key, g in groups.items():
            lat = sorted(g_lats[key])
            k95 = min(len(lat) - 1,
                      max(0, round(0.95 * (len(lat) - 1))))
            g["latency_p95_ms"] = round(lat[k95], 3)
            g["slo_pct"] = round(100.0 * g["slo_ok"] / g["requests"], 2)
        return groups

    models = group_by("model")
    tenants = group_by("tenant")

    rescoring = None
    re_fin = [r for r in rescore
              if isinstance(r.get("latency_ms"), (int, float))]
    if re_fin:
        re_lats = sorted(r["latency_ms"] for r in re_fin)
        k95 = min(len(re_lats) - 1,
                  max(0, round(0.95 * (len(re_lats) - 1))))

        def _phase_sum(name: str) -> float:
            return sum(float((r.get("phases") or {}).get(name, 0.0))
                       for r in re_fin
                       if isinstance((r.get("phases") or {}).get(name),
                                     (int, float)))

        rescoring = {
            "jobs": len(re_fin),
            "revised": sum(1 for r in re_fin if r.get("revised")),
            "latency_p95_ms": round(re_lats[k95], 3),
            "queue_ms": round(_phase_sum("rescore_queue"), 3),
            "compute_ms": round(_phase_sum("rescore_compute"), 3),
        }

    alerts = [{
        "window": r.get("window"),
        "burn_rate": r.get("burn_rate"),
        "trigger": r.get("trigger"),
        "tier": r.get("tier"),
        "slowest_named": len(r.get("slowest_requests") or []),
    } for r in records if r.get("event") == "postmortem"
        and r.get("kind") == "slo_burn"]

    return {
        "requests": len(finished),
        "statuses": statuses,
        "complete_pct": round(100.0 * complete / len(finished), 2)
        if finished else None,
        "latency_p50_ms": _pct(50),
        "latency_p95_ms": _pct(95),
        "critical_path": {
            name: {"cum_ms": round(ms, 3),
                   "share_pct": round(100.0 * ms / total_ms, 2)
                   if total_ms > 0 else None,
                   "caused": causes.get(name, 0)}
            for name, ms in sorted(phase_ms.items(),
                                   key=lambda kv: -kv[1])},
        "slowest": slowest_rows,
        "alerts": alerts,
        **({"models": models} if models else {}),
        **({"tenants": tenants} if tenants else {}),
        **({"rescoring": rescoring} if rescoring else {}),
    }


def render(agg: dict) -> str:
    if not agg["requests"]:
        return "slo_report: no finished trace records\n"
    lines = [
        f"{agg['requests']} finished requests "
        f"({', '.join(f'{k}={v}' for k, v in sorted(agg['statuses'].items()))})"
        f" | ledger complete {agg['complete_pct']}% | "
        f"p50 {agg['latency_p50_ms']} ms, p95 {agg['latency_p95_ms']} ms",
        "",
        f"{'phase':<16} {'cum_ms':>12} {'share':>7} {'caused':>7}",
        "-" * 46,
    ]
    for name, ph in agg["critical_path"].items():
        share = (f"{ph['share_pct']:>6.1f}%"
                 if ph["share_pct"] is not None else "    n/a")
        lines.append(f"{name:<16} {ph['cum_ms']:>12.3f} {share} "
                     f"{ph['caused']:>7}")
    lines.append("")
    lines.append(f"slowest {len(agg['slowest'])} (attributed cause):")
    lines.append(f"  {'rid':<16} {'status':<8} {'latency_ms':>11} "
                 f"{'cause':<14} phases")
    for row in agg["slowest"]:
        phases = " ".join(f"{k}={v}" for k, v in row["phases"].items())
        extra = "".join(f" {k}={row[k]}"
                        for k in ("tier", "replica", "model", "tenant")
                        if k in row)
        lines.append(f"  {str(row['rid']):<16} {str(row['status']):<8} "
                     f"{row['latency_ms']:>11.3f} "
                     f"{str(row['cause']):<14} {phases}{extra}")
    for key, title in (("models", "model"), ("tenants", "tenant")):
        if not agg.get(key):
            continue
        lines.append("")
        lines.append(f"per-{title} attainment:")
        lines.append(f"  {title:<12} {'requests':>9} {'ok':>6} "
                     f"{'slo%':>7} {'p95_ms':>10}")
        for gid, g in sorted(agg[key].items()):
            lines.append(
                f"  {gid:<12} {g['requests']:>9} {g['ok']:>6} "
                f"{g['slo_pct']:>6.1f}% {g['latency_p95_ms']:>10.3f}")
    if agg.get("rescoring"):
        r = agg["rescoring"]
        lines.append("")
        lines.append(
            f"rescoring (second pass, off the critical path): "
            f"{r['jobs']} jobs, {r['revised']} revised | "
            f"p95 {r['latency_p95_ms']} ms | queue {r['queue_ms']} ms"
            f" / compute {r['compute_ms']} ms")
    if agg["alerts"]:
        lines.append("")
        lines.append("slo_burn alerts in stream:")
        for a in agg["alerts"]:
            tier = f" tier={a['tier']}" if a.get("tier") else ""
            lines.append(
                f"  window={a['window']} burn={a['burn_rate']}"
                f"{tier} ({a['slowest_named']} slowest named)")
    return "\n".join(lines) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="per-request critical-path breakdown of a "
                    "request-trace JSONL stream")
    ap.add_argument("trace", help="trace JSONL ('-' = stdin)")
    ap.add_argument("--slowest", type=int, default=10,
                    help="rows in the slowest-requests table")
    ap.add_argument("--json", action="store_true",
                    help="emit the aggregate as one JSON object "
                         "instead of the tables")
    args = ap.parse_args(argv)
    agg = aggregate(load_records(read_lines(args.trace)),
                    slowest=args.slowest)
    if args.json:
        print(json.dumps(agg))
    else:
        sys.stdout.write(render(agg))
    return 0 if agg["requests"] else 1


if __name__ == "__main__":
    sys.exit(main())
