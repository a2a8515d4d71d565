#!/usr/bin/env python3
"""Fail if an obs JSONL stream violates the shared record schema.

Every observability record — ``MetricsRegistry.emit_jsonl`` snapshots,
``ServingTelemetry`` bench output, tracer span/compile records — rides
ONE schema so train/infer/serve/bench logs stay machine-consumable by
the same tooling (``tools/trace_report.py``, dashboards). The contract:

- the line parses as a JSON object and round-trips ``json.dumps``;
- every record carries a string ``event`` and a numeric ``ts``
  (wall-clock seconds);
- timing records (``event`` of ``span`` or ``compile``) additionally
  carry a numeric ``dur_ms`` and a string ``name``;
- the spans of the host's turn (``obs/trace.py``): a child span of a
  training step (``train.dispatch`` / ``.wait`` / ``.sync`` / ``.lr`` /
  ``.fetch`` / ``.emit``) carries the integer ``step`` it shares with
  its parent (``train.wait``: the step it blocks on, the one before
  its parent's), a child span of a served call (``infer.cache``,
  ``infer.prefill.dispatch`` / ``.wait``, ``infer.decode.dispatch`` /
  ``.fetch``) the integer ``call``, and ``host.gc`` (one garbage
  collection) an integer ``generation`` and ``collected`` — a child
  that does not say whose it is cannot be summed into its unit's
  turn, and a collection without its generation cannot be told from
  the cheap ones;
- postmortem records (``event`` of ``postmortem`` —
  ``resilience.postmortem``, one line per automatic intervention:
  quarantined sample/request, anomaly, rollback, stall) additionally
  carry a non-empty string ``kind`` and a string ``trigger``;
- the deployment-topology labels — ``replica`` (multi-replica serving
  plane, ``serving/pool.py``), ``tier`` (quality tiers,
  ``serving/scheduler.py``), ``version`` (rolling model swap,
  ``serving/rollout.py``), ``model`` (multi-model registry,
  ``serving/registry.py``), and ``tenant`` (multi-tenant admission,
  ``serving/tenancy.py``): wherever one appears — a ``replica="..."``
  / ``tier="..."`` / ``version="..."`` label on a snapshot series key,
  or the same-named field on a span/compile record — it must be a
  non-empty string, and within one snapshot record a metric *family*
  (series sharing a base name, e.g. ``gateway.dispatch_s`` and
  ``gateway.dispatch_s{replica="r0"}``) must not mix labeled and
  unlabeled series for that label: a reader aggregating the family
  would otherwise double- or under-count. Single-replica / tierless
  deployments stay fully unlabeled, pooled / tiered ones fully
  labeled — never both at once;
- the rollout metric families (``rollout_state``, ``canary_wer_delta``,
  ``rollout_swaps``, ``rollout_rollbacks``, ``rollout_paused``) must
  ALWAYS carry a ``version`` label: a version-less rollout series is
  unanswerable ("which rollout?") the moment two rollouts ever share a
  log;
- request-trace records (``event`` of ``trace`` — the
  ``obs/context.py`` phase ledger, one line per finished request when
  tracing is on) additionally carry a non-empty string ``rid``, a
  non-empty string ``status``, and a ``phases`` object mapping phase
  names to numeric milliseconds; ``latency_ms``, when present (always
  on finished requests), is numeric;
- the ``slo_burn_rate`` gauge family (``obs/slo.py``) must ALWAYS
  carry a ``window`` label: a window-less burn rate is unanswerable
  ("paging-fast or budget-slow?"), and the family follows the same
  all-or-nothing mixing rule as the topology labels;
- postmortem records with ``kind="slo_burn"`` (the burn-rate alert's
  page) additionally carry a non-empty string ``window`` and a numeric
  ``burn_rate`` — a page that doesn't say which window fired at what
  burn is undiagnosable;
- the ``autoscale_events`` counter family (``serving/autoscale.py``)
  must ALWAYS carry a non-empty ``direction`` label AND a non-empty
  ``actuator`` label (``horizontal`` | ``ladder`` | ``tier_mix``): an
  undirected scaling event can't be charged to growth or shrink, and
  an actuator-less one can't be charged to the replica axis or a
  vertical rung — capacity accounting over the log would be
  meaningless either way;
- postmortem records with ``kind="autoscale"`` (one per scaling
  episode, horizontal or vertical) additionally carry a non-empty
  string ``direction`` and numeric ``from_replicas`` /
  ``to_replicas`` — an episode record that doesn't say which way the
  fleet moved, from what size to what size, can't be replayed against
  the traffic curve (vertical episodes carry equal from/to: the fleet
  didn't move, the rung did);
- the fairness families (``slo_ok``, ``slo_miss``): a ``tenant``
  label never travels without a ``model`` label — per-tenant SLO
  attainment is only comparable within one model's serving plane
  (``serving/tenancy.py`` enforces this at submit; the lint catches
  any producer that doesn't);
- the ``rescore_shed`` counter family (``serving/rescoring.py``) must
  ALWAYS carry a non-empty ``reason`` label: rescoring is the first
  thing the plane sheds, so an unattributed shed can't distinguish
  "brownout working as designed" from "queue sized wrong" — the two
  opposite capacity actions;
- the ``compile_cache_*`` counter families (``serving/warmstore.py``
  — ``compile_cache_hit`` / ``_miss`` / ``_reject`` / ``_export``)
  must ALWAYS carry a non-empty ``rung`` label AND a non-empty
  ``tier`` label (same always-labeled rule as ``autoscale_events``'s
  direction): a bare series can't say which ``(B, T)`` executable was
  served warm or rejected, nor for which numeric family (``fp`` /
  ``int8`` / a quality tier) — and a reject whose rung is unknown is
  exactly the un-debuggable SIGABRT class the store exists to count;
- the migration families (``serving/migration.py`` —
  ``session_migrations`` / ``migration_latency`` counters+histogram,
  plus ``session_migration_fallbacks``) must ALWAYS carry a non-empty
  ``reason`` label, and the two handoff families additionally a
  non-empty ``replica`` label (the DESTINATION; ``model`` rides along
  under the usual topology rules in grouped pools): an unattributed
  migration can't be charged to the breaker trip / autoscale drain /
  rollout victim / resize that forced it, and a destination-less one
  can't be audited against the pin map;
- postmortem records with ``kind="migration"`` (one per live session
  handoff, cross-process handoff, or fallback) additionally carry
  non-empty strings ``outcome`` (``handoff`` | ``remote_handoff`` |
  ``fallback_drain`` | ``fallback_local``), ``reason``,
  ``src_replica`` and ``dst_replica``, and a numeric ``latency_ms`` —
  a migration record that doesn't say which way the session moved,
  why, and how long the stream stalled is unauditable against the
  zero-drain-wait claim; an out-of-enum outcome silently escapes
  every dashboard bucket;
- fleet-timeline records with ``kind`` of ``remote_begin`` /
  ``remote_ack`` / ``remote_fail`` (the cross-process handoff plane,
  ``serving/transport.py``) all carry non-empty string
  ``detail.sid``, ``detail.transfer_id`` and ``detail.peer`` — a
  transfer event that doesn't name the session, the idempotency key,
  and the wire peer can't be audited against the exactly-one-owner
  claim; ``remote_ack`` and ``remote_fail`` additionally carry a
  ``cause_seq`` edge back to their ``remote_begin``;
  ``remote_ack`` carries ``detail.status`` of ``imported`` or
  ``duplicate`` (the retried-send dedup verdict), and ``remote_fail``
  a non-empty ``detail.reason`` (the fallback-taxonomy bucket that
  armed the degradation ladder);
- fleet-timeline records with ``kind="retry_exhausted"`` (the
  ``resilience.retry`` give-up breadcrumb) carry a non-empty string
  ``detail.name`` (the policy that gave up) and a numeric
  ``detail.attempts`` — an exhaustion event that doesn't say which
  retry policy burned how many attempts can't explain the fallback
  it armed;
- postmortem records with ``kind="warm_start"`` (one per warm-store
  preload: replica init, autoscale scale-up, rollout re-admission)
  additionally carry a numeric ``warm_pct`` and a numeric
  ``compiles_avoided`` — a warm-start claim that doesn't say how warm
  the replica came up, avoiding how many compiles, can't be audited
  against the restart-latency band it justifies;
- fleet-timeline records (``event`` of ``timeline`` —
  ``obs/timeline.py``, one line per controller decision when
  ``serve.py --timeline`` is on) additionally carry an integer
  ``seq`` ≥ 1 (the ledger's monotone sequence number), a non-empty
  string ``kind`` and ``source``, and a numeric ``t_mono``;
  ``cause_seq``, when present, must be an integer with
  ``1 <= cause_seq < seq`` — an effect can't precede (or be) its own
  cause, and a dangling forward reference makes the causal chain
  unreplayable; ``detail``, when present, is an object;
- postmortem records with ``kind="incident"`` (the correlator's
  end-of-incident story, ``obs/timeline.py``) additionally carry a
  numeric ``duration_s``, a numeric ``n_events``, and a non-empty
  string ``root_kind`` — an incident that doesn't say what started
  it, how long it ran, or how many events it folded is not a
  postmortem, it's an anecdote;
- the ``sessions_recovered`` counter family
  (``serving/sessionstore.py`` — boot-time crash recovery) must
  ALWAYS carry an ``outcome`` label drawn from
  ``ok | torn | incompatible | stale``: an outcome-less recovery
  count can't be audited against the zero-lost-sessions claim, and an
  out-of-enum outcome silently escapes every dashboard bucket;
- postmortem records with ``kind="crash_recovery"`` (one per
  boot-time journal replay) additionally carry numeric ``recovered``,
  ``torn``, ``incompatible``, ``stale`` and ``latency_ms`` — a
  recovery story that doesn't say how many sessions came back, how
  many were lost to what, and how long the boot stalled is
  unauditable;
- fleet-timeline records with ``kind="recovery"`` (the replay's
  begin event and its per-session children) carry a ``detail.phase``
  of ``begin`` or ``session``; ``phase="session"`` events
  additionally carry a non-empty ``detail.sid``, a ``detail.outcome``
  from the recovery enum, and a ``cause_seq`` edge to the begin event
  (the correlator folds the whole replay into one incident);
  ``kind="recovery_done"`` events (the incident's resolution) carry
  ``cause_seq`` plus numeric ``detail.recovered`` and
  ``detail.latency_ms``;
- ``{"revision": {...}}`` records (the serve CLI's streamed
  second-pass revisions, ``serve.py --lm-rescore``) are their own
  record type — no ``event``/``ts``; they ride the CLI stream beside
  ``{"final"}`` lines — and must carry a non-empty string ``rid`` and
  a numeric ``score_delta``; ``old_text``/``new_text`` are strings
  when present, and a ``tenant`` never travels without a ``model``
  (same pairing rule as the fairness families: multi-tenant serving
  is multi-model serving).

That contract erodes one ad-hoc ``fh.write(...)`` at a time; this lint
makes the erosion loud. Wired into tier-1 via tests/test_tools.py.

Usage:
    python tools/check_obs_schema.py trace.jsonl [more.jsonl ...]
    some-producer | python tools/check_obs_schema.py -
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from deepspeech_tpu.obs.metrics import parse_series  # noqa: E402

TIMED_EVENTS = ("span", "compile")
# Spans of the host's turn -> the integer keys each must carry.
HOST_TURN_SPANS = {
    **{f"train.{k}": ("step",) for k in (
        "dispatch", "wait", "sync", "lr", "fetch", "emit")},
    **{f"infer.{k}": ("call",) for k in (
        "cache", "prefill.dispatch", "prefill.wait", "decode.dispatch",
        "decode.fetch")},
    "host.gc": ("generation", "collected"),
}
# Snapshot sections whose keys are (possibly labeled) series names.
SERIES_SECTIONS = ("counters", "gauges", "histograms")
# Labels holding the all-or-nothing family rule (module docstring).
TOPOLOGY_LABELS = ("replica", "tier", "version", "model", "tenant")
# Fairness families: tenant-sliced SLO attainment is only meaningful
# per model, so a tenant label requires a model label (and vice versa
# a tenant-less model-labeled series is fine, but tenant without
# model is not).
FAIRNESS_FAMILIES = ("slo_ok", "slo_miss")
# Rollout families must always carry a version label (docstring).
ROLLOUT_FAMILIES = ("rollout_state", "canary_wer_delta",
                    "rollout_swaps", "rollout_rollbacks",
                    "rollout_paused")
# Burn-rate families must always carry a window label (docstring).
WINDOWED_FAMILIES = ("slo_burn_rate",)
# Autoscale event families must always carry a direction label.
DIRECTIONAL_FAMILIES = ("autoscale_events",)
# Rescoring shed counters must always carry a reason label.
REASONED_FAMILIES = ("rescore_shed",)
# Migration families: reason always; the handoff pair also names the
# destination replica (serving/migration.py).
MIGRATION_FAMILIES = ("session_migrations", "migration_latency",
                      "session_migration_fallbacks")
MIGRATION_REPLICA_FAMILIES = ("session_migrations", "migration_latency")
# Warm-store compile-cache counters must always carry rung + tier.
COMPILE_CACHE_PREFIX = "compile_cache_"
# Crash-recovery counters must always carry an in-enum outcome label
# (serving/sessionstore.py).
RECOVERY_FAMILIES = ("sessions_recovered",)
RECOVERY_OUTCOMES = ("ok", "torn", "incompatible", "stale")
# Migration postmortem outcomes (serving/migration.py in-pool handoff
# + serving/transport.py cross-process ladder) — module docstring.
MIGRATION_OUTCOMES = ("handoff", "remote_handoff", "fallback_drain",
                      "fallback_local")
# Cross-process handoff timeline kinds (serving/transport.py).
REMOTE_HANDOFF_KINDS = ("remote_begin", "remote_ack", "remote_fail")
REMOTE_ACK_STATUSES = ("imported", "duplicate")


def validate_record(rec) -> List[str]:
    """Schema problems with one already-parsed record ([] = valid)."""
    problems = []
    if not isinstance(rec, dict):
        return [f"record is {type(rec).__name__}, not an object"]
    try:
        json.dumps(rec)
    except (TypeError, ValueError) as e:
        problems.append(f"not JSON-serializable: {e}")
    if "revision" in rec:
        # serve.py stream wrapper: {"revision": {...}} is its own
        # record type (module docstring) — validate the payload and
        # skip the event/ts contract.
        problems.extend(_lint_revision(rec["revision"]))
        return problems
    if not isinstance(rec.get("event"), str) or not rec.get("event"):
        problems.append("missing/invalid required key 'event' (string)")
    if not isinstance(rec.get("ts"), (int, float)) \
            or isinstance(rec.get("ts"), bool):
        problems.append("missing/invalid required key 'ts' (number)")
    if rec.get("event") in TIMED_EVENTS:
        if not isinstance(rec.get("dur_ms"), (int, float)) \
                or isinstance(rec.get("dur_ms"), bool):
            problems.append(
                "timing record missing/invalid 'dur_ms' (number)")
        if not isinstance(rec.get("name"), str) or not rec.get("name"):
            problems.append("timing record missing 'name' (string)")
        for key in HOST_TURN_SPANS.get(rec.get("name"), ()):
            if not isinstance(rec.get(key), int) \
                    or isinstance(rec.get(key), bool):
                problems.append(
                    f"{rec['name']} span missing/invalid {key!r} "
                    f"(integer)")
    if rec.get("event") == "postmortem":
        if not isinstance(rec.get("kind"), str) or not rec.get("kind"):
            problems.append(
                "postmortem record missing/invalid 'kind' (string)")
        if not isinstance(rec.get("trigger"), str):
            problems.append(
                "postmortem record missing/invalid 'trigger' (string)")
        if rec.get("kind") == "slo_burn":
            if not isinstance(rec.get("window"), str) \
                    or not rec.get("window"):
                problems.append("slo_burn postmortem missing/invalid "
                                "'window' (string)")
            if not isinstance(rec.get("burn_rate"), (int, float)) \
                    or isinstance(rec.get("burn_rate"), bool):
                problems.append("slo_burn postmortem missing/invalid "
                                "'burn_rate' (number)")
        if rec.get("kind") == "autoscale":
            if not isinstance(rec.get("direction"), str) \
                    or not rec.get("direction"):
                problems.append("autoscale postmortem missing/invalid "
                                "'direction' (string)")
            for key in ("from_replicas", "to_replicas"):
                if not isinstance(rec.get(key), (int, float)) \
                        or isinstance(rec.get(key), bool):
                    problems.append(
                        f"autoscale postmortem missing/invalid "
                        f"{key!r} (number)")
        if rec.get("kind") == "migration":
            for key in ("outcome", "reason", "src_replica",
                        "dst_replica"):
                if not isinstance(rec.get(key), str) \
                        or not rec.get(key):
                    problems.append(
                        f"migration postmortem missing/invalid "
                        f"{key!r} (string)")
            if isinstance(rec.get("outcome"), str) \
                    and rec.get("outcome") \
                    and rec["outcome"] not in MIGRATION_OUTCOMES:
                problems.append(
                    f"migration postmortem 'outcome' must be one of "
                    f"{list(MIGRATION_OUTCOMES)}, got "
                    f"{rec['outcome']!r}")
            if not isinstance(rec.get("latency_ms"), (int, float)) \
                    or isinstance(rec.get("latency_ms"), bool):
                problems.append(
                    "migration postmortem missing/invalid "
                    "'latency_ms' (number)")
        if rec.get("kind") == "warm_start":
            for key in ("warm_pct", "compiles_avoided"):
                if not isinstance(rec.get(key), (int, float)) \
                        or isinstance(rec.get(key), bool):
                    problems.append(
                        f"warm_start postmortem missing/invalid "
                        f"{key!r} (number)")
        if rec.get("kind") == "crash_recovery":
            for key in ("recovered", "torn", "incompatible", "stale",
                        "latency_ms"):
                if not isinstance(rec.get(key), (int, float)) \
                        or isinstance(rec.get(key), bool):
                    problems.append(
                        f"crash_recovery postmortem missing/invalid "
                        f"{key!r} (number)")
        if rec.get("kind") == "incident":
            for key in ("duration_s", "n_events"):
                if not isinstance(rec.get(key), (int, float)) \
                        or isinstance(rec.get(key), bool):
                    problems.append(
                        f"incident postmortem missing/invalid "
                        f"{key!r} (number)")
            if not isinstance(rec.get("root_kind"), str) \
                    or not rec.get("root_kind"):
                problems.append(
                    "incident postmortem missing/invalid "
                    "'root_kind' (string)")
    if rec.get("event") == "timeline":
        seq = rec.get("seq")
        if not isinstance(seq, int) or isinstance(seq, bool) \
                or seq < 1:
            problems.append(
                "timeline record missing/invalid 'seq' (integer >= 1)")
        for key in ("kind", "source"):
            if not isinstance(rec.get(key), str) or not rec.get(key):
                problems.append(
                    f"timeline record missing/invalid {key!r} "
                    f"(string)")
        if not isinstance(rec.get("t_mono"), (int, float)) \
                or isinstance(rec.get("t_mono"), bool):
            problems.append(
                "timeline record missing/invalid 't_mono' (number)")
        if "cause_seq" in rec and rec["cause_seq"] is not None:
            cs = rec["cause_seq"]
            if not isinstance(cs, int) or isinstance(cs, bool) \
                    or cs < 1 or (isinstance(seq, int)
                                  and not isinstance(seq, bool)
                                  and cs >= seq):
                problems.append(
                    "timeline 'cause_seq' must be an integer with "
                    "1 <= cause_seq < seq (an effect cannot precede "
                    "its cause)")
        if "detail" in rec and not isinstance(rec["detail"], dict):
            problems.append("timeline 'detail' must be an object")
        problems.extend(_lint_recovery_timeline(rec))
        problems.extend(_lint_remote_timeline(rec))
    if rec.get("event") == "trace":
        if not isinstance(rec.get("rid"), str) or not rec.get("rid"):
            problems.append(
                "trace record missing/invalid 'rid' (string)")
        if not isinstance(rec.get("status"), str) \
                or not rec.get("status"):
            problems.append(
                "trace record missing/invalid 'status' (string)")
        phases = rec.get("phases")
        if not isinstance(phases, dict):
            problems.append(
                "trace record missing/invalid 'phases' (object)")
        else:
            for k, v in phases.items():
                if not isinstance(v, (int, float)) \
                        or isinstance(v, bool):
                    problems.append(
                        f"trace phase {k!r} must be numeric ms")
        if "latency_ms" in rec and (
                not isinstance(rec["latency_ms"], (int, float))
                or isinstance(rec["latency_ms"], bool)):
            problems.append("trace 'latency_ms' must be numeric")
    for label in TOPOLOGY_LABELS:
        if label in rec and (not isinstance(rec[label], str)
                             or not rec[label]):
            problems.append(
                f"'{label}' field must be a non-empty string")
        problems.extend(_lint_labeled_series(rec, label))
    problems.extend(_lint_rollout_series(rec))
    problems.extend(_lint_window_series(rec))
    problems.extend(_lint_direction_series(rec))
    problems.extend(_lint_reason_series(rec))
    problems.extend(_lint_migration_series(rec))
    problems.extend(_lint_compile_cache_series(rec))
    problems.extend(_lint_recovery_series(rec))
    problems.extend(_lint_fairness_series(rec))
    return problems


def _lint_recovery_timeline(rec: dict) -> List[str]:
    """``kind="recovery"`` / ``kind="recovery_done"`` timeline rules
    (module docstring): a per-session recovery event that doesn't say
    which session, with what outcome, caused by which replay, can't be
    audited against the journal it replayed."""
    problems = []
    kind = rec.get("kind")
    detail = rec.get("detail")
    detail = detail if isinstance(detail, dict) else {}
    if kind == "recovery":
        phase = detail.get("phase")
        if phase not in ("begin", "session"):
            problems.append(
                "recovery timeline record needs detail.phase of "
                "'begin' or 'session'")
        if phase == "session":
            if not isinstance(detail.get("sid"), str) \
                    or not detail.get("sid"):
                problems.append(
                    "recovery session event missing/invalid "
                    "detail.sid (string)")
            if detail.get("outcome") not in RECOVERY_OUTCOMES:
                problems.append(
                    f"recovery session event detail.outcome must be "
                    f"one of {list(RECOVERY_OUTCOMES)}, got "
                    f"{detail.get('outcome')!r}")
            if rec.get("cause_seq") is None:
                problems.append(
                    "recovery session event missing 'cause_seq' "
                    "(the replay's begin event)")
    elif kind == "recovery_done":
        if rec.get("cause_seq") is None:
            problems.append(
                "recovery_done event missing 'cause_seq' (the "
                "replay's begin event)")
        for key in ("recovered", "latency_ms"):
            if not isinstance(detail.get(key), (int, float)) \
                    or isinstance(detail.get(key), bool):
                problems.append(
                    f"recovery_done event missing/invalid "
                    f"detail.{key} (number)")
    return problems


def _lint_remote_timeline(rec: dict) -> List[str]:
    """``kind="remote_begin"/"remote_ack"/"remote_fail"`` and
    ``kind="retry_exhausted"`` timeline rules (module docstring): a
    cross-process transfer event that doesn't name the session, the
    idempotency key, and the peer can't be audited against the
    exactly-one-owner claim."""
    problems = []
    kind = rec.get("kind")
    detail = rec.get("detail")
    detail = detail if isinstance(detail, dict) else {}
    if kind in REMOTE_HANDOFF_KINDS:
        for key in ("sid", "transfer_id", "peer"):
            if not isinstance(detail.get(key), str) \
                    or not detail.get(key):
                problems.append(
                    f"{kind} event missing/invalid detail.{key} "
                    f"(string)")
        if kind in ("remote_ack", "remote_fail") \
                and rec.get("cause_seq") is None:
            problems.append(
                f"{kind} event missing 'cause_seq' (the transfer's "
                f"remote_begin event)")
        if kind == "remote_ack" \
                and detail.get("status") not in REMOTE_ACK_STATUSES:
            problems.append(
                f"remote_ack event detail.status must be one of "
                f"{list(REMOTE_ACK_STATUSES)}, got "
                f"{detail.get('status')!r}")
        if kind == "remote_fail" and (
                not isinstance(detail.get("reason"), str)
                or not detail.get("reason")):
            problems.append(
                "remote_fail event missing/invalid detail.reason "
                "(string: the fallback-taxonomy bucket)")
    elif kind == "retry_exhausted":
        if not isinstance(detail.get("name"), str) \
                or not detail.get("name"):
            problems.append(
                "retry_exhausted event missing/invalid detail.name "
                "(string: the policy that gave up)")
        if not isinstance(detail.get("attempts"), (int, float)) \
                or isinstance(detail.get("attempts"), bool):
            problems.append(
                "retry_exhausted event missing/invalid "
                "detail.attempts (number)")
    return problems


def _lint_recovery_series(rec: dict) -> List[str]:
    """Crash-recovery counters must always carry an ``outcome`` label
    from the recovery enum (module docstring) — every replayed record
    lands in exactly one bucket."""
    problems = []
    for section in SERIES_SECTIONS:
        series_map = rec.get(section)
        if not isinstance(series_map, dict):
            continue
        for series in series_map:
            base, labels = parse_series(str(series))
            if base not in RECOVERY_FAMILIES:
                continue
            if labels.get("outcome") not in RECOVERY_OUTCOMES:
                problems.append(
                    f"{section} series {series!r}: recovery family "
                    f"{base!r} requires an 'outcome' label from "
                    f"{list(RECOVERY_OUTCOMES)}")
    return problems


def _lint_revision(rev) -> List[str]:
    """``{"revision": {...}}`` payload rules (module docstring): a
    revision that doesn't say which request it revises, or by how
    much the LM preferred the new text, can't be audited against the
    first-pass stream."""
    if not isinstance(rev, dict):
        return [f"'revision' payload is {type(rev).__name__}, "
                "not an object"]
    problems = []
    if not isinstance(rev.get("rid"), str) or not rev.get("rid"):
        problems.append(
            "revision record missing/invalid 'rid' (string)")
    if not isinstance(rev.get("score_delta"), (int, float)) \
            or isinstance(rev.get("score_delta"), bool):
        problems.append(
            "revision record missing/invalid 'score_delta' (number)")
    for key in ("old_text", "new_text"):
        if key in rev and not isinstance(rev[key], str):
            problems.append(f"revision {key!r} must be a string")
    if "rescore_latency_ms" in rev and (
            not isinstance(rev["rescore_latency_ms"], (int, float))
            or isinstance(rev["rescore_latency_ms"], bool)):
        problems.append("revision 'rescore_latency_ms' must be numeric")
    for key in ("model", "tenant"):
        if key in rev and (not isinstance(rev[key], str)
                           or not rev[key]):
            problems.append(
                f"revision {key!r} must be a non-empty string")
    if "tenant" in rev and "model" not in rev:
        problems.append(
            "revision record carries 'tenant' without 'model' "
            "(multi-tenant serving is multi-model serving)")
    return problems


def _lint_reason_series(rec: dict) -> List[str]:
    """Rescoring shed counters must always carry a non-empty
    ``reason`` label (module docstring) — every shed has exactly one
    gate that refused it."""
    problems = []
    for section in SERIES_SECTIONS:
        series_map = rec.get(section)
        if not isinstance(series_map, dict):
            continue
        for series in series_map:
            base, labels = parse_series(str(series))
            if base in REASONED_FAMILIES and not labels.get("reason"):
                problems.append(
                    f"{section} series {series!r}: rescoring family "
                    f"{base!r} requires a non-empty 'reason' label")
    return problems


def _lint_migration_series(rec: dict) -> List[str]:
    """Migration families must always carry a non-empty ``reason``
    label, and the handoff pair (``session_migrations`` /
    ``migration_latency``) a non-empty ``replica`` label naming the
    destination (module docstring)."""
    problems = []
    for section in SERIES_SECTIONS:
        series_map = rec.get(section)
        if not isinstance(series_map, dict):
            continue
        for series in series_map:
            base, labels = parse_series(str(series))
            if base not in MIGRATION_FAMILIES:
                continue
            if not labels.get("reason"):
                problems.append(
                    f"{section} series {series!r}: migration family "
                    f"{base!r} requires a non-empty 'reason' label")
            if base in MIGRATION_REPLICA_FAMILIES \
                    and not labels.get("replica"):
                problems.append(
                    f"{section} series {series!r}: migration family "
                    f"{base!r} requires a non-empty 'replica' label "
                    f"(the destination)")
    return problems


def _lint_compile_cache_series(rec: dict) -> List[str]:
    """Warm-store compile-cache counters must always carry a non-empty
    ``rung`` label AND a non-empty ``tier`` label (module docstring) —
    every hit/miss/reject/export concerns exactly one ``(B, T)``
    executable of exactly one numeric family."""
    problems = []
    for section in SERIES_SECTIONS:
        series_map = rec.get(section)
        if not isinstance(series_map, dict):
            continue
        for series in series_map:
            base, labels = parse_series(str(series))
            if not base.startswith(COMPILE_CACHE_PREFIX):
                continue
            if not labels.get("rung"):
                problems.append(
                    f"{section} series {series!r}: compile-cache "
                    f"family {base!r} requires a non-empty 'rung' "
                    f"label")
            if not labels.get("tier"):
                problems.append(
                    f"{section} series {series!r}: compile-cache "
                    f"family {base!r} requires a non-empty 'tier' "
                    f"label")
    return problems


def _lint_fairness_series(rec: dict) -> List[str]:
    """Fairness hygiene: a tenant-labeled SLO series (``slo_ok`` /
    ``slo_miss``) must also carry a ``model`` label — per-tenant
    attainment is only comparable within one model's serving plane, so
    the labels travel together (both or neither)."""
    problems = []
    for section in SERIES_SECTIONS:
        series_map = rec.get(section)
        if not isinstance(series_map, dict):
            continue
        for series in series_map:
            base, labels = parse_series(str(series))
            if base in FAIRNESS_FAMILIES and "tenant" in labels \
                    and "model" not in labels:
                problems.append(
                    f"{section} series {series!r}: fairness family "
                    f"{base!r} carries a 'tenant' label without a "
                    f"'model' label")
    return problems


def _lint_rollout_series(rec: dict) -> List[str]:
    """Rollout metric families must always carry a ``version`` label
    (module docstring) — they only ever exist in the context of one
    specific rollout."""
    problems = []
    for section in SERIES_SECTIONS:
        series_map = rec.get(section)
        if not isinstance(series_map, dict):
            continue
        for series in series_map:
            base, labels = parse_series(str(series))
            if base in ROLLOUT_FAMILIES and "version" not in labels:
                problems.append(
                    f"{section} series {series!r}: rollout family "
                    f"{base!r} requires a 'version' label")
    return problems


def _lint_window_series(rec: dict) -> List[str]:
    """Burn-rate families must always carry a non-empty ``window``
    label (module docstring) — and since every series is labeled, the
    family can never mix labeled and unlabeled either."""
    problems = []
    for section in SERIES_SECTIONS:
        series_map = rec.get(section)
        if not isinstance(series_map, dict):
            continue
        for series in series_map:
            base, labels = parse_series(str(series))
            if base in WINDOWED_FAMILIES and not labels.get("window"):
                problems.append(
                    f"{section} series {series!r}: burn-rate family "
                    f"{base!r} requires a non-empty 'window' label")
    return problems


def _lint_direction_series(rec: dict) -> List[str]:
    """Autoscale event families must always carry a non-empty
    ``direction`` label AND a non-empty ``actuator`` label (module
    docstring) — every scaling event is growth or shrink on exactly
    one axis: the replica count ("horizontal") or a vertical rung
    ("ladder" / "tier_mix")."""
    problems = []
    for section in SERIES_SECTIONS:
        series_map = rec.get(section)
        if not isinstance(series_map, dict):
            continue
        for series in series_map:
            base, labels = parse_series(str(series))
            if base not in DIRECTIONAL_FAMILIES:
                continue
            if not labels.get("direction"):
                problems.append(
                    f"{section} series {series!r}: autoscale family "
                    f"{base!r} requires a non-empty 'direction' label")
            if not labels.get("actuator"):
                problems.append(
                    f"{section} series {series!r}: autoscale family "
                    f"{base!r} requires a non-empty 'actuator' label")
    return problems


def _lint_labeled_series(rec: dict, label: str) -> List[str]:
    """Topology-label hygiene across a snapshot record's series maps:
    empty ``label`` values, and families mixing ``label``-labeled with
    unlabeled series (see module docstring). Applied per label in
    TOPOLOGY_LABELS — a family may carry both replica and tier, but
    for each label it is all-or-nothing."""
    problems = []
    for section in SERIES_SECTIONS:
        series_map = rec.get(section)
        if not isinstance(series_map, dict):
            continue
        families: dict = {}
        for series in series_map:
            base, labels = parse_series(str(series))
            has_label = label in labels
            if has_label and not labels[label]:
                problems.append(
                    f"{section} series {series!r}: empty {label!r} "
                    "label")
            families.setdefault(base, set()).add(has_label)
        for base in sorted(families):
            if len(families[base]) > 1:
                problems.append(
                    f"{section} family {base!r} mixes {label}-labeled "
                    "and unlabeled series")
    return problems


def scan(lines) -> List[tuple]:
    """(lineno, problem) for every schema violation in a JSONL stream.
    Blank lines are allowed (trailing newline idiom)."""
    out = []
    for n, line in enumerate(lines, 1):
        if not line.strip():
            continue
        try:
            rec = json.loads(line)
        except json.JSONDecodeError as e:
            out.append((n, f"invalid JSON: {e}"))
            continue
        for p in validate_record(rec):
            out.append((n, p))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="lint: obs JSONL records must carry the shared "
                    "event/ts(/dur_ms) schema")
    ap.add_argument("paths", nargs="+",
                    help="JSONL file(s) to validate ('-' = stdin)")
    args = ap.parse_args(argv)
    bad = 0
    checked = 0
    for path in args.paths:
        if path == "-":
            lines = sys.stdin.read().splitlines()
        else:
            with open(path, errors="replace") as fh:
                lines = fh.read().splitlines()
        checked += sum(1 for l in lines if l.strip())
        for n, problem in scan(lines):
            bad += 1
            print(f"check_obs_schema: {path}:{n}: {problem}",
                  file=sys.stderr)
    if bad:
        print(f"check_obs_schema: {bad} schema violation(s)",
              file=sys.stderr)
        return 1
    print(f"check_obs_schema: OK ({checked} records)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
