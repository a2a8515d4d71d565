"""Fleet event timeline: ledger, correlation engine, flight recorder.

Covers the ISSUE 18 core surface in isolation, then (the scenario at
the end) a scripted fault day through the real controllers that must
fold into ONE incident: EventLog ring
semantics on an injected clock, the process-wide install/clear seam's
production-default cost path (publish is a no-op returning None when
no log is installed), causal folding in IncidentCorrelator — join via
``cause_seq`` chains, ancestor back-fill that stops at root/reaction
ancestors, orphan counting, quiet-close postmortems — and MetricSeries
before/during/after context windows.
"""

import json
import os
import subprocess
import sys

import pytest

from deepspeech_tpu.obs import timeline as tl
from deepspeech_tpu.obs.timeline import (
    EventLog, IncidentCorrelator, MetricSeries,
    REACTION_KINDS, RESOLUTION_KINDS, ROOT_KINDS,
)
from deepspeech_tpu.obs.metrics import MetricsRegistry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Clock:
    """Deterministic monotonic clock (seconds)."""

    def __init__(self, t=0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


def _log(clock, **kw):
    return EventLog(clock=clock, wall=lambda: 1.7e9 + clock.t, **kw)


@pytest.fixture(autouse=True)
def _no_process_timeline():
    """Each test starts and ends with no process-wide log installed."""
    tl.clear()
    yield
    tl.clear()


# -- EventLog -------------------------------------------------------------

def test_event_log_seq_and_queries():
    clock = Clock()
    log = _log(clock)
    s1 = log.publish("drain_begin", "autoscale", replica="r0")
    clock.t = 1.5
    s2 = log.publish("breaker_open", "pool", replica="r1",
                     cause_seq=s1, failures=2)
    assert (s1, s2) == (1, 2)
    assert len(log) == 2
    ev = log.get(s2)
    assert ev["kind"] == "breaker_open" and ev["t_mono"] == 1.5
    assert ev["cause_seq"] == s1
    assert ev["detail"] == {"failures": 2}
    # last_for: newest event naming the replica, None for strangers.
    assert log.last_for("r1") == s2 and log.last_for("r0") == s1
    assert log.last_for("r9") is None and log.last_for(None) is None
    assert [e["seq"] for e in log.recent()] == [1, 2]
    assert [e["seq"] for e in log.recent(1)] == [2]


def test_event_log_capacity_evicts_oldest():
    clock = Clock()
    log = _log(clock, capacity=3)
    for i in range(5):
        log.publish(f"k{i}", "src")
    assert len(log) == 3
    assert log.dropped == 2
    assert [e["seq"] for e in log.recent()] == [3, 4, 5]
    assert log.get(1) is None and log.get(4) is not None
    with pytest.raises(ValueError):
        EventLog(capacity=0)


def test_event_log_listener_and_registry_counter():
    clock = Clock()
    reg = MetricsRegistry()
    log = _log(clock, registry=reg)
    seen = []
    log.add_listener(seen.append)
    log.publish("migration", "migration", replica="r2", cause_seq=None)
    log.publish("migration", "migration")
    assert [e["kind"] for e in seen] == ["migration", "migration"]
    assert reg.counter("timeline_events",
                       labels={"kind": "migration"}) == 2


def test_event_log_to_record_schema_shape():
    clock = Clock(t=2.0)
    log = _log(clock)
    log.publish("fault_fire", "faults", replica="r0", cause_seq=None,
                point="gateway.dispatch")
    s2 = log.publish("vertical_up", "autoscale", model="m0", tier="bulk")
    rec = EventLog.to_record(log.get(1))
    assert rec["event"] == "timeline"
    assert rec["seq"] == 1 and rec["t_mono"] == 2.0
    assert rec["ts"] == pytest.approx(1.7e9 + 2.0)
    assert rec["kind"] == "fault_fire" and rec["source"] == "faults"
    assert rec["replica"] == "r0"
    assert "cause_seq" not in rec  # None is never serialized
    assert rec["detail"] == {"point": "gateway.dispatch"}
    rec2 = EventLog.to_record(log.get(s2))
    assert rec2["model"] == "m0" and rec2["tier"] == "bulk"
    assert "detail" not in rec2  # empty detail is elided


# -- process-wide install seam -------------------------------------------

def test_module_publish_is_noop_when_uninstalled():
    assert tl.active() is None
    assert tl.publish("drain_begin", "autoscale", replica="r0") is None
    assert tl.last_for("r0") is None


def test_module_install_routes_and_clear_restores():
    clock = Clock()
    log = tl.install(_log(clock))
    assert tl.active() is log
    seq = tl.publish("drain_begin", "autoscale", replica="r0")
    assert seq == 1 and tl.last_for("r0") == 1
    tl.clear()
    assert tl.active() is None
    assert tl.publish("drain_begin", "autoscale") is None
    assert len(log) == 1  # cleared log keeps its history


# -- MetricSeries ---------------------------------------------------------

def test_metric_series_family_sum_and_interval_gate():
    clock = Clock()
    reg = MetricsRegistry()
    reg.count("queue_depth", 3)
    reg.count("queue_depth", 2, labels={"tier": "bulk"})
    reg.gauge("availability", 0.5)
    series = MetricSeries(registry=reg, clock=clock, interval_s=1.0,
                          names=("queue_depth", "availability",
                                 "missing_family"))
    vals = series.sample()
    # Labeled variants fold into the family; absent families are
    # omitted, not zero-filled.
    assert vals == {"queue_depth": 5.0, "availability": 0.5}
    clock.t = 0.5
    assert series.maybe_sample() is None  # inside the interval
    clock.t = 1.0
    assert series.maybe_sample() is not None


def test_metric_series_context_before_during_after():
    clock = Clock()
    reg = MetricsRegistry()
    series = MetricSeries(registry=reg, clock=clock, interval_s=0.0,
                          names=("queue_depth",))
    reg.gauge("queue_depth", 1.0)
    series.sample(0.0)           # before the window
    reg.gauge("queue_depth", 9.0)
    series.sample(1.0)           # inside
    reg.gauge("queue_depth", 4.0)
    series.sample(2.0)           # inside
    reg.gauge("queue_depth", 2.0)
    series.sample(5.0)           # at/after end_t
    ctx = series.context(0.5, 5.0)
    assert ctx["before"] == {"queue_depth": 1.0}
    assert ctx["during"]["queue_depth"] == {"min": 2.0, "max": 9.0}
    assert ctx["after"] == {"queue_depth": 2.0}
    # A window nothing precedes or follows reports None, not {}.
    assert series.context(-1.0, 99.0)["before"] is None
    assert series.context(-1.0, 99.0)["after"] is None


# -- IncidentCorrelator ---------------------------------------------------

def _correlator(clock, **kw):
    pms = []
    kw.setdefault("postmortem_fn",
                  lambda kind, **rec: pms.append((kind, rec)))
    kw.setdefault("quiet_s", 5.0)
    return IncidentCorrelator(clock=clock, **kw), pms


def test_correlator_folds_cause_chain_into_one_incident():
    clock = Clock()
    log = _log(clock)
    corr, pms = _correlator(clock)
    corr.attach(log)
    root = log.publish("breaker_open", "pool", replica="r1")
    mid = log.publish("drain_cancel", "autoscale", replica="r0",
                      cause_seq=root)
    # Joins transitively through mid, not directly through root.
    log.publish("migration", "migration", replica="r0", cause_seq=mid)
    assert len(corr.open) == 1 and not corr.closed
    assert corr.orphans == 0
    inc = corr.open[0]
    assert inc["root"]["kind"] == "breaker_open"
    assert len(inc["events"]) == 3
    assert inc["replicas"] == {"r0", "r1"}
    # drain_cancel is a RESOLUTION kind: already marked resolved.
    assert inc["resolved"] and inc["resolution"] == "drain_cancel"
    clock.t = 10.0
    corr.poll()
    assert len(corr.closed) == 1 and not corr.open
    rec = corr.closed[0]
    assert rec["root_kind"] == "breaker_open"
    assert rec["resolution"] == "resolved"
    assert rec["n_events"] == 3
    assert rec["duration_s"] == pytest.approx(0.0)
    assert [e["seq"] for e in rec["chain"]] == [1, 2, 3]
    assert pms == [("incident", dict(rec, trigger="breaker_open"))]


def test_correlator_orphan_reaction_without_edge():
    clock = Clock()
    reg = MetricsRegistry()
    log = _log(clock)
    corr, _ = _correlator(clock, registry=reg)
    corr.attach(log)
    log.publish("migration", "migration", replica="r0")  # no cause
    log.publish("holdoff", "autoscale")  # ambient kind: not an orphan
    assert corr.orphans == 1
    assert [e["kind"] for e in corr.orphan_events] == ["migration"]
    assert reg.counter("timeline_orphans") == 1
    assert not corr.open  # orphans never open incidents


def test_correlator_backfills_ambient_prelude():
    """A count=2 fault's second fire joins fire #1's incident through
    the shared arming event — the ambient ancestors (fault_armed,
    drain_begin) are back-filled as prelude when fire #1 opens."""
    clock = Clock()
    log = _log(clock)
    corr, _ = _correlator(clock)
    corr.attach(log)
    drain = log.publish("drain_begin", "autoscale", replica="r0")
    armed = log.publish("fault_armed", "faults", replica="r0",
                        cause_seq=drain)
    log.publish("fault_fire", "faults", replica="r1", cause_seq=armed)
    log.publish("fault_fire", "faults", replica="r1", cause_seq=armed)
    assert len(corr.open) == 1
    inc = corr.open[0]
    # Prelude rides in causal order before the root.
    assert [e["kind"] for e in inc["events"]] == [
        "drain_begin", "fault_armed", "fault_fire", "fault_fire"]
    assert inc["root"]["kind"] == "fault_fire"
    assert inc["opened_t"] == pytest.approx(0.0)


def test_correlator_backfill_stops_at_prior_episode():
    """The ancestor walk must not absorb a previous incident's events:
    a root chained to a root/reaction ancestor starts its own story."""
    clock = Clock()
    log = _log(clock)
    corr, _ = _correlator(clock, quiet_s=1.0)
    corr.attach(log)
    log.publish("breaker_open", "pool", replica="r1")
    close = log.publish("breaker_close", "pool", replica="r1",
                        cause_seq=1)
    clock.t = 10.0
    corr.poll()  # episode one closes
    assert len(corr.closed) == 1
    # New fault chains (via last_for) to the closed episode's
    # breaker_close — a reaction kind, so the walk stops there.
    log.publish("fault_fire", "faults", replica="r1", cause_seq=close)
    assert len(corr.open) == 1
    assert [e["kind"] for e in corr.open[0]["events"]] == ["fault_fire"]


def test_correlator_flush_and_unresolved():
    clock = Clock()
    log = _log(clock)
    corr, pms = _correlator(clock)
    corr.attach(log)
    log.publish("slo_alert", "slo")
    corr.flush()
    assert not corr.open and len(corr.closed) == 1
    rec = corr.closed[0]
    assert rec["resolution"] == "unresolved"
    assert rec["resolution_kind"] is None
    assert pms[0][0] == "incident"


def test_correlator_metrics_context_and_status():
    clock = Clock()
    reg = MetricsRegistry()
    series = MetricSeries(registry=reg, clock=clock, interval_s=0.0,
                          names=("queue_depth",))
    reg.gauge("queue_depth", 7.0)
    series.sample(-1.0)  # a "before" sample predating the incident
    log = _log(clock)
    corr, _ = _correlator(clock, series=series, registry=reg)
    corr.attach(log)
    root = log.publish("breaker_open", "pool", replica="r1")
    clock.t = 1.0
    log.publish("breaker_close", "pool", replica="r1", cause_seq=root)
    st = corr.status()
    assert st["open"][0]["root_kind"] == "breaker_open"
    assert st["open"][0]["resolved"] is True
    assert st["closed"] == [] and st["orphans"] == 0
    clock.t = 10.0
    corr.poll()
    rec = corr.closed[0]
    assert rec["metrics"]["before"] == {"queue_depth": 7.0}
    assert rec["metrics"]["during"]["queue_depth"]["max"] == 7.0
    assert reg.counter("incidents_opened") == 1
    assert reg.counter("incidents_resolved") == 1
    st = corr.status()
    assert st["open"] == [] and len(st["closed"]) == 1


def test_correlator_offline_replay_matches_live():
    """Feeding to_record() JSONL shapes through observe() (what
    tools/incident_report.py replay does) folds identically to the
    live listener — one engine, two surfaces."""
    clock = Clock()
    log = _log(clock)
    corr_live, _ = _correlator(clock)
    corr_live.attach(log)
    root = log.publish("fault_fire", "faults", replica="r1")
    log.publish("migration", "migration", replica="r0", cause_seq=root)
    clock.t = 10.0
    corr_live.poll()
    records = [EventLog.to_record(e) for e in log.recent()]
    corr_replay, _ = _correlator(Clock())
    for rec in records:
        corr_replay.observe(rec)
    corr_replay.flush()
    live, replay = corr_live.closed[0], corr_replay.closed[0]
    for key in ("root_kind", "n_events", "replicas", "resolution"):
        assert live[key] == replay[key]
    assert [e["seq"] for e in replay["chain"]] \
        == [e["seq"] for e in live["chain"]]


def test_kind_taxonomies_are_disjoint_where_required():
    # A root kind must never be classed as reaction-only (would make
    # every incident's own root an orphan candidate).
    assert not (ROOT_KINDS & REACTION_KINDS)
    # Resolutions that are also reactions (breaker_close, drain_cancel)
    # is by design; sanity-pin membership the correlator relies on.
    assert "breaker_close" in RESOLUTION_KINDS & REACTION_KINDS
    assert "fault_fire" in ROOT_KINDS


def test_postmortem_seam_default_writes_incident_record():
    """Without an explicit postmortem_fn the correlator goes through
    the postmortem_link seam into resilience.postmortem — the
    circular-import inversion ISSUE 18 formalized."""
    import io
    from deepspeech_tpu.resilience import postmortem
    clock = Clock()
    sink = io.StringIO()
    postmortem.configure(sink=sink)
    try:
        log = _log(clock)
        corr = IncidentCorrelator(quiet_s=1.0, clock=clock).attach(log)
        log.publish("guardian_skip", "guardian")
        clock.t = 5.0
        corr.poll()
    finally:
        postmortem.configure()
    recs = [json.loads(ln) for ln in sink.getvalue().splitlines()]
    incident = [r for r in recs if r.get("kind") == "incident"]
    assert len(incident) == 1
    assert incident[0]["root_kind"] == "guardian_skip"
    assert incident[0]["event"] == "postmortem"


def test_incident_report_tool_renders_replayed_stream(tmp_path):
    """tools/incident_report.py reconstructs the same incident from a
    raw timeline JSONL file (no pre-correlated postmortems)."""
    clock = Clock()
    log = _log(clock)
    root = log.publish("breaker_open", "pool", replica="r1")
    clock.t = 0.25
    log.publish("breaker_close", "pool", replica="r1", cause_seq=root)
    path = tmp_path / "timeline.jsonl"
    path.write_text("".join(
        json.dumps(EventLog.to_record(e)) + "\n" for e in log.recent()))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "incident_report.py"), str(path)],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "incident #1" in out.stdout
    assert "root=breaker_open" in out.stdout
    assert "resolved (breaker_close)" in out.stdout
    assert "orphan reactions: 0" in out.stdout


# -- scenario: a scripted fault day is ONE incident -----------------------

def test_scenario_fault_day_through_real_controllers_is_one_incident(
        obs_lint, postmortems):
    """The real pool, breakers, gateway, autoscaler (with the vertical
    actuator), handoff router and an episode-relative fault plan on one
    virtual clock: a trough starts a drain, which arms a fault that
    fires twice on the only routable peer; its breaker opens; the
    controller cancels the drain and the peer's pinned sessions
    live-migrate; pressure inside the horizontal cooldown takes a
    vertical step; a probe closes the breaker. The correlator must
    fold that into exactly one incident rooted at the first fault fire
    and resolved by the breaker close, with no orphan reaction and the
    exact per-kind counts the script implies; ``incident_report``
    replayed over the JSONL reconstructs it; nothing is lost; all three
    streams lint clean."""
    from collections import Counter

    import numpy as np

    from scenario import ChunkLogManager
    from deepspeech_tpu.resilience import (CircuitBreaker, FaultPlan,
                                           FaultSpec, Retry, faults)
    from deepspeech_tpu.serving import (AutoscaleController,
                                        MicroBatchScheduler,
                                        MigrationController,
                                        PooledSessionRouter, Replica,
                                        ReplicaPool, ServingTelemetry)
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import incident_report
    finally:
        sys.path.pop(0)

    clock = Clock()
    tel = ServingTelemetry()
    log = tl.install(_log(clock, registry=tel))
    lines = []
    log.add_listener(lambda ev: lines.append(
        json.dumps(EventLog.to_record(ev), ensure_ascii=False)))
    series = MetricSeries(registry=tel, clock=clock, interval_s=0.02,
                          names=("autoscale_pressure",
                                 "autoscale_replicas"))
    pm = postmortems
    corr = IncidentCorrelator(quiet_s=2.0, clock=clock, series=series,
                              registry=tel,
                              postmortem_fn=pm.write).attach(log)
    chunk_log = []

    def replica(rid):
        return Replica(
            rid, lambda batch, plan: ["ok"] * plan.n_valid,
            telemetry=tel, clock=clock,
            session_factory=lambda: ChunkLogManager(chunk_log),
            breaker=CircuitBreaker(name=f"b{rid}", failure_threshold=2,
                                   cooldown_s=0.5, clock=clock,
                                   registry=tel))

    pool = ReplicaPool([replica("r0"), replica("r1")], clock=clock,
                       telemetry=tel, drain_window_s=0.25, handoff=True)
    sched = MicroBatchScheduler(
        (64, 128), 2, max_queue=24, default_deadline=0.05,
        default_timeout=60.0, max_attempts=8, clock=clock,
        telemetry=tel, pool=pool,
        retry_backoff=Retry(base_s=0.01, max_s=0.01, jitter=0.0,
                            name="gateway_dispatch"))
    mig = MigrationController(telemetry=tel, clock=clock,
                              postmortem_fn=pm.write)
    router = PooledSessionRouter(pool, migrator=mig)
    sids = []
    while len(sids) < 8 or not (pool.pins_on("r0")
                                and pool.pins_on("r1")):
        sids.append(f"s{len(sids)}")
        router.join(sids[-1])
    router.step({sid: "c0" for sid in sids})
    ctrl = AutoscaleController(
        pool, replica, scheduler=sched, min_replicas=1, max_replicas=2,
        up_pressure=0.45, down_pressure=0.2, hold_s=0.05,
        cooldown_s=10.0, rows_per_replica=4, drain_window_s=0.25,
        vertical_max_batch=4, vertical_hold_s=0.02,
        vertical_cooldown_s=5.0, handoff=True, telemetry=tel,
        clock=clock, postmortem_fn=pm.write)
    faults.install(FaultPlan([FaultSpec(
        "gateway.dispatch", "unavailable", prob=1.0, count=2,
        on_event="autoscale.drain_begin", arm_for_s=5.0)],
        clock=clock, registry=tel))

    def submit(n):
        return [sched.submit(np.zeros((32, 13), np.float32),
                             deadline=5.0, timeout=60.0)
                for _ in range(n)]

    def pump_until_done(rids):
        for _ in range(60):
            if all(r in sched.results for r in rids):
                return
            clock.t += 0.05
            sched.pump()

    try:
        ctrl.tick()                    # trough hold starts
        clock.t = 0.06
        ctrl.tick()                    # drain_begin arms the spec
        victim = ctrl.status()["victim"]
        peer = "r1" if victim == "r0" else "r0"
        rids = submit(4)
        clock.t = 0.08
        sched.pump()                   # two fires open the peer
        clock.t = 0.10
        ctrl.tick()                    # breaker_open -> drain_cancel
        moved = pool.pins_on(peer)
        router.step({sid: "c1" for sid in sids})
        rids += submit(8)
        clock.t = 0.12
        ctrl.tick()                    # holdoff, vertical hold starts
        clock.t = 0.15
        ctrl.tick()                    # vertical_up inside the cooldown
        pump_until_done(rids)
        clock.t = max(clock.t, 0.75)   # past the breaker cooldown
        rids += submit(8)
        pump_until_done(rids)
        pool.maintain(clock.t)
        router.step({sid: "c2" for sid in sids})
        for sid in sids:
            router.leave(sid)
        router.flush()
        clock.t += 2.5
        corr.poll()                    # quiet-close
    finally:
        faults.clear()

    assert len(corr.closed) == 1 and not corr.open
    inc = corr.closed[0]
    assert inc["root_kind"] == "fault_fire"
    assert (inc["resolution"], inc["resolution_kind"]) \
        == ("resolved", "breaker_close")
    assert corr.orphans == 0 and moved >= 1
    assert dict(Counter(ev["kind"] for ev in log.recent())) == {
        "init": 1, "drain_begin": 1, "fault_armed": 1, "fault_fire": 2,
        "breaker_open": 1, "drain_cancel": 1, "holdoff": 1,
        "migration": moved, "vertical_up": 1, "breaker_half_open": 1,
        "breaker_close": 1}
    assert inc["n_events"] == 9 + moved
    assert {e["kind"] for e in inc["chain"]} >= {
        "drain_begin", "fault_armed", "fault_fire", "breaker_open",
        "drain_cancel", "migration", "vertical_up",
        "breaker_half_open", "breaker_close"}
    assert set(inc["replicas"]) == {"r0", "r1"}
    assert inc["metrics"]["before"] is not None \
        and inc["metrics"]["after"] is not None \
        and inc["metrics"]["during"]
    assert (mig.migrations, mig.fallbacks) == (moved, 0)
    assert (ctrl.vertical_ups, ctrl.drain_cancels) == (1, 1)

    report = incident_report.aggregate([json.loads(ln) for ln in lines])
    assert len(report["incidents"]) == 1 and report["orphans"] == 0
    assert report["incidents"][0]["n_events"] == inc["n_events"]
    assert report["incidents"][0]["root_kind"] == "fault_fire"
    assert "incident #" in incident_report.render(report)

    assert [sched.results[r].status for r in rids] == ["ok"] * 20
    assert [router.final(sid) for sid in sids] == ["c0 c1 c2"] * len(sids)
    assert len(pm.recent("incident")) == 1
    assert obs_lint(lines, pm, tel) == []
