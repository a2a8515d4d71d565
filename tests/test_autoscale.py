"""Closed-loop autoscaling: AutoscaleController contract tests.

Covers the ISSUE-10 acceptance list: hysteresis (hold time, mid-band
reset, one-poll blips never resize), the cooldown window, min/max fleet
bounds, rollout and open-breaker hold-off (with resume), scale-down as
drain-before-remove over live pinned sessions (zero lost chunks), the
gateway-capacity coupling with its bounded shrink, every pressure
signal in isolation, and the ``kind="autoscale"`` postmortem /
``autoscale_events`` direction label round-trip through
``tools/check_obs_schema.py``.

ISSUE-14 widened the action space: the vertical actuators (rung-ladder
height, premium->bulk tier shift) step inside the horizontal cooldown
with their own hysteresis, disengage before any drain, and restore the
scheduler's baselines exactly; a peer breaker opening mid-drain
cancels the episode and un-parks the victim. Those contracts are
covered here too (the chunk-level races live in
tests/test_availability_races.py).

Everything rides an injectable virtual clock with echo-backend
Replicas and a stub (or real) scheduler — no model, no device, no
sleeping, deterministic.
"""

import io
import json
import os
import sys

import numpy as np
import pytest

from deepspeech_tpu.resilience import CircuitBreaker
from deepspeech_tpu.serving import (AutoscaleController,
                                    MicroBatchScheduler,
                                    OverloadRejected,
                                    PooledSessionRouter, Replica,
                                    ReplicaPool, ServingTelemetry,
                                    TrafficModel)
from deepspeech_tpu.serving.autoscale import (AUTOSCALE_DRAINING,
                                              AUTOSCALE_HOLDOFF,
                                              AUTOSCALE_STEADY)
from deepspeech_tpu.serving.replica import STATE_DRAINING, STATE_PARKED

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EDGES = (64, 128)
NF = 13


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _echo(tag):
    def fn(batch, plan):
        return [f"{tag}:B{plan.batch_pad}T{plan.bucket_frames}"
                ] * plan.n_valid
    return fn


def _breaker(clock, tel, name, threshold=2, cooldown=1.0):
    return CircuitBreaker(name=name, failure_threshold=threshold,
                          cooldown_s=cooldown, clock=clock,
                          registry=tel)


def _feat(n):
    return np.zeros((n, NF), np.float32)


def _replica(rid, clock, tel, **kw):
    return Replica(rid, _echo(rid), telemetry=tel, clock=clock,
                   breaker=_breaker(clock, tel, f"b{rid}"), **kw)


def _pool(n, clock, tel, drain_window_s=0.25, **rep_kw):
    reps = [_replica(f"r{k}", clock, tel, **rep_kw) for k in range(n)]
    return ReplicaPool(reps, clock=clock, telemetry=tel,
                       drain_window_s=drain_window_s)


class StubSched:
    """Just the surface the controller reads/writes: pending,
    max_queue, set_max_queue with the real bounded-shrink clamp."""

    def __init__(self, max_queue=8, pending=0):
        self.max_queue = max_queue
        self.pending = pending
        self.applied = []

    def set_max_queue(self, n):
        got = max(int(n), self.pending, 1)
        self.max_queue = got
        self.applied.append(got)
        return got


def _ctrl(pool, clock, tel, **kw):
    kw.setdefault("min_replicas", 1)
    kw.setdefault("max_replicas", 3)
    kw.setdefault("up_pressure", 0.7)
    kw.setdefault("down_pressure", 0.25)
    kw.setdefault("hold_s", 0.05)
    kw.setdefault("cooldown_s", 1.0)
    kw.setdefault("telemetry", tel)
    kw.setdefault("clock", clock)
    kw.setdefault("postmortem_fn", lambda *a, **k: None)
    factory = kw.pop("factory", None) or (
        lambda rid: _replica(rid, clock, tel))
    return AutoscaleController(pool, factory, **kw)


# -- constructor contracts ------------------------------------------------

def test_constructor_validation():
    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(1, clock, tel)
    fac = lambda rid: _replica(rid, clock, tel)   # noqa: E731
    with pytest.raises(ValueError):
        AutoscaleController(pool, fac, min_replicas=0)
    with pytest.raises(ValueError):
        AutoscaleController(pool, fac, min_replicas=3, max_replicas=2)
    with pytest.raises(ValueError):
        AutoscaleController(pool, fac, up_pressure=0.3,
                            down_pressure=0.5)
    with pytest.raises(ValueError):
        AutoscaleController(pool, fac, rows_per_replica=0)
    with pytest.raises(ValueError):
        AutoscaleController(pool, fac, dispatch_budget_s=-1)
    with pytest.raises(ValueError):
        AutoscaleController(pool, fac, slo_burn_budget=0)


def test_init_emits_event_and_gauges():
    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(2, clock, tel)
    seen = []
    ctrl = _ctrl(pool, clock, tel, on_event=seen.append)
    assert ctrl.state == AUTOSCALE_STEADY
    assert [e["action"] for e in seen] == ["init"]
    assert seen[0]["replicas"] == 2
    assert tel.gauges["autoscale_replicas"] == 2
    assert tel.gauges["autoscale_state"] == 0


# -- hysteresis: hold, blips, mid-band reset ------------------------------

def test_scale_up_needs_sustained_pressure():
    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(1, clock, tel)
    sched = StubSched(max_queue=8, pending=8)      # pressure 1.0
    ctrl = _ctrl(pool, clock, tel, scheduler=sched)
    ctrl.tick()
    assert len(pool) == 1                          # hold not yet earned
    clock.t = 0.06
    ctrl.tick()
    assert len(pool) == 2
    assert ctrl.scale_ups == 1
    # The newcomer got a controller-allocated rid and is routable.
    new = [r for r in pool if r.rid.startswith("a")]
    assert len(new) == 1 and new[0].can_route(clock.t)
    assert tel.counters[
        'autoscale_events{actuator="horizontal",direction="up"}'] == 1
    assert tel.gauges["autoscale_replicas"] == 2
    # Capacity followed the fleet: 8 per replica x 2 replicas.
    assert sched.applied == [16]
    assert tel.gauges["autoscale_capacity"] == 16
    ep = ctrl.episodes[0]
    assert (ep["direction"], ep["from_replicas"],
            ep["to_replicas"]) == ("up", 1, 2)
    assert ep["pressure"]["max"] == 1.0


def test_one_poll_blip_never_scales():
    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(1, clock, tel)
    sched = StubSched(max_queue=8, pending=8)
    ctrl = _ctrl(pool, clock, tel, scheduler=sched)
    ctrl.tick()                    # blip: above for one poll...
    sched.pending = 4              # ...back to mid-band before hold_s
    clock.t = 0.03
    ctrl.tick()
    sched.pending = 8
    clock.t = 0.04
    ctrl.tick()                    # above again: the timer restarted
    clock.t = 0.08                 # 0.04s sustained < hold_s
    ctrl.tick()
    assert len(pool) == 1 and ctrl.scale_ups == 0
    clock.t = 0.10                 # 0.06s sustained >= hold_s
    ctrl.tick()
    assert len(pool) == 2


def test_cooldown_blocks_back_to_back_episodes():
    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(1, clock, tel)
    sched = StubSched(max_queue=8, pending=8)
    ctrl = _ctrl(pool, clock, tel, scheduler=sched, cooldown_s=1.0)
    ctrl.tick()
    clock.t = 0.06
    ctrl.tick()
    assert len(pool) == 2
    # Pressure stays pinned high (the backlog grows into the doubled
    # capacity), hold re-earned — but cooldown gates.
    sched.pending = sched.max_queue
    clock.t = 0.2
    ctrl.tick()
    clock.t = 0.9
    ctrl.tick()
    assert len(pool) == 2
    clock.t = 1.1                  # past cooldown, hold re-earned
    ctrl.tick()
    clock.t = 1.2
    ctrl.tick()
    assert len(pool) == 3


def test_fleet_bounds_are_hard():
    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(2, clock, tel)
    sched = StubSched(max_queue=8, pending=8)
    ctrl = _ctrl(pool, clock, tel, scheduler=sched, min_replicas=2,
                 max_replicas=2, cooldown_s=0.0)
    for t in (0.0, 0.1, 0.2):
        clock.t = t
        ctrl.tick()
    assert len(pool) == 2 and ctrl.scale_ups == 0
    sched.pending = 0              # pressure 0: below down threshold
    for t in (0.3, 0.4, 0.5):
        clock.t = t
        ctrl.tick()
    assert len(pool) == 2 and ctrl.scale_downs == 0
    assert ctrl.state == AUTOSCALE_STEADY


# -- hold-off -------------------------------------------------------------

def test_rollout_in_flight_holds_off_then_resumes():
    class RO:
        state = "running"

    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(1, clock, tel)
    sched = StubSched(max_queue=8, pending=8)
    ro = RO()
    seen = []
    ctrl = _ctrl(pool, clock, tel, scheduler=sched, rollout=ro,
                 on_event=seen.append)
    for t in (0.0, 0.1, 0.2):
        clock.t = t
        ctrl.tick()
    assert ctrl.state == AUTOSCALE_HOLDOFF
    assert len(pool) == 1          # pressure high, but held off
    assert ctrl.holdoffs == 1      # counted once per entry, not per tick
    assert tel.counters["autoscale_holdoffs"] == 1
    assert ctrl.status()["holdoff_reason"] == "rollout_running"
    ro.state = "paused"            # still mid-swap
    clock.t = 0.3
    ctrl.tick()
    assert ctrl.state == AUTOSCALE_HOLDOFF
    ro.state = "done"
    clock.t = 0.4
    ctrl.tick()                    # resumes; hold timer starts fresh
    assert ctrl.state == AUTOSCALE_STEADY
    assert len(pool) == 1
    clock.t = 0.5
    ctrl.tick()
    assert len(pool) == 2
    assert [e["action"] for e in seen] == [
        "init", "holdoff", "resume", "scale_up"]


def test_open_breaker_holds_off_until_cooldown():
    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(2, clock, tel)
    sched = StubSched(max_queue=8, pending=8)
    ctrl = _ctrl(pool, clock, tel, scheduler=sched)
    r0 = pool.replicas[0]
    while r0.breaker.state != "open":
        r0.breaker.record_failure()
    for t in (0.0, 0.1):
        clock.t = t
        ctrl.tick()
    assert ctrl.state == AUTOSCALE_HOLDOFF
    assert ctrl.status()["holdoff_reason"] == "breaker_open_r0"
    assert len(pool) == 2
    clock.t = 1.2                  # past the breaker cooldown (1.0)
    ctrl.tick()
    assert ctrl.state == AUTOSCALE_STEADY
    clock.t = 1.3
    ctrl.tick()
    assert len(pool) == 3


# -- scale-down: drain-before-remove over live sessions -------------------

class FakeMgr:
    """Duck-typed session manager (the test_replica idiom): a left
    session finalizes immediately, so no-lost-chunks is exact."""

    def __init__(self, log):
        self.log = log
        self.active = {}
        self.done = {}

    def join(self, sid, raw_len=None):
        self.active[sid] = []

    def leave(self, sid, tail=None):
        self.done[sid] = " ".join(self.active.pop(sid))

    def step(self, chunks):
        assert set(chunks) == set(self.active)
        for sid, c in chunks.items():
            self.active[sid].append(str(c))
            self.log.append((sid, str(c)))
        return {sid: " ".join(v) for sid, v in self.active.items()}

    def flush(self):
        pass

    def final(self, sid):
        return self.done[sid]

    def stats(self):
        return {"active": len(self.active), "draining": 0}


def test_scale_down_drains_then_removes_no_lost_chunks():
    clock = Clock()
    tel = ServingTelemetry()
    log = []
    pool = _pool(2, clock, tel, drain_window_s=0.25,
                 session_factory=lambda: FakeMgr(log))
    router = PooledSessionRouter(pool)
    sids = [f"s{k}" for k in range(40)]
    for sid in sids:
        router.join(sid)
    router.step({sid: "c0" for sid in sids})
    pins = {rid: pool.pins_on(rid) for rid in ("r0", "r1")}
    victim_rid = min(pins, key=lambda r: (pins[r], r))
    moved = [sid for sid in sids if pool.pin_of(sid) == victim_rid]

    sched = StubSched(max_queue=16, pending=0)    # pressure 0
    pm = []
    ctrl = _ctrl(pool, clock, tel, scheduler=sched, min_replicas=1,
                 postmortem_fn=lambda kind, **kw: pm.append((kind, kw)))
    ctrl.tick()
    clock.t = 0.06
    ctrl.tick()
    # Episode started: victim picked by fewest pins, parked-for-
    # autoscale drain began — but NOT removed yet.
    victim = pool.replica(victim_rid)
    assert ctrl.state == AUTOSCALE_DRAINING
    assert ctrl.status()["victim"] == victim_rid
    assert victim.state == STATE_DRAINING
    assert victim.park_reason == "autoscale"
    assert len(pool) == 2

    # The router re-pins the victim's sessions on its next step; every
    # chunk fed to the old home comes back as a finalized segment.
    out = router.step({sid: "c1" for sid in sids})
    assert out == {sid: "c0 c1" for sid in sids}
    assert all(pool.pin_of(sid) != victim_rid for sid in moved)

    # Mid-drain the controller reports draining and won't start
    # another episode whatever the pressure does.
    sched.pending = 16
    clock.t = 0.1
    ctrl.tick()
    assert ctrl.state == AUTOSCALE_DRAINING and len(pool) == 2
    sched.pending = 0

    # Window elapses, sessions quiet -> the replica leaves the ring.
    clock.t = 0.4
    ctrl.tick()
    assert len(pool) == 1
    assert ctrl.state == AUTOSCALE_STEADY
    assert ctrl.scale_downs == 1
    assert victim_rid not in [r.rid for r in pool]
    assert tel.counters[
        'autoscale_events{actuator="horizontal",direction="down"}'] == 1
    # Capacity follows the fleet down (8/replica from the ctor split).
    assert sched.applied[-1] == 8

    # Post-removal traffic and finals: nothing lost anywhere.
    router.step({sid: "c2" for sid in sids})
    for sid in sids:
        router.leave(sid)
    router.flush()
    for sid in sids:
        assert router.final(sid) == "c0 c1 c2"

    # The episode's postmortem names direction and fleet sizes.
    assert len(pm) == 1
    kind, ev = pm[0]
    assert kind == "autoscale"
    assert ev["direction"] == "down"
    assert (ev["from_replicas"], ev["to_replicas"]) == (2, 1)
    assert ev["replica"] == victim_rid
    assert ev["trigger"] == "pressure_below_down"


def test_scale_down_waits_for_session_quiet():
    """A parked victim with un-finalized streaming state must NOT be
    removed — the router still has segments to collect from it."""
    clock = Clock()
    tel = ServingTelemetry()
    log = []
    pool = _pool(2, clock, tel, drain_window_s=0.1,
                 session_factory=lambda: FakeMgr(log))
    router = PooledSessionRouter(pool)
    for k in range(10):
        router.join(f"s{k}")
    router.step({f"s{k}": "c0" for k in range(10)})
    ctrl = _ctrl(pool, clock, tel, scheduler=StubSched(pending=0))
    ctrl.tick()
    clock.t = 0.06
    ctrl.tick()
    victim_rid = ctrl.status()["victim"]
    assert victim_rid is not None
    # Window elapses but the router never stepped: the victim's
    # sessions are still active on it -> parked, NOT removed.
    clock.t = 0.5
    ctrl.tick()
    assert pool.replica(victim_rid).state == STATE_PARKED
    assert len(pool) == 2
    assert ctrl.state == AUTOSCALE_DRAINING
    # One router step re-pins and finalizes; the next tick removes.
    router.step({f"s{k}": "c1" for k in range(10)})
    clock.t = 0.6
    ctrl.tick()
    assert len(pool) == 1
    for k in range(10):
        router.leave(f"s{k}")
    router.flush()
    for k in range(10):
        assert router.final(f"s{k}") == "c0 c1"


def test_never_drains_the_last_routable_replica():
    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(2, clock, tel)
    r1 = pool.replicas[1]
    while r1.breaker.state != "open":
        r1.breaker.record_failure()
    # r1 is broken; its breaker cooldown (1.0) also holds the
    # controller off. Wait it out, then push pressure low: r0 is the
    # only routable replica, so no victim qualifies even though
    # len(pool) > min_replicas.
    clock.t = 5.0
    ctrl = _ctrl(pool, clock, tel, scheduler=StubSched(pending=0),
                 min_replicas=1)
    for t in (5.0, 5.1, 5.2):
        clock.t = t
        ctrl.tick()
    assert ctrl.state == AUTOSCALE_STEADY
    assert ctrl.scale_downs == 0
    assert len(pool) == 2


# -- pressure signals -----------------------------------------------------

def test_queue_pressure_reads_scheduler_fill():
    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(1, clock, tel)
    ctrl = _ctrl(pool, clock, tel,
                 scheduler=StubSched(max_queue=10, pending=3))
    assert ctrl.queue_pressure() == pytest.approx(0.3)
    ctrl2 = _ctrl(pool, clock, tel)
    assert ctrl2.queue_pressure() == 0.0   # inert without a scheduler


def test_occupancy_pressure_counts_routable_rows():
    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(2, clock, tel)
    ctrl = _ctrl(pool, clock, tel, rows_per_replica=4)
    assert ctrl.occupancy_pressure() == 0.0
    pool.replicas[0].inflight = 4
    assert ctrl.occupancy_pressure() == pytest.approx(0.5)
    # An unroutable replica leaves the budget (its rows don't count,
    # the fleet denominator shrinks).
    r1 = pool.replicas[1]
    while r1.breaker.state != "open":
        r1.breaker.record_failure()
    assert ctrl.occupancy_pressure() == pytest.approx(1.0)


def test_dispatch_pressure_scans_the_histogram_family():
    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(1, clock, tel)
    ctrl = _ctrl(pool, clock, tel, dispatch_budget_s=1.0)
    assert ctrl.dispatch_pressure() == 0.0
    # The worst labeled variant drives the signal, capped at 1.
    tel.observe("gateway.dispatch_s", 0.2, labels={"replica": "r0"})
    tel.observe("gateway.dispatch_s", 0.6, labels={"replica": "r1"})
    assert ctrl.dispatch_pressure() == pytest.approx(0.6)
    tel.observe("gateway.dispatch_s", 5.0, labels={"replica": "r1"})
    assert ctrl.dispatch_pressure() == 1.0


def test_slo_burn_pressure_scans_the_gauge_family():
    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(1, clock, tel)
    ctrl = _ctrl(pool, clock, tel, slo_burn_budget=2.0)
    assert ctrl.slo_burn_pressure() == 0.0
    tel.gauge("slo_burn_rate", 0.5, labels={"window": "5m"})
    tel.gauge("slo_burn_rate", 1.0, labels={"window": "1h"})
    assert ctrl.slo_burn_pressure() == pytest.approx(0.5)
    # Unrelated gauges sharing the prefix-as-substring don't leak in.
    tel.gauge("slo_burn_rate_limit", 99.0)
    assert ctrl.slo_burn_pressure() == pytest.approx(0.5)


def test_brownout_pressure_maps_the_ladder():
    class BO:
        level = 0

    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(1, clock, tel)
    bo = BO()
    ctrl = _ctrl(pool, clock, tel, brownout=bo)
    assert ctrl.brownout_pressure() == 0.0
    bo.level = 3                   # LEVEL_REPLICA_DRAIN: top rung
    assert ctrl.brownout_pressure() == 1.0
    sig = ctrl.signals()
    assert sig["max"] == 1.0 and sig["brownout"] == 1.0


# -- gateway-capacity coupling (real scheduler) ---------------------------

def test_set_max_queue_shrink_never_below_pending():
    """The satellite regression: admission capacity shrink is bounded
    by the already-admitted backlog — the autoscaler must never turn
    accepted requests into liars."""
    clock = Clock()
    tel = ServingTelemetry()
    s = MicroBatchScheduler(EDGES, 4, clock=clock, telemetry=tel,
                            max_queue=8, default_deadline=9.0)
    for _ in range(3):
        s.submit(_feat(50))
    assert s.pending == 3
    # Shrink clamps to the backlog, never below it (and never to 0).
    assert s.set_max_queue(1) == 3
    assert s.max_queue == 3
    assert tel.counters["capacity_shrinks"] == 1
    assert tel.gauges["gateway_capacity"] == 3
    # Growth applies immediately.
    assert s.set_max_queue(10) == 10
    assert tel.counters["capacity_grows"] == 1
    # And the queue keeps admitting up to the new cap.
    for _ in range(7):
        s.submit(_feat(50))
    assert s.pending == 10


def test_capacity_coupling_with_real_scheduler():
    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(1, clock, tel)
    sched = MicroBatchScheduler(EDGES, 4, clock=clock, telemetry=tel,
                                max_queue=12, default_deadline=9.0,
                                pool=pool)
    for _ in range(12):
        sched.submit(_feat(50))
    ctrl = _ctrl(pool, clock, tel, scheduler=sched, max_replicas=2)
    assert ctrl.capacity_per_replica == 12   # starting split
    ctrl.tick()
    clock.t = 0.06
    ctrl.tick()
    assert len(pool) == 2
    assert sched.max_queue == 24


# -- observability round-trip ---------------------------------------------

def test_autoscale_obs_passes_schema_lint():
    """What a scaling run actually emits — the telemetry snapshot
    (directional autoscale_events) and the episode postmortem — must
    pass tools/check_obs_schema.py, and stripping the direction label
    or the postmortem fields must fail it."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import check_obs_schema
    finally:
        sys.path.pop(0)

    from deepspeech_tpu.resilience import postmortem

    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(1, clock, tel)
    sink = io.StringIO()
    postmortem.configure(sink=sink)
    try:
        ctrl = _ctrl(pool, clock, tel,
                     scheduler=StubSched(max_queue=8, pending=8),
                     postmortem_fn=postmortem.record)
        ctrl.tick()
        clock.t = 0.06
        ctrl.tick()
        assert len(pool) == 2
    finally:
        postmortem.configure()
    snap = io.StringIO()
    tel.emit_jsonl(snap, wall_s=1.0)
    lines = (snap.getvalue() + sink.getvalue()).splitlines()
    assert any('"kind": "autoscale"' in l for l in lines)
    problems = check_obs_schema.scan([l for l in lines if l.strip()])
    assert problems == [], problems

    # A direction-less autoscale_events series is a lint error.
    bad = {"event": "metrics", "ts": 1.0,
           "counters": {"autoscale_events": 2}}
    assert any("direction" in p
               for p in check_obs_schema.validate_record(bad))
    # So is an autoscale postmortem missing its episode fields.
    pm = json.loads([l for l in lines
                     if '"kind": "autoscale"' in l][0])
    assert check_obs_schema.validate_record(pm) == []
    for missing in ("direction", "from_replicas", "to_replicas"):
        broken = {k: v for k, v in pm.items() if k != missing}
        assert any(missing in p for p in
                   check_obs_schema.validate_record(broken)), missing


def test_autoscale_report_renders_a_run():
    """tools/autoscale_report.py aggregates the controller's own event
    stream: counts, fleet range, and piecewise replica-seconds."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import autoscale_report
    finally:
        sys.path.pop(0)

    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(1, clock, tel)
    sched = StubSched(max_queue=8, pending=8)
    ctrl = _ctrl(pool, clock, tel, scheduler=sched, cooldown_s=0.1)
    ctrl.tick()
    clock.t = 0.06
    ctrl.tick()                    # up: 1 -> 2 at t=0.06
    sched.pending = 0
    clock.t = 1.0
    ctrl.tick()
    clock.t = 1.1
    ctrl.tick()                    # drain begins
    clock.t = 2.0
    ctrl.tick()                    # removed: 2 -> 1 at t=2.0
    assert (ctrl.scale_ups, ctrl.scale_downs) == (1, 1)

    # serve.py wraps each event as {"autoscale": ...} JSONL.
    lines = [json.dumps({"autoscale": e}) for e in ctrl.events]
    agg = autoscale_report.aggregate(
        autoscale_report.load_records(lines))
    assert (agg["ups"], agg["downs"]) == (1, 1)
    assert (agg["size_min"], agg["size_max"]) == (1, 2)
    # Fleet of 1 from init to t=0.06, then 2 until the removal at 2.0.
    assert agg["replica_seconds"] == pytest.approx(
        1 * 0.06 + 2 * (2.0 - 0.06))
    text = autoscale_report.render(agg)
    assert "scale_ups=1 scale_downs=1" in text
    assert "fleet_size=[1..2]" in text


# -- vertical actuators & drain cancel ------------------------------------

class StubVSched(StubSched):
    """StubSched plus the vertical-actuator surface: the rung ladder
    (max_batch / tier_max_batch) and the tier-shift map."""

    def __init__(self, max_queue=8, pending=0, max_batch=4):
        super().__init__(max_queue=max_queue, pending=pending)
        self.max_batch = max_batch
        self.tier_max_batch = {}
        self.tier_shift = {}


def test_vertical_steps_inside_horizontal_cooldown():
    """The rung ladder and tier-mix shift absorb a burst while the
    horizontal cooldown still has the replica axis locked — that's the
    point of a second, cheaper actuator."""
    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(1, clock, tel)
    sched = StubVSched(max_queue=8, pending=8, max_batch=4)
    seen = []
    ctrl = _ctrl(pool, clock, tel, scheduler=sched,
                 vertical_max_batch=8,
                 tier_shift={"premium": "bulk"},
                 vertical_hold_s=0.02, vertical_cooldown_s=0.1,
                 on_event=seen.append)
    ctrl.tick()                     # timers start
    clock.t = 0.03
    ctrl.tick()                     # cheapest rung first: the ladder
    assert sched.max_batch == 8
    assert len(pool) == 1           # no replica added
    assert ctrl.vertical_ups == 1
    assert tel.counters[
        'autoscale_events{actuator="ladder",direction="up"}'] == 1
    clock.t = 0.06
    ctrl.tick()           # vertical in own cooldown -> horizontal up
    assert len(pool) == 2 and ctrl.scale_ups == 1
    sched.pending = 16              # capacity doubled; stay saturated
    clock.t = 0.2
    ctrl.tick()                     # inside the 1.0s horizontal cooldown
    assert sched.tier_shift == {"premium": "bulk"}
    assert len(pool) == 2           # cooldown held the replica axis
    ev = [e for e in seen if e["action"] == "vertical_up"]
    assert [e["actuator"] for e in ev] == ["ladder", "tier_mix"]
    assert ev[1]["in_horizontal_cooldown"] is True
    assert tel.gauges["autoscale_vertical"] == 2
    assert ctrl.status()["vertical_engaged"] == ["ladder", "tier_mix"]
    # Vertical episodes keep the fleet columns honest: same size both
    # sides, no replica, no repins.
    vep = [e for e in ctrl.episodes if e["actuator"] != "horizontal"]
    assert vep and all(e["from_replicas"] == e["to_replicas"]
                       and e["replica"] is None and e["repins"] == 0
                       for e in vep)


def test_vertical_disengages_before_scale_down():
    """On the way down the controller restores quality first: no
    horizontal drain while any vertical rung is engaged, and the
    scheduler's baselines (max_batch, tier caps) come back exactly."""
    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(2, clock, tel, drain_window_s=0.05)
    sched = StubVSched(max_queue=16, pending=16, max_batch=4)
    ctrl = _ctrl(pool, clock, tel, scheduler=sched, max_replicas=2,
                 cooldown_s=0.05,
                 vertical_max_batch=8,
                 vertical_tier_max_batch={"premium": 8},
                 vertical_hold_s=0.02, vertical_cooldown_s=0.5)
    ctrl.tick()
    clock.t = 0.03
    ctrl.tick()                     # ladder engages
    assert sched.max_batch == 8
    assert sched.tier_max_batch == {"premium": 8}
    sched.pending = 0               # pressure collapses
    clock.t = 0.1
    ctrl.tick()                     # below-timers start
    clock.t = 0.16
    ctrl.tick()
    # Below-hold met, no horizontal cooldown — but the rung is still
    # engaged (vertical cooldown 0.5s): the drain must NOT begin.
    assert ctrl.status()["victim"] is None
    assert len(pool) == 2 and ctrl.state == AUTOSCALE_STEADY
    clock.t = 0.55
    ctrl.tick()                     # vertical down: baselines restored
    assert ctrl.vertical_downs == 1
    assert sched.max_batch == 4 and sched.tier_max_batch == {}
    assert ctrl.status()["vertical_engaged"] == []
    clock.t = 0.62
    ctrl.tick()                     # only now may the drain begin
    assert ctrl.status()["victim"] is not None


def test_peer_breaker_trip_cancels_drain():
    """A peer's breaker opening mid-drain flips the episode's premise
    (the fleet is degraded while we're voluntarily removing capacity):
    the drain cancels, the victim re-admits, the cancel charges the
    cooldown."""
    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(2, clock, tel, drain_window_s=0.25)
    seen = []
    ctrl = _ctrl(pool, clock, tel, scheduler=StubSched(pending=0),
                 on_event=seen.append)
    ctrl.tick()
    clock.t = 0.06
    ctrl.tick()
    victim_rid = ctrl.status()["victim"]
    assert victim_rid is not None
    peer = next(r for r in pool.replicas if r.rid != victim_rid)
    while peer.breaker.state != "open":
        peer.breaker.record_failure()
    clock.t = 0.1
    ctrl.tick()
    assert ctrl.drain_cancels == 1
    assert ctrl.status()["victim"] is None
    assert ctrl.state == AUTOSCALE_STEADY
    assert len(pool) == 2
    victim = pool.replica(victim_rid)
    assert victim.state not in (STATE_DRAINING, STATE_PARKED)
    assert victim.can_route(clock.t)
    assert tel.counters[
        'autoscale_events{actuator="horizontal",direction="cancel"}'] \
        == 1
    ev = [e for e in seen if e["action"] == "drain_cancel"]
    assert len(ev) == 1 and ev[0]["replica"] == victim_rid
    assert ev[0]["reason"].startswith("breaker_open")
    # The cancel counted as an action: no immediate re-drain.
    clock.t = 0.12
    ctrl.tick()
    assert ctrl.status()["victim"] is None
    assert ctrl.scale_downs == 0


# -- run_until_steady -----------------------------------------------------

def test_run_until_steady_finishes_a_started_drain():
    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(2, clock, tel, drain_window_s=0.1)
    ctrl = _ctrl(pool, clock, tel, scheduler=StubSched(pending=0))
    ctrl.tick()
    clock.t = 0.06
    ctrl.tick()
    assert ctrl.status()["victim"] is not None

    def pump():
        clock.t += 0.05            # stand-in for wall progress

    assert ctrl.run_until_steady(pump=pump) == AUTOSCALE_STEADY
    assert len(pool) == 1 and ctrl.status()["victim"] is None


# -- the seeded load layer ----------------------------------------------

def test_traffic_model_is_seed_deterministic():
    """The seeded load layer must replay bit-identically:
    same seed -> the same arrivals, lengths, and session plans; a
    different seed -> a different schedule."""
    kw = dict(duration_s=10.0, base_rps=20.0, day_s=10.0,
              diurnal_amplitude=0.8, burst_rate_mult=2.0,
              session_rate=0.5)
    a = TrafficModel(seed=7, **kw).schedule()
    b = TrafficModel(seed=7, **kw).schedule()
    assert a.arrivals == b.arrivals
    assert a.sessions == b.sessions
    assert a.summary() == b.summary()
    assert a.arrivals and a.sessions
    # Arrivals are time-ordered with lengths inside the clip band.
    ts = [arr.t for arr in a.arrivals]
    assert ts == sorted(ts) and ts[-1] <= 10.0
    assert all(16 <= arr.feat_len <= 1600 for arr in a.arrivals)
    c = TrafficModel(seed=8, **kw).schedule()
    assert c.arrivals != a.arrivals


# -- scenario: one modeled day, closed loop -------------------------------

def test_scenario_modeled_day_scales_up_and_down_losing_nothing(obs_lint, postmortems):
    """One compressed day of seeded ``TrafficModel`` traffic through a
    real scheduler and pool on the virtual clock, the controller
    ticking every 50 ms between arrivals and dispatch, with pinned
    sessions fed a chunk a tick: the fleet grows under the burst and
    drains back in the trough, one replica an episode; no request and
    no session chunk is lost across the resizes; no session is re-pinned
    more often than the fleet resized; and the telemetry with its
    ``autoscale`` postmortems lints clean."""
    import math


    day = 6.0
    arrivals = TrafficModel(
        seed=0, duration_s=day, base_rps=26.0, day_s=day,
        diurnal_amplitude=0.9, burst_rate_mult=2.5, burst_enter_p=0.25,
        burst_exit_p=0.2, burst_step_s=0.25,
        len_log_mean=math.log(64.0), len_log_sigma=0.5, len_min=16,
        len_max=max(EDGES), max_arrivals=260).schedule().arrivals
    clock = Clock()
    tel = ServingTelemetry()
    log = []
    pm = postmortems

    def factory(rid):
        return _replica(rid, clock, tel,
                        session_factory=lambda: FakeMgr(log))

    pool = ReplicaPool([factory("r0")], clock=clock, telemetry=tel,
                       drain_window_s=0.15)
    sched = MicroBatchScheduler(EDGES, 4, clock=clock, telemetry=tel,
                                max_queue=8, default_deadline=2.5,
                                pool=pool)
    ctrl = _ctrl(pool, clock, tel, factory=factory, scheduler=sched,
                 up_pressure=0.5, down_pressure=0.12, hold_s=0.1,
                 cooldown_s=0.6, drain_window_s=0.15,
                 postmortem_fn=pm.write)
    router = PooledSessionRouter(pool)
    sids = [f"s{k}" for k in range(6)]
    homes = {sid: router.join(sid) for sid in sids}
    moves = dict.fromkeys(sids, 0)

    i = ticks = 0
    peak = 1
    while i < len(arrivals) or sched.pending or len(pool) > 1 \
            or ctrl.status()["victim"] is not None:
        clock.t += 0.05
        while i < len(arrivals) and arrivals[i].t <= clock.t:
            try:
                sched.submit(_feat(arrivals[i].feat_len), rid=f"q{i}")
            except OverloadRejected:
                pass
            i += 1
        ctrl.tick()
        peak = max(peak, len(pool))
        sched.pump()
        router.step({sid: f"c{ticks}" for sid in sids})
        ticks += 1
        for sid in sids:
            if router.home_of(sid) != homes[sid]:
                moves[sid] += 1
                homes[sid] = router.home_of(sid)
        assert ticks < 400, "the day never settled"
    for sid in sids:
        router.leave(sid)
    router.flush()

    assert ctrl.scale_ups >= 1 and ctrl.scale_downs >= 1
    assert peak > len(pool) == ctrl.min_replicas
    for ep in ctrl.episodes:
        assert ep["direction"] in ("up", "down")
        assert abs(ep["from_replicas"] - ep["to_replicas"]) == 1
    c = tel.snapshot()["counters"]
    assert int(c["admitted"]) + int(c.get("rejected", 0)) \
        == len(arrivals)
    assert int(c["admitted"]) == int(c["requests_ok"])
    want = " ".join(f"c{k}" for k in range(ticks))
    assert [router.final(sid) for sid in sids] == [want] * len(sids)
    assert max(moves.values()) <= ctrl.scale_ups + ctrl.scale_downs
    assert any(k.startswith("autoscale_events{") for k in c)
    assert len(pm.recent("autoscale")) == len(ctrl.episodes)
    assert obs_lint(tel, pm) == []
