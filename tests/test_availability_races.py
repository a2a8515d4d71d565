"""Availability under chaos x load: the mid-episode fault races.

ISSUE-14's acceptance names two races that only exist when a fault
plan composes with a *moving* fleet — neither is reachable from the
steady-state chaos tests in test_resilience.py:

- **Fault on the fresh replica, same episode.** A scale-up arms an
  episode-relative spec (``on_event="autoscale.scale_up"``,
  ``target="@event"``) so the injected dispatch errors chase exactly
  the replica the controller just added. Its breaker must trip, the
  fleet must keep serving every admitted request off the survivors,
  and the controller must read the degraded fleet as hold-off — not
  as a reason to add more capacity on top of a faulting episode.

- **Fault during a scale-down drain.** A drain arms an
  ``on_event="autoscale.drain_begin"`` spec; the injected
  unavailability lands on the only routable peer and opens its
  breaker mid-drain. The controller must cancel the episode and
  un-park the victim (voluntarily removing capacity from a degraded
  fleet is the wrong call), and every in-flight request and streamed
  session chunk must survive the reversal.

Plus the trigger plumbing those races ride on: notify/arm, the
``arm_for_s`` expiry window, the ``@event`` replica chase, the
``min_load`` gate, and the wall-clock/episode mutual exclusion.

All virtual-clock: the FaultPlan, scheduler, replicas, breakers and
controller share one injectable clock — no sleeping, deterministic.
"""

import numpy as np
import pytest

from scenario import ChunkLogManager, burst_edges, counter_family

from deepspeech_tpu.resilience import (CircuitBreaker, FaultPlan,
                                       FaultSpec, InjectedFault, Retry,
                                       faults)
from deepspeech_tpu.serving import (AutoscaleController,
                                    MicroBatchScheduler,
                                    PooledSessionRouter, Replica,
                                    ReplicaPool, ServingTelemetry)
from deepspeech_tpu.serving.autoscale import AUTOSCALE_HOLDOFF
from deepspeech_tpu.serving.replica import STATE_DRAINING, STATE_PARKED

EDGES = (64, 128)
NF = 13


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _echo(tag):
    def fn(batch, plan):
        return [f"{tag}:B{plan.batch_pad}"] * plan.n_valid
    return fn


def _feat(n):
    return np.zeros((n, NF), np.float32)


def _replica(rid, clock, tel, **kw):
    return Replica(rid, _echo(rid), telemetry=tel, clock=clock,
                   breaker=CircuitBreaker(name=f"b{rid}",
                                          failure_threshold=2,
                                          cooldown_s=0.5, clock=clock,
                                          registry=tel), **kw)


def _sched(pool, clock, tel, max_queue=8):
    return MicroBatchScheduler(
        EDGES, 2, max_queue=max_queue, default_deadline=0.05,
        default_timeout=60.0, max_attempts=6, clock=clock,
        telemetry=tel, pool=pool,
        retry_backoff=Retry(base_s=0.01, max_s=0.01, jitter=0.0,
                            name="gateway_dispatch"))


# -- trigger plumbing ------------------------------------------------------

def test_on_event_arms_and_arm_window_expires():
    clock = Clock()
    plan = FaultPlan([FaultSpec("p", "error", on_event="autoscale.x",
                                arm_for_s=1.0)],
                     clock=clock, registry=ServingTelemetry())
    plan.start()
    assert plan.check("p") is None          # never armed: inert
    assert plan.notify("autoscale.x") == 1
    assert plan.check("p") is not None      # armed window open
    clock.t = 2.0
    assert plan.check("p") is None          # window expired
    plan.notify("autoscale.x")              # re-notify re-arms
    assert plan.check("p") is not None


def test_target_event_chases_the_arming_replica():
    clock = Clock()
    plan = FaultPlan([FaultSpec("p", "error", on_event="autoscale.up",
                                target="@event")],
                     clock=clock, registry=ServingTelemetry())
    plan.start()
    plan.notify("autoscale.up", replica="a7")
    assert plan.check("p", replica="r0") is None   # wrong replica
    spec = plan.check("p", replica="a7")
    assert spec is not None and spec.armed_target == "a7"


def test_min_load_gates_firing():
    clock = Clock()
    plan = FaultPlan([FaultSpec("p", "error", on_event="e",
                                min_load=0.5)],
                     clock=clock, registry=ServingTelemetry())
    plan.start()
    plan.notify("e")
    plan.note_load(0.2)
    assert plan.check("p") is None          # trough: below the gate
    plan.note_load(0.8)
    assert plan.check("p") is not None


def test_wall_clock_and_episode_triggers_are_exclusive():
    with pytest.raises(ValueError):
        FaultSpec("p", "error", on_event="e", after_s=1.0)
    with pytest.raises(ValueError):
        FaultSpec("p", "error", target="@event")


def test_module_hooks_route_to_the_active_plan():
    clock = Clock()
    plan = FaultPlan([FaultSpec("p", "error", on_event="e",
                                min_load=0.5)],
                     clock=clock, registry=ServingTelemetry())
    faults.install(plan)
    try:
        assert faults.notify("e") == 1
        faults.note_load(1.0)
        with pytest.raises(InjectedFault):
            faults.inject("p")
    finally:
        faults.clear()
    assert faults.notify("e") == 0          # no plan: cheap no-op


# -- race 1: breaker trip on the same-episode-added replica ----------------

def test_breaker_trip_on_fresh_replica_same_episode():
    clock = Clock()
    tel = ServingTelemetry()
    pool = ReplicaPool([_replica("r0", clock, tel)], clock=clock,
                       telemetry=tel, drain_window_s=0.25)
    sched = _sched(pool, clock, tel)
    ctrl = AutoscaleController(
        pool, lambda rid: _replica(rid, clock, tel), scheduler=sched,
        min_replicas=1, max_replicas=2, up_pressure=0.7,
        down_pressure=0.1, hold_s=0.05, cooldown_s=10.0,
        telemetry=tel, clock=clock,
        postmortem_fn=lambda *a, **k: None)
    spec = FaultSpec("gateway.dispatch", "error", prob=1.0, count=2,
                     on_event="autoscale.scale_up", target="@event",
                     arm_for_s=5.0, message="fresh replica fault")
    faults.install(FaultPlan([spec], clock=clock, registry=tel))
    try:
        rids = [sched.submit(_feat(32), deadline=1.0, timeout=60.0)
                for _ in range(8)]
        ctrl.tick()
        clock.t = 0.06
        ctrl.tick()                   # queue saturated -> scale up
        assert ctrl.scale_ups == 1
        fresh = spec.armed_target
        assert fresh is not None and fresh != "r0"
        assert fresh in [r.rid for r in pool]

        for _ in range(50):
            clock.t += 0.05
            sched.pump()
            if all(r in sched.results for r in rids):
                break
        # The fault chased exactly the episode's replica and tripped
        # its breaker...
        assert spec.fired == 2
        assert pool.replica(fresh).breaker.state == "open"
        # ...while the survivors served every admitted request.
        assert all(sched.results[r].status == "ok" for r in rids)

        # A degraded same-episode fleet reads as hold-off, not as a
        # reason to stack more capacity on a faulting episode.
        ctrl.tick()
        assert ctrl.state == AUTOSCALE_HOLDOFF
        assert ctrl.status()["holdoff_reason"].startswith(
            "breaker_open")
        assert ctrl.scale_ups == 1
    finally:
        faults.clear()


# -- race 2: fault during a scale-down drain -------------------------------

def test_fault_during_drain_cancels_and_unparks():
    clock = Clock()
    tel = ServingTelemetry()
    chunk_log = []
    pool = ReplicaPool(
        [_replica(f"r{k}", clock, tel,
                  session_factory=lambda: ChunkLogManager(chunk_log))
         for k in range(2)],
        clock=clock, telemetry=tel, drain_window_s=0.25)
    router = PooledSessionRouter(pool)
    sids = [f"s{k}" for k in range(10)]
    for sid in sids:
        router.join(sid)
    router.step({sid: "c0" for sid in sids})

    sched = _sched(pool, clock, tel)
    ctrl = AutoscaleController(
        pool, lambda rid: _replica(rid, clock, tel), scheduler=sched,
        min_replicas=1, max_replicas=2, up_pressure=0.9,
        down_pressure=0.25, hold_s=0.05, cooldown_s=0.5,
        telemetry=tel, clock=clock,
        postmortem_fn=lambda *a, **k: None)
    spec = FaultSpec("gateway.dispatch", "unavailable", prob=1.0,
                     count=2, on_event="autoscale.drain_begin",
                     arm_for_s=5.0, message="fault during drain")
    faults.install(FaultPlan([spec], clock=clock, registry=tel))
    try:
        # Trough: the drain begins and arms the spec.
        ctrl.tick()
        clock.t = 0.06
        ctrl.tick()
        victim_rid = ctrl.status()["victim"]
        assert victim_rid is not None
        peer_rid = next(r.rid for r in pool.replicas
                        if r.rid != victim_rid)

        # Traffic arrives mid-drain; with the victim out of routing it
        # all lands on the peer, whose injected unavailability opens
        # its breaker (failure_threshold=2).
        rids = [sched.submit(_feat(32), deadline=1.0, timeout=60.0)
                for _ in range(4)]
        clock.t = 0.08
        sched.pump()
        assert spec.fired == 2
        assert pool.replica(peer_rid).breaker.state == "open"

        # The controller's next turn cancels the episode: removing
        # capacity from a degraded fleet is the wrong call.
        ctrl.tick()
        assert ctrl.drain_cancels == 1
        assert ctrl.status()["victim"] is None
        victim = pool.replica(victim_rid)
        assert victim.state not in (STATE_DRAINING, STATE_PARKED)
        assert len(pool) == 2

        # The faulted requests re-dispatch onto the re-admitted victim
        # — nothing admitted is lost to the cancelled episode.
        for _ in range(50):
            clock.t += 0.05
            sched.pump()
            if all(r in sched.results for r in rids):
                break
        assert all(sched.results[r].status == "ok" for r in rids)

        # Streamed sessions survive the whole reversal: every chunk
        # fed before, during and after the cancelled drain finalizes.
        router.step({sid: "c1" for sid in sids})
        for sid in sids:
            router.leave(sid)
        router.flush()
        for sid in sids:
            assert router.final(sid) == "c0 c1"
        assert sorted(c for _, c in chunk_log) == \
            sorted(["c0"] * 10 + ["c1"] * 10)
    finally:
        faults.clear()


# -- scenario: the three races on one modeled day --------------------------

def test_scenario_modeled_day_with_episode_faults_loses_nothing(obs_lint, postmortems):
    """One compressed day of seeded, tier-mixed traffic through an
    autoscaled, handoff-enabled fleet on the virtual clock, under a
    plan of three episode-relative faults: dispatch errors chasing the
    replica a scale-up just added, unavailability armed by a drain, and
    a swap fault armed by a traffic burst under a rolling swap. Every
    spec fires; the fleet scaled up and took a vertical step inside the
    horizontal cooldown; the drain episode resolved (removed or
    cancelled at most once) with no victim left parked; pinned
    sessions moved by live handoff, never by fallback; the rollout
    rolled back; and no admitted request and no session chunk was
    lost. Telemetry and postmortems lint clean."""
    import math

    from deepspeech_tpu.serving import (MigrationController,
                                        OverloadRejected,
                                        RolloutController, TrafficModel)

    day = 7.0
    schedule = TrafficModel(
        seed=7, duration_s=day, base_rps=26.0, day_s=day,
        diurnal_amplitude=0.9, burst_rate_mult=2.5, burst_enter_p=0.3,
        burst_exit_p=0.2, burst_step_s=0.25,
        len_log_mean=math.log(64.0), len_log_sigma=0.5, len_min=16,
        len_max=max(EDGES), tier_mix={"premium": 0.35, "bulk": 0.65},
        max_arrivals=280).schedule()
    arrivals = schedule.arrivals
    bursts = burst_edges(schedule)
    t_roll = next(t for t, ev in bursts
                  if ev == "traffic.burst" and t >= 0.45 * day)

    clock = Clock()
    tel = ServingTelemetry()
    chunk_log = []
    pm = postmortems
    spec_fresh = FaultSpec("gateway.dispatch", "error", prob=1.0,
                           count=2, on_event="autoscale.scale_up",
                           target="@event", arm_for_s=1.5, min_load=0.05)
    spec_drain = FaultSpec("gateway.dispatch", "unavailable", prob=1.0,
                           count=4, on_event="autoscale.drain_begin",
                           arm_for_s=1.5)
    spec_swap = FaultSpec("rollout.swap", "error", prob=1.0, count=1,
                          on_event="traffic.burst", arm_for_s=2.5)
    plan = FaultPlan([spec_fresh, spec_drain, spec_swap], seed=7,
                     clock=clock, registry=tel)

    def decode(batch, plan_):
        clock.t += 0.005               # service time, on the same clock
        return ["ok"] * plan_.n_valid

    def factory(rid):
        rep = _replica(rid, clock, tel,
                       session_factory=lambda: ChunkLogManager(chunk_log))
        rep.decode_fn, rep.version = decode, "v1"
        return rep

    pool = ReplicaPool([factory("r0")], clock=clock, telemetry=tel,
                       drain_window_s=0.2, handoff=True)
    sched = MicroBatchScheduler(EDGES, 4, clock=clock, telemetry=tel,
                                max_queue=16, default_deadline=2.5,
                                max_attempts=12, pool=pool)
    probes = {"due": 0, "sent": 0}

    def on_event(ev):
        if ev.get("action") == "drain_begin":
            probes["due"] += 8          # traffic to meet the drain fault

    ctrl = AutoscaleController(
        pool, factory, scheduler=sched, min_replicas=1, max_replicas=3,
        up_pressure=0.3, down_pressure=0.12, hold_s=0.08, cooldown_s=1.2,
        rows_per_replica=8, drain_window_s=0.2, vertical_max_batch=8,
        tier_shift={"premium": "bulk"}, vertical_hold_s=0.03,
        vertical_cooldown_s=0.25, handoff=True, telemetry=tel,
        clock=clock, on_event=on_event, postmortem_fn=pm.write)
    ro = RolloutController(
        pool, lambda rep: {"decode_fn": decode,
                           "session_factory":
                           lambda: ChunkLogManager(chunk_log)},
        to_version="v2", min_routable=1, drain_window_s=0.15,
        handoff=True, telemetry=tel, postmortem_fn=pm.write)
    mig = MigrationController(telemetry=tel, postmortem_fn=pm.write)
    router = PooledSessionRouter(pool, migrator=mig)
    sids = [f"s{k}" for k in range(4)]
    for sid in sids:
        router.join(sid)

    faults.install(plan)
    i = b = ticks = 0
    try:
        while True:
            clock.t += 0.05
            while b < len(bursts) and bursts[b][0] <= clock.t:
                faults.notify(bursts[b][1])
                b += 1
            while i < len(arrivals) and arrivals[i].t <= clock.t:
                try:
                    sched.submit(_feat(arrivals[i].feat_len),
                                 rid=f"q{i}", tier=arrivals[i].tier)
                except OverloadRejected:
                    pass
                i += 1
            while probes["due"]:
                probes["due"] -= 1
                probes["sent"] += 1
                try:
                    sched.submit(_feat(16), rid=f"p{probes['sent']}",
                                 tier="bulk")
                except OverloadRejected:
                    pass
            ctrl.tick()
            faults.note_load(float(
                tel.gauges.get("autoscale_pressure", 0.0)))
            if ro.state == "idle" and clock.t >= t_roll:
                if len(pool) < 2:
                    pool.add_replica(factory("rroll"))
                ro.start()
            if ro.state in ("running", "paused"):
                ro.tick()
            sched.pump()
            router.step({sid: f"c{ticks}" for sid in sids})
            ticks += 1
            if (i >= len(arrivals) and not sched.pending
                    and ctrl.status()["victim"] is None
                    and ro.state not in ("idle", "running", "paused")
                    and (ctrl.drain_cancels or ctrl.scale_downs
                         or len(pool) <= ctrl.min_replicas)):
                break
            assert ticks < 600, "the day never settled"
        clock.t += 5.0
        sched.drain()
    finally:
        faults.clear()
    for sid in sids:
        router.leave(sid)
    router.flush()

    assert min(spec_fresh.fired, spec_drain.fired, spec_swap.fired) >= 1
    assert ctrl.scale_ups >= 1
    assert ctrl.scale_downs + ctrl.drain_cancels >= 1
    assert ctrl.drain_cancels <= 1
    assert ctrl.status()["victim"] is None
    assert not [r.rid for r in pool if r.park_reason == "autoscale"]
    assert any(ev.get("action") == "vertical_up"
               and ev.get("in_horizontal_cooldown") for ev in ctrl.events)
    assert mig.migrations >= 1 and mig.fallbacks == 0
    assert ro.rollbacks >= 1
    admitted = counter_family(tel, "admitted")
    assert admitted == counter_family(tel, "requests_ok") > 0
    assert admitted + counter_family(tel, "rejected") \
        == len(arrivals) + probes["sent"]
    want = " ".join(f"c{k}" for k in range(ticks))
    assert [router.final(sid) for sid in sids] == [want] * len(sids)
    assert obs_lint(tel, pm) == []
