"""Multi-replica serving plane: Replica/ReplicaPool routing contracts.

Covers the ISSUE-6 satellite list: consistent-hash stability under
pool resize, session re-pin on breaker open (drain window honored, no
lost chunks), least-loaded spill tie-break, the replica-drain brownout
transition (rung 3), the per-replica ``obs`` label round-trip through
``tools/check_obs_schema.py``, and the pooled scheduler dispatch path
(spread, defer-when-unroutable, quarantine with replica attribution).

All pool tests ride an injectable virtual clock and either bare
Replicas with echo backends or FakeMgr session managers — no model,
no device, deterministic.
"""

import json
import io
import os
import sys

import numpy as np
import pytest

from deepspeech_tpu.resilience import CircuitBreaker
from deepspeech_tpu.resilience.brownout import (BrownoutController,
                                                LEVEL_REPLICA_DRAIN)
from deepspeech_tpu.serving import (MicroBatchScheduler,
                                    PooledSessionRouter, Replica,
                                    ReplicaPool, ServingTelemetry,
                                    synthetic_replicas)
from deepspeech_tpu.serving.replica import (STATE_ACTIVE, STATE_DRAINING,
                                            STATE_PARKED)

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


def _pool(n, clock, tel, drain_window_s=0.25, **rep_kw):
    reps = [Replica(f"r{k}", _echo(f"r{k}"), telemetry=tel, clock=clock,
                    breaker=_breaker(clock, tel, f"b{k}"), **rep_kw)
            for k in range(n)]
    return ReplicaPool(reps, clock=clock, telemetry=tel,
                       drain_window_s=drain_window_s)


def _feat(n):
    return np.zeros((n, NF), np.float32)


def _trip(breaker):
    while breaker.state != "open":
        breaker.record_failure()


# -- consistent-hash ring -------------------------------------------------

def test_ring_owner_stability_under_resize():
    """Adding a replica moves ~1/N of the keyspace, and every moved
    key moves TO the new replica — the consistent-hash contract that
    makes pool resizes cheap for pinned sessions."""
    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(3, clock, tel)
    keys = [f"session-{i}" for i in range(300)]
    before = {k: pool.ring_owner(k) for k in keys}
    pool.add_replica(Replica("r3", _echo("r3"), telemetry=tel,
                             clock=clock,
                             breaker=_breaker(clock, tel, "b3")))
    after = {k: pool.ring_owner(k) for k in keys}
    moved = [k for k in keys if before[k] != after[k]]
    # ~1/4 expected; anything near a full reshuffle is a regression.
    assert 0 < len(moved) < len(keys) // 2
    assert all(after[k] == "r3" for k in moved)
    # Removing it again restores every original owner exactly.
    pool.remove_replica("r3")
    assert {k: pool.ring_owner(k) for k in keys} == before


def test_ring_owner_is_process_stable():
    """The ring hashes with blake2b, not the salted builtin ``hash`` —
    the same key must land on the same replica in every process."""
    from deepspeech_tpu.serving.pool import _hash64

    assert _hash64("session-a") == _hash64("session-a")
    # Pinned value: changing the hash function unpins every live
    # session across a restart, so treat it as part of the contract.
    assert _hash64("") == int.from_bytes(
        __import__("hashlib").blake2b(b"", digest_size=8).digest(),
        "big")


# -- least-loaded spill ---------------------------------------------------

def test_spill_prefers_fewest_inflight_then_p95_then_index():
    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(3, clock, tel)
    r0, r1, r2 = pool.replicas
    # In-flight slots dominate.
    r0.inflight = 2
    assert pool.route() is r1  # r1/r2 tie on (0, 0.0, idx) -> index
    # Dispatch p95 breaks the in-flight tie: a slow replica loses.
    tel.observe("gateway.dispatch_s", 0.5, labels=r1.labels)
    assert pool.route() is r2
    # Planned rows (routed but not yet dispatched) count as load.
    assert pool.route(planned={"r2": 4}) is r1


def test_spill_skips_unroutable_replicas():
    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(2, clock, tel)
    r0, r1 = pool.replicas
    _trip(r0.breaker)
    assert pool.route() is r1
    _trip(r1.breaker)
    assert pool.route() is None
    # Past the cooldown an open breaker admits a half-open probe.
    clock.t = 1.5
    assert pool.route() is not None


# -- session re-pin on breaker open --------------------------------------

class FakeMgr:
    """Duck-typed StreamingSessionManager: records which chunks each
    local session saw; a left session finalizes immediately (zero
    acoustic lag), which is exactly the accounting the no-lost-chunks
    invariant needs."""

    def __init__(self, log):
        self.log = log          # shared: every chunk fed, pool-wide
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


def test_session_repin_on_breaker_open_no_lost_chunks():
    clock = Clock()
    tel = ServingTelemetry()
    log = []
    pool = _pool(2, clock, tel,
                 session_factory=lambda: FakeMgr(log))
    router = PooledSessionRouter(pool)
    home = router.join("a")
    assert router.step({"a": "c0"}) == {"a": "c0"}
    old = pool.replica(home)
    _trip(old.breaker)
    # Next step: maintain() starts the drain, the session re-pins to
    # the surviving replica, and the old home's chunks come back as an
    # already-finalized segment prefixing the partial.
    out = router.step({"a": "c1"})
    assert out == {"a": "c0 c1"}
    assert router.home_of("a") != home
    assert pool.repins == 1
    assert int(tel.counters.get("session_repins", 0)) == 1
    # Drain window honored: the tripped replica drains for the window,
    # then returns to ACTIVE state — but stays unroutable while its
    # breaker cooldown runs.
    assert old.state == STATE_DRAINING
    clock.t = 0.5
    pool.maintain()
    assert old.state == STATE_ACTIVE and not old.can_route()
    router.leave("a")
    router.flush()
    # No lost chunks: every fed chunk landed in exactly one manager,
    # and the final is the segments joined in feed order.
    assert router.final("a") == "c0 c1"
    assert log == [("a@0", "c0"), ("a@1", "c1")]


def test_session_keeps_warm_home_while_routable():
    clock = Clock()
    tel = ServingTelemetry()
    log = []
    pool = _pool(2, clock, tel, session_factory=lambda: FakeMgr(log))
    router = PooledSessionRouter(pool)
    home = router.join("a")
    for k in range(3):
        router.step({"a": f"c{k}"})
    assert router.home_of("a") == home and pool.repins == 0


# -- brownout rung 3 ------------------------------------------------------

def test_brownout_level3_parks_most_loaded_and_readmits():
    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(3, clock, tel, drain_window_s=0.0)
    r0, r1, r2 = pool.replicas
    r1.inflight = 5  # most-loaded -> the park victim
    pool.apply_brownout(LEVEL_REPLICA_DRAIN)
    assert r1.state == STATE_DRAINING and r1.parking
    r1.inflight = 0  # in-flight work finishes inside the window
    pool.maintain()
    assert r1.state == STATE_PARKED
    assert int(tel.counters.get("brownout_replica_parks", 0)) == 1
    # At most one parked at a time: a second rung-3 tick is a no-op.
    pool.apply_brownout(LEVEL_REPLICA_DRAIN)
    assert [r.state for r in pool] == [STATE_ACTIVE, STATE_PARKED,
                                       STATE_ACTIVE]
    # Recovery (any level below 3) re-admits.
    pool.apply_brownout(0)
    assert [r.state for r in pool] == [STATE_ACTIVE] * 3


def test_brownout_never_parks_the_last_routable_replica():
    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(2, clock, tel, drain_window_s=0.0)
    r0, r1 = pool.replicas
    _trip(r0.breaker)
    pool.apply_brownout(LEVEL_REPLICA_DRAIN)
    assert r1.state == STATE_ACTIVE and not r1.parking


def test_brownout_controller_escalates_to_level3():
    clock = Clock()
    ctl = BrownoutController(park_pressure=0.95, hold_s=0.0,
                             clock=clock, registry=ServingTelemetry())
    for t, p in ((0.0, 0.8), (0.1, 0.95), (0.2, 0.96)):
        clock.t = t
        ctl.update(p)
    assert ctl.level == LEVEL_REPLICA_DRAIN
    assert ctl.should_park_replica()
    # Without park_pressure the ladder stops at 2, exactly as before.
    ctl2 = BrownoutController(hold_s=0.0, clock=clock,
                              registry=ServingTelemetry())
    for t, p in ((1.0, 0.8), (1.1, 0.95), (1.2, 1.0), (1.3, 1.0)):
        clock.t = t
        ctl2.update(p)
    assert ctl2.level == 2 and not ctl2.should_park_replica()


def test_brownout_hbm_pressure_gauge_fed_and_inert_without_gauge():
    clock = Clock()
    tel = ServingTelemetry()
    ctl = BrownoutController(hold_s=0.0, clock=clock, registry=tel,
                             hbm_budget_bytes=1000.0)
    assert ctl.hbm_pressure() == 0.0       # gauge absent: inert
    assert ctl.update(0.0) == 0
    tel.gauge("hbm_used_bytes", 950)
    assert ctl.hbm_pressure() == pytest.approx(0.95)
    clock.t = 1.0
    assert ctl.update(0.0) == 1            # max-combined with queue
    tel.gauge("hbm_used_bytes", 5000)
    assert ctl.hbm_pressure() == 1.0       # capped
    # No budget configured -> the hook is fully inert.
    assert BrownoutController(registry=tel).hbm_pressure() == 0.0


# -- pooled scheduler dispatch -------------------------------------------

def _sched(clock, pool, **kw):
    kw.setdefault("max_queue", 64)
    kw.setdefault("default_deadline", 1.0)
    kw.setdefault("telemetry", pool.telemetry)
    return MicroBatchScheduler(EDGES, 4, clock=clock, pool=pool, **kw)


def test_pooled_dispatch_spreads_one_poll_across_replicas():
    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(2, clock, tel)
    s = _sched(clock, pool)
    for _ in range(8):                     # two full 4-row batches
        s.submit(_feat(50))
    res = s.pump()
    assert len(res) == 8
    assert {r.status for r in res} == {"ok"}
    # The planned-rows spread: one batch per replica, not both piling
    # on the construction-order winner.
    assert sorted(r.dispatches for r in pool) == [1, 1]
    texts = {r.text.split(":")[0] for r in res}
    assert texts == {"r0", "r1"}


def test_pooled_dispatch_defers_when_nothing_routable():
    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(2, clock, tel)
    for r in pool:
        _trip(r.breaker)
    s = _sched(clock, pool)
    for _ in range(4):
        s.submit(_feat(50))
    assert s.pump() == []                  # deferred, not failed
    assert s.pending == 4
    assert int(tel.counters.get("breaker_deferred", 0)) == 1
    # Requests burned no attempts while the pool was down.
    clock.t = 2.0                          # past breaker cooldown
    res = s.pump()
    assert len(res) == 4 and all(r.attempts == 1 for r in res)


def test_pooled_quarantine_carries_replica_label():
    clock = Clock()
    tel = ServingTelemetry()

    def boom(batch, plan):
        raise RuntimeError("sick backend")

    rep = Replica("r0", boom, telemetry=tel, clock=clock,
                  breaker=_breaker(clock, tel, "b0", threshold=99))
    pool = ReplicaPool([rep], clock=clock, telemetry=tel)
    s = _sched(clock, pool, max_attempts=2)
    s.submit(_feat(50))
    s.submit(_feat(50))
    clock.t = 1.0                          # deadline flush, 2-row batch
    s.pump()
    assert int(tel.counters.get('quarantined{replica="r0"}', 0)) == 2
    assert "quarantined" not in tel.counters  # labeled-only, no mixing


def test_scheduler_rejects_pool_plus_breaker():
    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(1, clock, tel)
    with pytest.raises(ValueError):
        MicroBatchScheduler(EDGES, 4, clock=clock, pool=pool,
                            breaker=_breaker(clock, tel, "x"))


# -- per-replica obs label round-trip ------------------------------------

def test_replica_labels_roundtrip_through_check_obs_schema(tmp_path):
    """A pooled run's telemetry snapshot passes the schema lint, and
    a hand-broken record mixing labeled/unlabeled series fails it."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import check_obs_schema

    clock = Clock()
    tel = ServingTelemetry()
    pool = ReplicaPool(synthetic_replicas(2, telemetry=tel,
                                          clock=clock),
                       clock=clock, telemetry=tel)
    s = _sched(clock, pool)
    for _ in range(8):
        s.submit(_feat(50))
    s.pump()
    buf = io.StringIO()
    tel.emit_jsonl(buf)
    lines = buf.getvalue().splitlines()
    assert check_obs_schema.scan(lines) == []
    rec = json.loads(lines[0])
    assert 'gateway.dispatch_s{replica="r0"}' in rec["histograms"]
    # Now poison the record: an unlabeled twin in the same family.
    rec["histograms"]["gateway.dispatch_s"] = \
        rec["histograms"]['gateway.dispatch_s{replica="r0"}']
    problems = check_obs_schema.scan([json.dumps(rec)])
    assert any("mixes replica-labeled" in p for _, p in problems)


def test_trace_report_groups_per_replica(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import trace_report

    recs = [
        {"event": "span", "name": "gateway.dispatch", "ts": 0.0,
         "dur_ms": 4.0, "id": 1, "replica": "r0"},
        {"event": "span", "name": "gateway.dispatch", "ts": 0.01,
         "dur_ms": 8.0, "id": 2, "replica": "r1"},
        {"event": "compile", "name": "compile", "ts": 0.02,
         "dur_ms": 1.0, "rung": "4x64", "replica": "r1"},
    ]
    agg = trace_report.aggregate(recs)
    assert agg["replicas"]["r0"]["spans"] == 1
    assert agg["replicas"]["r1"]["compiles"] == 1
    assert agg["replicas"]["r1"]["p95_ms"] == pytest.approx(8.0)
    assert "per-replica breakdown" in trace_report.render(agg)


# -- quality tiers --------------------------------------------------------

def test_pool_routes_strictly_by_tier():
    """Tiered replicas serve exactly their own tier: a bulk batch can
    only land on the int8 replica, premium only on the bf16 one, and a
    tierless replica/request carries no constraint."""
    clock = Clock()
    tel = ServingTelemetry()
    prem = Replica("p0", _echo("p0"), telemetry=tel, clock=clock,
                   tier="premium")
    bulk = Replica("b0", _echo("b0"), telemetry=tel, clock=clock,
                   tier="bulk")
    pool = ReplicaPool([prem, bulk], clock=clock, telemetry=tel)
    assert pool.route(tier="premium").rid == "p0"
    assert pool.route(tier="bulk").rid == "b0"
    assert pool.route(tier=None) is not None   # tierless: anyone
    # serves(): strict match for tiered replicas, open for tierless.
    assert prem.serves("premium") and not prem.serves("bulk")
    assert prem.serves(None)
    anyrep = Replica("x0", _echo("x0"), telemetry=tel, clock=clock)
    assert anyrep.serves("premium") and anyrep.serves("bulk")
    # Labels carry the tier, so every metric series is tier-labeled.
    assert prem.labels == {"replica": "p0", "tier": "premium"}
    assert anyrep.labels == {"replica": "x0"}
    # An all-premium pool cannot route bulk at all (defer, not
    # upgrade): route returns None.
    solo = ReplicaPool([Replica("p1", _echo("p1"), telemetry=tel,
                                clock=clock, tier="premium")],
                       clock=clock, telemetry=tel)
    assert solo.route(tier="bulk") is None


def test_pooled_scheduler_dispatches_tiers_to_matching_replicas():
    """End-to-end through the gateway: mixed-tier traffic lands each
    micro-batch on the replica of ITS tier (echo backends tag the
    transcript with the serving replica)."""
    clock = Clock()
    tel = ServingTelemetry()
    reps = [Replica("p0", _echo("p0"), telemetry=tel, clock=clock,
                    tier="premium"),
            Replica("b0", _echo("b0"), telemetry=tel, clock=clock,
                    tier="bulk")]
    pool = ReplicaPool(reps, clock=clock, telemetry=tel)
    s = _sched(clock, pool, tier_max_batch={"premium": 2, "bulk": 2})
    rids = {}
    for k in range(2):
        rids[s.submit(_feat(50), tier="premium")] = "p0"
        rids[s.submit(_feat(50), tier="bulk")] = "b0"
    s.pump()
    assert len(s.results) == 4
    for rid, home in rids.items():
        r = s.results[rid]
        assert r.status == "ok" and r.text.startswith(f"{home}:")
    # Tier-labeled gateway metrics (the check_obs_schema family rule).
    assert tel.counter("requests_ok", labels={"tier": "premium"}) == 2
    assert tel.counter("requests_ok", labels={"tier": "bulk"}) == 2


def test_tier_labels_roundtrip_through_check_obs_schema():
    """A tiered pooled run's snapshot passes the schema lint; a record
    mixing tier-labeled and unlabeled series in one family fails."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import check_obs_schema

    clock = Clock()
    tel = ServingTelemetry()
    reps = [Replica("p0", _echo("p0"), telemetry=tel, clock=clock,
                    tier="premium"),
            Replica("b0", _echo("b0"), telemetry=tel, clock=clock,
                    tier="bulk")]
    pool = ReplicaPool(reps, clock=clock, telemetry=tel)
    s = _sched(clock, pool, tier_max_batch={"premium": 2, "bulk": 2})
    for _ in range(2):
        s.submit(_feat(50), tier="premium")
        s.submit(_feat(50), tier="bulk")
    s.pump()
    buf = io.StringIO()
    tel.emit_jsonl(buf)
    lines = buf.getvalue().splitlines()
    assert check_obs_schema.scan(lines) == []
    rec = json.loads(lines[0])
    assert 'requests_ok{tier="premium"}' in rec["counters"]
    # Poison: an unlabeled twin in a tier-labeled family.
    rec["counters"]["requests_ok"] = 1
    problems = check_obs_schema.scan([json.dumps(rec)])
    assert any("mixes tier-labeled" in p for _, p in problems)


def test_trace_report_groups_per_tier():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import trace_report

    recs = [
        {"event": "span", "name": "gateway.dispatch", "ts": 0.0,
         "dur_ms": 4.0, "id": 1, "replica": "p0", "tier": "premium"},
        {"event": "span", "name": "gateway.dispatch", "ts": 0.01,
         "dur_ms": 8.0, "id": 2, "replica": "b0", "tier": "bulk"},
        {"event": "span", "name": "gateway.dispatch", "ts": 0.02,
         "dur_ms": 2.0, "id": 3, "replica": "b0", "tier": "bulk"},
        {"event": "compile", "name": "compile", "ts": 0.03,
         "dur_ms": 1.0, "rung": "4x64", "replica": "b0",
         "tier": "bulk"},
    ]
    agg = trace_report.aggregate(recs)
    assert agg["tiers"]["premium"]["spans"] == 1
    assert agg["tiers"]["bulk"]["spans"] == 2
    assert agg["tiers"]["bulk"]["compiles"] == 1
    assert agg["tiers"]["bulk"]["cum_ms"] == pytest.approx(10.0)
    # Per-replica grouping is unchanged alongside.
    assert agg["replicas"]["b0"]["spans"] == 2
    out = trace_report.render(agg)
    assert "per-tier breakdown" in out and "per-replica breakdown" in out


def test_replica_decode_span_carries_tier(tmp_path):
    """Replica.decode's gateway.dispatch span carries the tier
    attribute when the replica is tiered — trace_report's per-tier
    grouping feeds off it."""
    from deepspeech_tpu import obs
    from deepspeech_tpu.serving.scheduler import MicroBatch

    trace = tmp_path / "t.jsonl"
    with open(trace, "w") as fh:
        obs.configure(enabled=True, sink=fh)
        try:
            clock = Clock()
            tel = ServingTelemetry()
            rep = Replica("b0", _echo("b0"), telemetry=tel, clock=clock,
                          tier="bulk")
            s = _sched(clock, ReplicaPool([rep], clock=clock,
                                          telemetry=tel),
                       tier_max_batch={"bulk": 2})
            for _ in range(2):
                s.submit(_feat(50), tier="bulk")
            s.pump()
        finally:
            obs.configure(enabled=False)
    recs = [json.loads(l) for l in open(trace) if l.strip()]
    spans = [r for r in recs if r.get("name") == "gateway.dispatch"]
    assert spans and all(r.get("tier") == "bulk" for r in spans)
    assert all(r.get("replica") == "b0" for r in spans)


# -- rollout-adjacent lifecycle fixes (ISSUE-8 satellites) ----------------

def test_unpark_does_not_reactivate_breaker_draining_replica():
    """Regression: unpark() used to flip ANY draining replica back to
    ACTIVE — including one draining because its breaker opened, undoing
    the drain mid-window. It must act only on parked / parking-bound
    replicas."""
    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(2, clock, tel)
    r0 = pool.replicas[0]
    _trip(r0.breaker)
    pool.maintain()                      # breaker open -> plain drain
    assert r0.state == STATE_DRAINING and not r0.parking
    r0.unpark()                          # must be a no-op
    assert r0.state == STATE_DRAINING
    # Parking-bound (brownout/rollout) drains DO unpark mid-window...
    r1 = pool.replicas[1]
    r1.begin_drain(clock(), 0.25, park=True, reason="rollout")
    assert r1.parking and r1.park_reason == "rollout"
    r1.unpark()
    assert r1.state == STATE_ACTIVE and r1.park_reason is None
    # ...and so does a fully parked replica.
    r1.begin_drain(clock(), 0.0, park=True, reason="rollout")
    pool.maintain()
    assert r1.state == STATE_PARKED
    r1.unpark()
    assert r1.state == STATE_ACTIVE


def test_brownout_ignores_rollout_parks_both_ways():
    """park_reason separates the two park owners: a rollout park must
    not satisfy brownout rung 3's at-most-one-parked rule, and brownout
    recovery must not re-admit a mid-swap replica."""
    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(3, clock, tel, drain_window_s=0.0)
    r0, r1, r2 = pool.replicas
    r0.begin_drain(clock(), 0.0, park=True, reason="rollout")
    pool.maintain()
    assert r0.state == STATE_PARKED and r0.park_reason == "rollout"
    # Rung 3 still parks ITS OWN victim (the rollout park is not
    # "the one allowed brownout park").
    r1.inflight = 5
    pool.apply_brownout(LEVEL_REPLICA_DRAIN)
    assert r1.parking and r1.park_reason == "brownout"
    r1.inflight = 0
    pool.maintain()
    assert r1.state == STATE_PARKED
    # Recovery re-admits the brownout park ONLY; the rollout park stays
    # with the controller that owns it.
    pool.apply_brownout(0)
    assert r1.state == STATE_ACTIVE
    assert r0.state == STATE_PARKED and r0.park_reason == "rollout"


def test_decode_inflight_gauge_reports_snapshot_under_lock():
    """Regression: the inflight gauge used to re-read self.inflight
    outside the lock, so two concurrent decodes could both report the
    decremented value (or a torn intermediate). The gauge must emit the
    value captured inside the critical section."""
    from deepspeech_tpu.data.infer_bucket import InferBucketPlan

    class MB:
        requests = [object()]
        b_rung, t_rung = 1, 64
        reason, occupancy = "full", 1.0

        def batch(self):
            return {"features": _feat(64)[None]}

        def plan(self):
            return InferBucketPlan(np.arange(1), 1, 64)

    clock = Clock()
    tel = ServingTelemetry()
    seen = []
    orig_gauge = tel.gauge

    def spy(name, value, labels=None):
        if name == "inflight":
            seen.append(value)
        return orig_gauge(name, value, labels=labels)

    tel.gauge = spy
    rep = Replica("r0", _echo("r0"), telemetry=tel, clock=clock)
    rep.decode(MB())
    # One decode: gauge goes 1 (enter) then 0 (exit) — the snapshot
    # values, in order.
    assert seen == [1, 0]
    assert rep.inflight == 0


def test_add_replica_repins_live_sessions_no_lost_chunks():
    """Live pool resize under pinned streaming sessions: add_replica
    moves ~1/N of the pins onto the new replica (counted as
    session_repins), the router follows the moved pins, and every
    chunk fed before/after the resize lands in the final."""
    clock = Clock()
    tel = ServingTelemetry()
    log = []
    pool = _pool(3, clock, tel, session_factory=lambda: FakeMgr(log))
    router = PooledSessionRouter(pool)
    sids = [f"s{k}" for k in range(60)]
    for sid in sids:
        router.join(sid)
    router.step({sid: "c0" for sid in sids})
    before = {sid: pool.pin_of(sid) for sid in sids}
    repins0 = pool.repins
    pool.add_replica(Replica("r3", _echo("r3"), telemetry=tel,
                             clock=clock,
                             breaker=_breaker(clock, tel, "b3"),
                             session_factory=lambda: FakeMgr(log)))
    moved = [sid for sid in sids if pool.pin_of(sid) != before[sid]]
    # ~1/4 of the keyspace, every moved pin onto the NEW replica.
    assert 0 < len(moved) < len(sids) // 2
    assert all(pool.pin_of(sid) == "r3" for sid in moved)
    assert pool.repins - repins0 == len(moved)
    assert int(tel.counters.get("session_repins", 0)) == len(moved)
    # The router follows the pool-side pin moves on the next step; the
    # old homes' chunks come back as finalized segments.
    out = router.step({sid: "c1" for sid in sids})
    assert all(router.home_of(sid) == "r3" for sid in moved)
    assert out == {sid: "c0 c1" for sid in sids}
    for sid in sids:
        router.leave(sid)
    router.flush()
    for sid in sids:
        assert router.final(sid) == "c0 c1"
    # An unroutable newcomer must NOT steal pins (sessions would park
    # on a dead home).
    r4 = Replica("r4", _echo("r4"), telemetry=tel, clock=clock,
                 breaker=_breaker(clock, tel, "b4"))
    _trip(r4.breaker)
    pins_before = dict(pool._pins)
    pool.add_replica(r4)
    assert pool._pins == pins_before


def test_remove_replica_repins_live_sessions_no_lost_chunks():
    """The scale-down mirror of the resize contract: drain the victim
    behind the window first (its sessions re-pin, their fed chunks
    finalize as a segment), then remove_replica only returns its ring
    share — pins NOT on the victim never move, and nothing is lost."""
    clock = Clock()
    tel = ServingTelemetry()
    log = []
    pool = _pool(3, clock, tel, session_factory=lambda: FakeMgr(log))
    router = PooledSessionRouter(pool)
    sids = [f"s{k}" for k in range(60)]
    for sid in sids:
        router.join(sid)
    router.step({sid: "c0" for sid in sids})
    before = {sid: pool.pin_of(sid) for sid in sids}
    on_victim = [sid for sid in sids if before[sid] == "r0"]
    assert on_victim   # 60 sessions over 3 replicas: r0 has some

    # The autoscale lifecycle: park-drain (reason tagged so brownout
    # recovery keeps its hands off), step once so the router re-pins
    # and collects the old home's segments, then remove.
    r0 = pool.replica("r0")
    r0.begin_drain(clock.t, 0.25, park=True, reason="autoscale")
    out = router.step({sid: "c1" for sid in sids})
    assert out == {sid: "c0 c1" for sid in sids}
    assert all(pool.pin_of(sid) != "r0" for sid in on_victim)
    clock.t = 0.5
    pool.maintain(clock.t)
    assert r0.state == STATE_PARKED
    assert r0.peek_session_manager().stats()["active"] == 0

    repins0 = pool.repins
    pool.remove_replica("r0")
    assert len(pool) == 2
    # Only the victim's pins moved — survivors' pins are untouched by
    # the removal itself (the re-pin happened at drain time).
    for sid in sids:
        if before[sid] != "r0":
            assert pool.pin_of(sid) == before[sid]
    assert pool.repins == repins0   # removal itself re-pins nothing

    router.step({sid: "c2" for sid in sids})
    for sid in sids:
        router.leave(sid)
    router.flush()
    for sid in sids:
        assert router.final(sid) == "c0 c1 c2"


# -- scenario: two real engines behind the pool ---------------------------

def test_scenario_two_replicas_trip_midreplay_loses_nothing(
        tiny_offline, tiny_streaming, obs_lint):
    """Seeded traffic over two real (tiny) engines with one replica's
    breaker forced open halfway, then pinned streaming sessions whose
    home trips: no admitted request is lost, a transcript does not
    depend on the replica that served it, both replicas carried rows,
    every session finalizes after its re-pin, and the replica-labeled
    telemetry lints clean."""
    from scenario import ManualClock, poisson_requests, replay, solo_decode
    from deepspeech_tpu.serving import StreamingSessionManager

    clock = ManualClock()
    tel = ServingTelemetry()
    scfg, stok, sparams, sstats = tiny_streaming

    def sessions():
        return StreamingSessionManager(scfg, sparams, sstats, stok,
                                       chunk_frames=64, capacity=1,
                                       telemetry=tel)

    infs = [tiny_offline.inferencer() for _ in range(2)]
    pool = ReplicaPool(
        [Replica.from_inferencer(f"r{k}", infs[k], telemetry=tel,
                                 clock=clock, session_factory=sessions,
                                 breaker=_breaker(clock, tel, f"b{k}",
                                                  cooldown=0.25))
         for k in range(2)], clock=clock, telemetry=tel)
    n = 16
    arrivals, reqs = poisson_requests(n)
    s = _sched(clock, pool, default_deadline=0.02)
    results = replay(
        s, clock, arrivals, reqs, on_arrival=lambda i: i == n // 2
        and _trip(pool.replica("r1").breaker))
    c = tel.snapshot()["counters"]
    assert int(c["admitted"]) == n == len(results)
    assert int(c["admitted"]) - int(c.get("requests_ok", 0)) \
        - int(c.get("requests_timeout", 0)) \
        - int(c.get("requests_error", 0)) == 0
    assert sum(r.breaker.opens for r in pool) >= 1
    for rid, r in results.items():
        assert r.status == "ok"
        feat = reqs[int(rid[1:])]
        assert r.text == solo_decode(infs[0], feat) \
            == solo_decode(infs[1], feat)
    rows = {r.rid: r.stats()["rows"] for r in pool}
    assert set(rows) == {"r0", "r1"} and min(rows.values()) > 0
    assert sum(rows.values()) >= n

    # Streaming: two sessions, the first one's home trips mid-stream.
    for r in pool:
        r.breaker.cooldown_s = 60.0
    clock.t += 1.0
    pool.maintain()
    router = PooledSessionRouter(pool)
    rng = np.random.default_rng(1)
    sids = ["s0", "s1"]
    homes = {sid: router.join(sid) for sid in sids}

    def feed():
        router.step({sid: rng.standard_normal((64, NF)).astype(
            np.float32) for sid in sids})

    feed(), feed()
    _trip(pool.replica(homes["s0"]).breaker)
    feed(), feed()
    assert router.home_of("s0") != homes["s0"]
    for sid in sids:
        router.leave(sid)
    router.flush()
    assert all(isinstance(router.final(sid), str) for sid in sids)
    assert pool.repins >= 1
    grows = [ev for r in pool if r.peek_session_manager() is not None
             for ev in r.peek_session_manager().grow_events]
    assert int(tel.snapshot()["counters"]["capacity_grows"]) \
        == len(grows) >= 1
    assert obs_lint(tel) == []
