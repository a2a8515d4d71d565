"""Async LM rescoring plane (serving/rescoring.py): offer gates in
order (empty n-best, brownout rung, tenancy quota, bounded queue),
pump-driven determinism, the score_delta argmax contract, per-job
trace ledgers, and the brownout controller's dedicated rescore rung.
The end-to-end legs (first-pass latencies unchanged on the scripted
clock, shed-to-zero under flood) are the scenario at the end."""

import json

import pytest

from deepspeech_tpu.obs.context import FlightRecorder
from deepspeech_tpu.resilience.brownout import BrownoutController
from deepspeech_tpu.serving import (AdmissionController, RescoringPool,
                                    RescoringQueue, ServingTelemetry,
                                    TenantConfig)


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class PreferGood:
    """Deterministic toy LM: +2 per 'good' token, -0.25 per word."""

    def score_sentence(self, s):
        words = s.split()
        return 2.0 * sum(w == "good" for w in words) - 0.25 * len(words)


def _pool(clock, **kw):
    kw.setdefault("lm", PreferGood())
    kw.setdefault("alpha", 1.0)
    kw.setdefault("telemetry", ServingTelemetry())
    return RescoringPool(clock=clock, **kw)


# Combined scores under PreferGood, alpha=1: "bad x" = 1.0 - 0.5 =
# 0.5; "good x" = 0.9 + (2.0 - 0.5) = 2.4 — the LM flips the order.
NB = [("bad x", 1.0), ("good x", 0.9)]


def test_offer_pump_revision():
    clock = Clock()
    pool = _pool(clock)
    assert pool.offer("r1", NB, "bad x", now=0.0)
    assert pool.depth == 1
    clock.advance(0.5)
    (ev,) = pool.pump()
    assert (ev.rid, ev.old_text, ev.new_text) == ("r1", "bad x",
                                                  "good x")
    assert ev.score_delta == pytest.approx(1.9)
    assert ev.rescore_latency == pytest.approx(0.5)
    assert pool.stats() == {"submitted": 1, "completed": 1,
                            "revised": 1, "shed": {},
                            "queue_depth": 0, "workers": 1}


def test_no_revision_when_first_pass_already_wins():
    pool = _pool(Clock())
    assert pool.offer("r1", [("good x", 1.0), ("bad x", 0.9)],
                      "good x", now=0.0)
    assert pool.pump(now=0.0) == []
    st = pool.stats()
    assert st["completed"] == 1 and st["revised"] == 0


def test_revision_event_json_shape():
    pool = _pool(Clock())
    pool.offer("r1", NB, "bad x", model="a", tenant="gold", now=0.0)
    (ev,) = pool.pump(now=0.25)
    rec = ev.to_json()
    assert rec["rid"] == "r1" and rec["model"] == "a"
    assert rec["tenant"] == "gold"
    assert rec["score_delta"] == pytest.approx(1.9)
    assert rec["rescore_latency_ms"] == pytest.approx(250.0)


def test_empty_nbest_sheds():
    pool = _pool(Clock())
    assert not pool.offer("r1", [], now=0.0)
    assert not pool.offer("r2", None, now=0.0)
    assert pool.shed == {"empty_nbest": 2}
    assert pool.submitted == 0


def test_bounded_queue_sheds_when_full():
    pool = _pool(Clock(), max_queue=1)
    assert pool.offer("r1", NB, now=0.0)
    assert not pool.offer("r2", NB, now=0.0)
    assert pool.shed == {"queue_full": 1}
    assert len(pool.drain(now=0.0)) == 1  # the accepted job survives


def test_queue_bounds():
    with pytest.raises(ValueError):
        RescoringQueue(max_depth=0)
    q = RescoringQueue(max_depth=2)
    assert q.pop() is None


def test_exactly_one_lm_source():
    with pytest.raises(ValueError):
        RescoringPool()
    with pytest.raises(ValueError):
        RescoringPool(lm=PreferGood(), lm_factory=PreferGood)


def test_lm_factory_builds_one_per_worker():
    made = []

    def factory():
        made.append(PreferGood())
        return made[-1]

    pool = RescoringPool(lm_factory=factory, workers=3, clock=Clock())
    assert len(made) == 3
    assert len({id(lm) for lm in pool._lms}) == 3


def test_worker_assignment_is_submit_order_round_robin():
    pool = _pool(Clock(), workers=2)
    for i in range(4):
        assert pool.offer(f"r{i}",
                          [(f"bad {i}", 1.0), (f"good {i}", 0.9)],
                          now=0.0)
    evs = pool.drain(now=0.0)
    assert [ev.worker for ev in evs] == [0, 1, 0, 1]


def test_replay_bit_identical():
    def run():
        clock = Clock()
        pool = _pool(clock, workers=2)
        out = []
        for i in range(6):
            pool.offer(f"r{i}",
                       [(f"bad {i}", 1.0), (f"good {i}", 0.9)],
                       now=clock())
            clock.advance(0.01)
            out.extend(pool.pump(now=clock()))
        return [(e.rid, e.new_text, e.score_delta, e.worker,
                 e.rescore_latency) for e in out]

    assert run() == run()


def test_pump_max_jobs_bounds_one_beat():
    pool = _pool(Clock())
    for i in range(3):
        pool.offer(f"r{i}", NB, now=0.0)
    pool.pump(now=0.0, max_jobs=2)
    assert pool.depth == 1


def test_old_text_missing_from_nbest_falls_back_to_head():
    # Segment-joined finals (endpointing, multi-segment sessions) may
    # not appear in the n-best; the delta falls back to the head's
    # rescored score rather than crashing or going unbounded.
    pool = _pool(Clock())
    pool.offer("r1", NB, "joined segment text", now=0.0)
    (ev,) = pool.pump(now=0.0)
    assert ev.old_text == "joined segment text"
    assert ev.new_text == "good x"
    assert ev.score_delta == pytest.approx(1.9)


def test_to_lm_text_maps_hypotheses():
    seen = []

    class SpyLM:
        def score_sentence(self, s):
            seen.append(s)
            return 0.0

    pool = RescoringPool(lm=SpyLM(), alpha=1.0, clock=Clock(),
                         to_lm_text=lambda t: " ".join(t))
    pool.offer("r1", [("ab", 0.0), ("cd", -1.0)], now=0.0)
    pool.pump(now=0.0)
    assert seen == ["a b", "c d"]


def test_brownout_rescore_rung_sheds_before_any_degradation():
    clock = Clock()
    tel = ServingTelemetry()
    bro = BrownoutController(enter_pressure=0.75, exit_pressure=0.0,
                             shed_pressure=0.9, hold_s=0.0,
                             rescore_pressure=0.4, clock=clock,
                             registry=tel)
    pool = _pool(clock, brownout=bro, telemetry=tel)
    bro.update(0.5, now=0.0)
    assert bro.level == 0            # first pass fully undegraded...
    assert not bro.should_rescore()  # ...rescore rung already fired
    assert not pool.offer("r1", NB, now=0.0)
    assert pool.shed == {"brownout": 1}
    clock.advance(1.0)
    bro.update(0.0, now=clock())
    assert bro.should_rescore()
    assert pool.offer("r2", NB, now=clock())
    counters = tel.snapshot()["counters"]
    assert counters.get("rescore_disabled") == 1
    assert counters.get("rescore_reenabled") == 1
    assert tel.snapshot()["gauges"].get("rescore_enabled") == 1


def test_brownout_level_gate_without_rescore_pressure():
    clock = Clock()
    bro = BrownoutController(enter_pressure=0.5, exit_pressure=0.0,
                             shed_pressure=0.9, hold_s=0.0,
                             clock=clock)
    pool = _pool(clock, brownout=bro)
    bro.update(0.6, now=0.0)
    assert bro.level >= 1            # degraded: rescoring off
    assert not pool.offer("r1", NB, now=0.0)
    assert pool.shed == {"brownout": 1}


def test_rescore_pressure_validation():
    with pytest.raises(ValueError):
        BrownoutController(enter_pressure=0.5, rescore_pressure=0.6)
    with pytest.raises(ValueError):
        BrownoutController(rescore_pressure=0.0)


def test_tenancy_charge_release_and_quota_shed():
    clock = Clock()
    ten = AdmissionController(
        [TenantConfig("rescore", quota=1, priority="batch")])
    pool = _pool(clock, tenancy=ten)
    assert pool.offer("r1", NB, now=0.0)
    assert ten.inflight("rescore") == 1
    assert not pool.offer("r2", NB, now=0.0)   # quota full
    assert pool.shed == {"quota": 1}
    pool.drain(now=0.0)
    assert ten.inflight("rescore") == 0        # released after pump


def test_tenancy_unknown_tenant_sheds_not_raises():
    pool = _pool(Clock(), tenancy=AdmissionController(
        [TenantConfig("gold", quota=4, priority="realtime")]),
        tenant="nonexistent")
    assert not pool.offer("r1", NB, now=0.0)
    assert pool.shed == {"quota": 1}


def test_rescore_trace_ledger_is_its_own_context():
    clock = Clock()
    fr = FlightRecorder(capacity=8)
    pool = _pool(clock, flight_recorder=fr)
    pool.offer("r1", NB, "bad x", now=0.0)
    clock.advance(0.2)     # time spent queued
    pool.pump()
    recs = [r for r in fr.recent() if r.get("kind") == "rescore"]
    assert len(recs) == 1
    rec = recs[0]
    assert rec["rid"] == "r1" and rec["status"] == "ok"
    assert rec["revised"] is True
    assert rec["phases"]["rescore_queue"] == pytest.approx(200.0)
    assert rec["latency_ms"] == pytest.approx(200.0)


# -- scenario: the slow path behind a live gateway ------------------------

def _gateway_replay(rescoring_on):
    """30 scripted requests through a two-replica gateway whose
    decoders return ``(texts, nbest)``: 16 paced, a 12-request flood
    (queue fill 0.5: above the rescore rung, below degradation), 2
    after the drain. The pool pumps between scheduler pumps."""
    import numpy as np

    from deepspeech_tpu.serving import (MicroBatchScheduler, Replica,
                                        ReplicaPool)

    clock = Clock()
    tel = ServingTelemetry()
    bro = BrownoutController(enter_pressure=0.75, exit_pressure=0.0,
                             shed_pressure=0.9, hold_s=0.0,
                             rescore_pressure=0.3, clock=clock,
                             registry=tel)
    revisions = []
    resc = _pool(clock, workers=2, max_queue=16, telemetry=tel,
                 brownout=bro, on_revision=revisions.append) \
        if rescoring_on else None

    def decode(batch, plan):
        uids = [int(batch["features"][i].sum())
                for i in range(plan.n_valid)]
        texts = [f"bad {u}" if u % 2 else f"plain {u}" for u in uids]
        return texts, [[(t, 1.0),
                        (f"good {u}" if u % 2 else f"also {u}", 0.9)]
                       for t, u in zip(texts, uids)]

    pool = ReplicaPool([Replica(f"r{k}", decode, telemetry=tel,
                                clock=clock) for k in range(2)],
                       clock=clock, telemetry=tel)
    sched = MicroBatchScheduler((16, 32), 4, max_queue=24,
                                default_deadline=0.05, clock=clock,
                                telemetry=tel, pool=pool, brownout=bro,
                                rescorer=resc)
    rids = []

    def submit(uid, frames=8):
        feat = np.zeros((frames, 8), np.float32)
        feat[0, 0] = uid
        clock.advance(0.0005)
        rids.append(sched.submit(feat))

    for uid in range(1, 17):
        submit(uid, 8 if uid % 3 else 20)
        clock.advance(0.0015)
        sched.pump()
        clock.advance(0.0005)
        if resc is not None:
            resc.pump(now=clock())
    sched.drain()
    if resc is not None:
        clock.advance(0.001)
        resc.drain(now=clock())
    shed0 = dict(resc.shed).get("brownout", 0) if resc else 0
    for uid in range(17, 29):
        submit(uid)
    level_at_flood = bro.level
    sched.drain()
    flood_shed = (dict(resc.shed).get("brownout", 0) - shed0
                  if resc else 0)
    offered0 = resc.submitted if resc else 0
    for uid in range(29, 31):
        submit(uid)
        clock.advance(0.0015)
        sched.pump()
    sched.drain()
    if resc is not None:
        clock.advance(0.001)
        resc.drain(now=clock())
    assert [sched.results[r].status for r in rids] == ["ok"] * 30
    return {"latencies": [sched.results[r].latency for r in rids],
            "revisions": [(ev.rid, ev.old_text, ev.new_text,
                           ev.score_delta) for ev in revisions],
            "events": revisions, "tel": tel,
            "level_at_flood": level_at_flood, "flood_shed": flood_shed,
            "offered_after": resc.submitted - offered0 if resc else 0}


def test_scenario_slow_path_costs_the_fast_path_nothing(obs_lint):
    """The same scripted replay with the rescoring pool on, on again,
    and off: every first-pass request completes with the SAME
    scripted-clock latency either way; the LM revises exactly the
    flippable (odd) finals of the paced phase, each to its 'good'
    hypothesis with a nonnegative delta; two runs emit identical
    revision streams; the flood, which never degrades the first pass,
    sheds all 12 rescoring offers and the pool re-enables afterwards;
    telemetry and the streamed ``revision`` lines lint clean."""
    on, again, off = (_gateway_replay(True), _gateway_replay(True),
                      _gateway_replay(False))
    assert on["latencies"] == off["latencies"]
    revs = on["revisions"]
    assert len(revs) == 9                  # odd uids of 1..16, 29
    assert all(old.startswith("bad") and new.startswith("good")
               and delta >= 0.0 for _, old, new, delta in revs)
    assert revs == again["revisions"] and off["revisions"] == []
    assert on["level_at_flood"] == 0 and on["flood_shed"] == 12
    assert on["offered_after"] == 2
    c = on["tel"].snapshot()["counters"]
    assert c["rescore_disabled"] >= 1 and c["rescore_reenabled"] >= 1
    lines = [json.dumps({"revision": ev.to_json()})
             for ev in on["events"]]
    assert obs_lint(on["tel"], lines) == []
