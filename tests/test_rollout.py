"""Zero-downtime rolling model swap: RolloutController contracts.

Covers the ISSUE-8 tentpole surface: the drain->canary->swap->re-admit
state machine, bit-exact rollback on canary regression or injected
swap fault (with the ``kind="rollout"`` postmortem and the parked
candidate), pause/resume under brownout pressure and breaker opens,
the never-below-floor rule, at-most-one re-pin for pinned streaming
sessions riding a full-pool swap, and the ``version``-labeled metric
families round-tripping through ``tools/check_obs_schema.py``.

Same test substrate as test_replica.py: an injectable virtual clock,
echo decode backends, and FakeMgr session managers — no model, no
device, deterministic.
"""

import io
import json
import os
import sys

import pytest

from deepspeech_tpu.resilience import (CircuitBreaker, FaultPlan,
                                       FaultSpec, faults)
from deepspeech_tpu.resilience.brownout import LEVEL_DEGRADED
from deepspeech_tpu.serving import (PooledSessionRouter, Replica,
                                    ReplicaPool, RolloutController,
                                    ServingTelemetry)
from deepspeech_tpu.serving.replica import (STATE_ACTIVE,
                                            STATE_DRAINING,
                                            STATE_PARKED)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _echo(tag):
    def fn(batch, plan):
        return [f"{tag}"]
    return fn


def _breaker(clock, tel, name, threshold=2, cooldown=1.0):
    return CircuitBreaker(name=name, failure_threshold=threshold,
                          cooldown_s=cooldown, clock=clock,
                          registry=tel)


def _pool(n, clock, tel, drain_window_s=0.25, **rep_kw):
    reps = [Replica(f"r{k}", _echo(f"r{k}"), telemetry=tel, clock=clock,
                    breaker=_breaker(clock, tel, f"b{k}"), **rep_kw)
            for k in range(n)]
    pool = ReplicaPool(reps, clock=clock, telemetry=tel,
                       drain_window_s=drain_window_s)
    for rep in pool:
        rep.version = "v1"
    return pool


def _same_backend(rep):
    """A candidate whose transcripts match the old backend's exactly —
    the bit-identical canary accept path."""
    return {"decode_fn": _echo(rep.rid), "session_factory": None,
            "inferencer": None}


def _drive(ro, clock, max_ticks=50, dt=0.3):
    """Advance the virtual clock past the drain window between ticks
    until the rollout settles."""
    for _ in range(max_ticks):
        if ro.state in ("done", "rolled_back"):
            return ro.state
        clock.t += dt
        ro.tick()
    return ro.state


CANARY = [({}, None)]  # echo backends ignore (batch, plan)


# -- the accept path ------------------------------------------------------

def test_full_pool_swap_reaches_done_on_new_version():
    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(3, clock, tel)
    old_fns = {r.rid: r.decode_fn for r in pool}
    ro = RolloutController(pool, _same_backend, to_version="v2",
                           canary_set=CANARY)
    ro.start()
    assert ro.state == "running"
    assert _drive(ro, clock) == "done"
    assert sorted(ro.upgraded) == ["r0", "r1", "r2"]
    for rep in pool:
        assert rep.version == "v2"
        assert rep.state == STATE_ACTIVE and rep.can_route()
        assert rep.decode_fn is not old_fns[rep.rid]  # really swapped
    # The re-pin preference is cleared once the rollout is over.
    assert pool.prefer_rids == set()
    assert int(tel.counters.get('rollout_swaps{version="v2"}', 0)) == 3
    assert tel.gauges.get('rollout_state{version="v2"}') == 3  # done
    actions = [e["action"] for e in ro.events]
    assert actions[0] == "start" and actions[-1] == "done"
    assert actions.count("swap") == 3
    # Replicas already on the target version are not re-swapped.
    ro2 = RolloutController(pool, _same_backend, to_version="v2",
                            canary_set=CANARY)
    ro2.start()
    assert ro2.state == "done" and ro2.upgraded == []


def test_one_replica_at_a_time_and_drain_window_honored():
    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(2, clock, tel, drain_window_s=0.25)
    ro = RolloutController(pool, _same_backend, canary_set=CANARY)
    ro.start()
    ro.tick()
    draining = [r for r in pool if r.state == STATE_DRAINING]
    assert len(draining) == 1 and draining[0].park_reason == "rollout"
    # Inside the window nothing is swapped yet, and the OTHER replica
    # keeps routing (zero downtime).
    clock.t = 0.1
    ro.tick()
    assert draining[0].state == STATE_DRAINING
    assert pool.route() is not None
    # Past the window the victim parks, swaps, and re-admits.
    clock.t = 0.3
    ro.tick()
    assert draining[0].state == STATE_ACTIVE
    assert draining[0].version == "v2"


def test_on_event_callback_sees_every_transition():
    clock = Clock()
    seen = []
    pool = _pool(2, clock, ServingTelemetry())
    ro = RolloutController(pool, _same_backend, canary_set=CANARY,
                           on_event=seen.append)
    ro.start()
    _drive(ro, clock)
    assert [e["action"] for e in seen] == [e["action"] for e in ro.events]
    assert all(e["version"] == "v2" for e in seen)


# -- canary ---------------------------------------------------------------

def test_canary_guardrail_accepts_within_and_rejects_beyond():
    def near_miss(rep):
        # 1 of 4 words differs: WER 0.25 against the old transcripts.
        return {"decode_fn": lambda b, p: [f"{rep.rid} a b X"]}

    for guardrail, want in ((0.30, "done"), (0.10, "rolled_back")):
        clock = Clock()
        pool = _pool(2, clock, ServingTelemetry())
        for rep in pool:
            rep.decode_fn = (lambda tag: lambda b, p:
                             [f"{tag} a b c"])(rep.rid)
        ro = RolloutController(pool, near_miss, canary_set=CANARY,
                               wer_guardrail=guardrail)
        ro.start()
        assert _drive(ro, clock) == want
        assert ro.last_wer_delta == pytest.approx(0.25)


def test_canary_skipped_when_not_configured():
    clock = Clock()
    pool = _pool(2, clock, ServingTelemetry())
    ro = RolloutController(pool, _same_backend)  # no canary_set/fn
    ro.start()
    assert _drive(ro, clock) == "done"
    assert ro.last_wer_delta is None


def test_canary_fn_overrides_canary_set():
    calls = []

    def canary_fn(old, new):
        calls.append((old["decode_fn"] is not None,
                      new["decode_fn"] is not None))
        return ["same"], ["same"]

    clock = Clock()
    pool = _pool(2, clock, ServingTelemetry())
    ro = RolloutController(pool, _same_backend, canary_fn=canary_fn)
    ro.start()
    assert _drive(ro, clock) == "done"
    assert calls == [(True, True)] * 2


# -- rollback -------------------------------------------------------------

def test_canary_regression_rolls_back_bit_exact_with_postmortem():
    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(2, clock, tel)
    old_fns = {r.rid: r.decode_fn for r in pool}
    pms = []

    def mangled(rep):
        return {"decode_fn": lambda b, p: ["totally different words"]}

    ro = RolloutController(pool, mangled, to_version="v2",
                           canary_set=CANARY, wer_guardrail=0.0,
                           postmortem_fn=lambda *a, **kw:
                           pms.append((a, kw)))
    ro.start()
    assert _drive(ro, clock) == "rolled_back"
    assert ro.rollbacks == 1
    # The victim serves the OLD backend object again — bit-exact
    # restore, not a re-build — and the pool stays fully routable.
    for rep in pool:
        assert rep.decode_fn is old_fns[rep.rid]
        assert rep.version == "v1"
        assert rep.state == STATE_ACTIVE and rep.can_route()
    assert pool.prefer_rids == set()
    # The rejected candidate is parked for inspection, never routable.
    assert ro.parked_candidate is not None
    assert ro.parked_candidate["decode_fn"] is not None
    # Postmortem: kind="rollout", trigger=canary_regression, evidence.
    (args, kw), = pms
    assert args == ("rollout",)
    assert kw["trigger"] == "canary_regression"
    assert kw["to_version"] == "v2" and kw["from_version"] == "v1"
    assert kw["wer_delta"] > 0
    assert int(tel.counters.get(
        'rollout_rollbacks{version="v2"}', 0)) == 1
    assert tel.gauges.get('rollout_state{version="v2"}') == 4


def test_swap_fault_point_rolls_back_and_pool_stays_routable():
    assert "rollout.swap" in faults.KNOWN_POINTS
    assert "rollout.canary" in faults.KNOWN_POINTS
    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(2, clock, tel)
    pms = []
    ro = RolloutController(pool, _same_backend, canary_set=CANARY,
                           postmortem_fn=lambda *a, **kw:
                           pms.append(kw))
    faults.install(FaultPlan([FaultSpec("rollout.swap", "error",
                                        count=1)], clock=clock))
    try:
        ro.start()
        assert _drive(ro, clock) == "rolled_back"
    finally:
        faults.clear()
    assert pms[0]["trigger"] == "swap_fault"
    assert "error" in pms[0]
    for rep in pool:
        assert rep.version == "v1"
        assert rep.can_route()
    assert pool.route() is not None


def test_rollback_keeps_already_upgraded_replicas():
    """Each upgraded replica passed its own canary: a later failure
    rolls back only the victim, not the fleet."""
    clock = Clock()
    pool = _pool(3, clock, ServingTelemetry())
    hits = []

    def flaky(rep):
        hits.append(rep.rid)
        if len(hits) == 3:   # third swap attempt raises mid-factory
            raise RuntimeError("checkpoint load failed")
        return _same_backend(rep)

    ro = RolloutController(pool, flaky, to_version="v2",
                           canary_set=CANARY)
    ro.start()
    assert _drive(ro, clock) == "rolled_back"
    versions = sorted(r.version for r in pool)
    assert versions == ["v1", "v2", "v2"]
    assert len(ro.upgraded) == 2


# -- pause / floor --------------------------------------------------------

class FakeBrownout:
    def __init__(self, level=0):
        self.level = level


def test_pause_on_brownout_readmits_victim_and_resumes():
    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(2, clock, tel)
    bo = FakeBrownout()
    ro = RolloutController(pool, _same_backend, canary_set=CANARY,
                           brownout=bo, pause_level=LEVEL_DEGRADED)
    ro.start()
    ro.tick()
    victim = next(r for r in pool if r.state == STATE_DRAINING)
    # Pressure hits mid-drain: the controller pauses AND gives the
    # capacity back (the victim re-enters routing on the old backend).
    bo.level = LEVEL_DEGRADED
    clock.t = 0.1
    ro.tick()
    assert ro.state == "paused"
    assert victim.state == STATE_ACTIVE and victim.can_route()
    assert victim.version == "v1"
    assert int(tel.counters.get('rollout_paused{version="v2"}', 0)) == 1
    # While paused nothing swaps, however long we wait.
    clock.t = 5.0
    ro.tick()
    assert ro.state == "paused"
    assert all(r.version == "v1" for r in pool)
    # Pressure clears: resume, and the rollout completes.
    bo.level = 0
    assert _drive(ro, clock) == "done"
    actions = [e["action"] for e in ro.events]
    assert "pause" in actions and "resume" in actions


def test_pause_on_foreign_breaker_open_then_resume():
    clock = Clock()
    pool = _pool(3, clock, ServingTelemetry())
    ro = RolloutController(pool, _same_backend, canary_set=CANARY)
    ro.start()
    # A NON-victim replica's breaker opens: pause rather than dropping
    # a second replica out of routing.
    bad = pool.replicas[2]
    while bad.breaker.state != "open":
        bad.breaker.record_failure()
    ro.tick()
    assert ro.state == "paused"
    assert ro.status()["pause_reason"] == "breaker_open_r2"
    # Past the cooldown the breaker admits probes again: resume.
    clock.t = 1.5
    assert _drive(ro, clock) == "done"


def test_never_drains_below_min_routable_floor():
    clock = Clock()
    pool = _pool(2, clock, ServingTelemetry())
    ro = RolloutController(pool, _same_backend, canary_set=CANARY,
                           min_routable=2)
    ro.start()
    for _ in range(5):
        clock.t += 0.3
        ro.tick()
    # A drain would leave only 1 other routable replica (< floor 2):
    # the rollout waits instead of starting one.
    assert ro.state == "running"
    assert all(r.state == STATE_ACTIVE for r in pool)
    assert all(r.version == "v1" for r in pool)


# -- sessions ride the swap ----------------------------------------------

class FakeMgr:
    """Duck-typed StreamingSessionManager (see test_replica.py): a left
    session finalizes immediately — exact chunk accounting."""

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


def test_pinned_sessions_repin_at_most_once_no_lost_chunks():
    clock = Clock()
    tel = ServingTelemetry()
    log = []
    pool = _pool(2, clock, tel, session_factory=lambda: FakeMgr(log))
    router = PooledSessionRouter(pool)
    # Sessions all homed on ONE replica (rejection-sample sids by ring
    # owner): fewest-pinned-first drains the empty replica first, and
    # prefer_rids lands the displaced sessions on the upgraded one.
    loaded = "r0"
    sids, k = [], 0
    while len(sids) < 3:
        if pool.ring_owner(f"s{k}") == loaded:
            sids.append(f"s{k}")
        k += 1
    for sid in sids:
        assert router.join(sid) == loaded

    def v2_backend(rep):
        # The candidate ships its own session factory — the swap drops
        # the old (drained) manager and rebuilds from this one.
        return {"decode_fn": _echo(rep.rid),
                "session_factory": lambda: FakeMgr(log)}

    ro = RolloutController(pool, v2_backend, to_version="v2",
                           canary_set=CANARY)
    ro.start()
    moves = {sid: 0 for sid in sids}
    last = {sid: loaded for sid in sids}
    fed = 0
    for tick in range(40):
        if ro.state in ("done", "rolled_back"):
            break
        clock.t += 0.3
        router.step({sid: f"c{fed}" for sid in sids})
        fed += 1
        for sid in sids:
            home = router.home_of(sid)
            if home != last[sid]:
                moves[sid] += 1
                last[sid] = home
        ro.tick()
    assert ro.state == "done"
    # At most one displacement per session, and it landed on the
    # already-upgraded replica (the prefer_rids economics).
    assert all(m <= 1 for m in moves.values())
    assert all(last[sid] != loaded for sid in sids)
    for sid in sids:
        router.leave(sid)
    router.flush()
    # Zero lost chunks: every fed chunk, in order, lands in the final.
    for sid in sids:
        assert router.final(sid) == " ".join(f"c{i}" for i in range(fed))


# -- observability --------------------------------------------------------

def test_rollout_metrics_roundtrip_through_check_obs_schema():
    """A rollout's telemetry snapshot (swap + rollback families, all
    version-labeled) passes the schema lint; stripping the version
    label off a rollout family fails it."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import check_obs_schema

    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(2, clock, tel)
    ro = RolloutController(pool, _same_backend, canary_set=CANARY)
    ro.start()
    _drive(ro, clock)
    buf = io.StringIO()
    tel.emit_jsonl(buf)
    lines = buf.getvalue().splitlines()
    assert check_obs_schema.scan(lines) == []
    rec = json.loads(lines[0])
    assert 'rollout_swaps{version="v2"}' in rec["counters"]
    assert 'rollout_state{version="v2"}' in rec["gauges"]
    # Poison 1: a version-less rollout series.
    bad = json.loads(lines[0])
    bad["counters"]["rollout_swaps"] = 1
    del bad["counters"]['rollout_swaps{version="v2"}']
    problems = check_obs_schema.scan([json.dumps(bad)])
    assert any("requires a 'version' label" in p for _, p in problems)
    # Poison 2: the family-mixing rule applies to version like any
    # other topology label.
    mixed = json.loads(lines[0])
    mixed["counters"]["rollout_swaps"] = 1
    problems = check_obs_schema.scan([json.dumps(mixed)])
    assert any("mixes version-labeled" in p for _, p in problems)


def test_rollout_spans_carry_version_for_trace_report(tmp_path):
    from deepspeech_tpu import obs

    sys.path.insert(0, os.path.join(REPO, "tools"))
    import trace_report

    trace = tmp_path / "t.jsonl"
    with open(trace, "w") as fh:
        obs.configure(enabled=True, sink=fh)
        try:
            clock = Clock()
            pool = _pool(2, clock, ServingTelemetry())
            ro = RolloutController(pool, _same_backend,
                                   to_version="ckpt-42",
                                   canary_set=CANARY)
            ro.start()
            _drive(ro, clock)
        finally:
            obs.configure(enabled=False)
    recs = [json.loads(l) for l in open(trace) if l.strip()]
    spans = [r for r in recs
             if r.get("name") in ("rollout.swap", "rollout.canary")]
    assert spans and all(r["version"] == "ckpt-42" for r in spans)
    agg = trace_report.aggregate(recs)
    assert agg["versions"]["ckpt-42"]["spans"] == len(spans)
    assert "rollout (per-version) breakdown" in trace_report.render(agg)


def test_run_convenience_driver_and_double_start_rejected():
    clock = Clock()
    pool = _pool(2, clock, ServingTelemetry(), drain_window_s=0.0)
    ro = RolloutController(pool, _same_backend, canary_set=CANARY)
    pumped = []
    assert ro.run(pump=lambda: pumped.append(1)) == "done"
    assert pumped  # the caller's pump ran between ticks
    with pytest.raises(RuntimeError):
        ro.start()


# -- scenario: the accept path over real engines under traffic ------------

def test_scenario_swap_under_live_traffic_real_engines(tiny_offline,
                                                       obs_lint):
    """v1 -> v2 (same weights) across two real (tiny) engines while a
    seeded replay runs: the rollout ends ``done`` on v2 with a real
    canary batch, at least one replica is routable at every arrival,
    no admitted request is lost, transcripts equal the solo v1 decode
    whichever version served them, and the version-labeled telemetry
    lints clean."""
    import numpy as np

    from scenario import (EDGES, ManualClock, poisson_requests, replay,
                          solo_decode)
    from deepspeech_tpu.data.infer_bucket import InferBucketPlan
    from deepspeech_tpu.serving import MicroBatchScheduler

    clock = ManualClock()
    tel = ServingTelemetry()
    v1 = [tiny_offline.inferencer() for _ in range(2)]
    pool = ReplicaPool(
        [Replica.from_inferencer(f"r{k}", v1[k], telemetry=tel,
                                 clock=clock,
                                 breaker=_breaker(clock, tel, f"b{k}"))
         for k in range(2)],
        clock=clock, telemetry=tel, drain_window_s=0.0)
    for rep in pool:
        rep.version = "v1"

    def v2_backend(rep):
        inf = tiny_offline.inferencer()
        return {"decode_fn": lambda batch, plan:
                inf.decode_batch_bucketed(batch, plans=[plan]),
                "session_factory": None, "inferencer": inf}

    n = 24
    arrivals, reqs = poisson_requests(n)
    canary = [({"features": reqs[0][None, :64],
                "feat_lens": np.full((1,), 64, np.int32)},
               InferBucketPlan(np.arange(1), 1, 64))]
    ro = RolloutController(pool, v2_backend, to_version="v2",
                           canary_set=canary, drain_window_s=0.0)
    ro.start()
    routable = []

    def tick(i):
        ro.tick()
        routable.append(sum(r.can_route() for r in pool))

    sched = MicroBatchScheduler(EDGES, 4, clock=clock,
                                pool=pool, telemetry=tel, max_queue=64,
                                default_deadline=0.02)
    results = replay(sched, clock, arrivals, reqs, on_arrival=tick)
    assert _drive(ro, clock) == "done"
    assert {r.version for r in pool} == {"v2"}
    assert len(ro.upgraded) == 2 and ro.rollbacks == 0
    # Traffic really overlapped the swap, and the pool never went dark.
    assert min(routable) >= 1 and 1 in routable
    c = tel.snapshot()["counters"]
    assert int(c["admitted"]) == n == int(c["requests_ok"])
    for rid, r in results.items():
        assert r.text == solo_decode(v1[0], reqs[int(rid[1:])])
    assert obs_lint(tel) == []
