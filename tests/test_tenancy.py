"""Multi-model multi-tenant gateway: registry + admission contracts.

Covers the ISSUE-11 tentpole and satellites: ModelRegistry /
ModelGroup validation (duplicate models, cross-group replica-id
clashes, default resolution), GroupState as the shared controller
surface (breaker-opens scan, cooldown hold-out, attach/detach
probes), AdmissionController quotas / priority-class defaults /
staged brownout shed / weighted-fair dequeue, the scheduler's
model+tenant threading (model-homogeneous batches, quota charge and
release around the full request lifecycle), the streaming router's
per-session quota, and the ``set_max_queue`` shrink racing an
in-flight submit over a quota-subdivided queue.

Everything rides an injectable virtual clock and echo decode
backends — no model, no device, deterministic.
"""

import io
import json

import numpy as np
import pytest

from deepspeech_tpu.resilience import CircuitBreaker
from deepspeech_tpu.resilience.brownout import BrownoutController
from deepspeech_tpu.serving import (AdmissionController, GroupState,
                                    MicroBatchScheduler, ModelGroup,
                                    ModelRegistry, OverloadRejected,
                                    PooledSessionRouter, Replica,
                                    ReplicaPool, ServingTelemetry,
                                    TenantConfig, TenantQuotaExceeded)
from deepspeech_tpu.serving.tenancy import (CLASS_DEADLINES,
                                            PRIORITY_BATCH,
                                            PRIORITY_REALTIME)

EDGES = (16, 32)
NF = 8


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _feat(n=8):
    return np.zeros((n, NF), np.float32)


def _echo(tag):
    def fn(batch, plan):
        return [f"{tag}"] * plan.n_valid
    return fn


def _replica(rid, tag, tel, clock, **kw):
    return Replica(rid, _echo(tag), telemetry=tel, clock=clock,
                   breaker=CircuitBreaker(name=f"b_{rid}",
                                          failure_threshold=2,
                                          cooldown_s=1.0, clock=clock,
                                          registry=tel), **kw)


def _registry(clock, tel, models=("a", "b"), n=2):
    reg = ModelRegistry()
    for mid in models:
        pool = ReplicaPool(
            [_replica(f"{mid}-r{k}", mid, tel, clock)
             for k in range(n)],
            clock=clock, telemetry=tel)
        reg.add_group(mid, pool)
    return reg


def _tenancy(**quotas):
    cfgs = {
        "gold": TenantConfig("gold", quota=quotas.get("gold", 4),
                             priority="realtime", weight=2.0),
        "silver": TenantConfig("silver", quota=quotas.get("silver", 4),
                               priority="standard"),
        "bulk": TenantConfig("bulk", quota=quotas.get("bulk", 8),
                             priority="batch", weight=0.5),
    }
    return AdmissionController(cfgs.values())


# -- TenantConfig / AdmissionController ----------------------------------

def test_tenant_config_validation():
    with pytest.raises(ValueError):
        TenantConfig("")
    with pytest.raises(ValueError):
        TenantConfig("x", quota=0)
    with pytest.raises(ValueError):
        TenantConfig("x", priority="vip")
    with pytest.raises(ValueError):
        TenantConfig("x", weight=0.0)
    with pytest.raises(ValueError):
        AdmissionController([])
    with pytest.raises(ValueError):
        AdmissionController([TenantConfig("x"), TenantConfig("x")])


def test_quota_charge_release_and_peak():
    ten = AdmissionController([TenantConfig("acme", quota=2)])
    ten.charge("acme")
    ten.charge("acme")
    with pytest.raises(TenantQuotaExceeded) as ei:
        ten.charge("acme")
    # The subclassing contract: every existing shed path catches it.
    assert isinstance(ei.value, OverloadRejected)
    assert ten.inflight("acme") == 2 and ten.peak("acme") == 2
    ten.release("acme")
    assert ten.inflight("acme") == 1
    ten.charge("acme")                   # back under quota: admitted
    assert ten.peak("acme") == 2
    # Release never goes negative, unknown tenants are inert.
    for _ in range(5):
        ten.release("acme")
        ten.release("ghost")
    assert ten.inflight("acme") == 0
    st = ten.stats()["tenants"]["acme"]
    assert st["rejected"] == 1 and st["served"] == 3
    with pytest.raises(KeyError):
        ten.charge("ghost")              # typos must not ride free


def test_priority_class_defaults_and_shed_staging():
    ten = _tenancy()
    assert ten.default_deadline("gold") == \
        CLASS_DEADLINES[PRIORITY_REALTIME]
    assert ten.default_deadline("bulk") == \
        CLASS_DEADLINES[PRIORITY_BATCH]
    # Explicit per-tenant overrides beat the class default.
    ten2 = AdmissionController([
        TenantConfig("t", deadline=0.123, tier="bulk")])
    assert ten2.default_deadline("t") == 0.123
    assert ten2.default_tier("t") == "bulk"
    # The staged shed order: batch first, standard at 2, realtime never.
    assert not ten.sheds_at("bulk", 0)
    assert ten.sheds_at("bulk", 1) and ten.sheds_at("bulk", 2)
    assert not ten.sheds_at("silver", 1)
    assert ten.sheds_at("silver", 2)
    assert not ten.sheds_at("gold", 3)


def test_from_file_shapes(tmp_path):
    p = tmp_path / "tenants.json"
    p.write_text(json.dumps({"tenants": [
        {"tenant": "acme", "quota": 8, "priority": "realtime",
         "weight": 2.0}]}))
    ten = AdmissionController.from_file(str(p))
    assert ten.tenants() == ["acme"] and ten.weight("acme") == 2.0
    p.write_text(json.dumps([{"tenant": "solo"}]))   # bare list
    assert AdmissionController.from_file(str(p)).tenants() == ["solo"]
    p.write_text(json.dumps({"tenants": "nope"}))
    with pytest.raises(ValueError):
        AdmissionController.from_file(str(p))


class _Req:
    def __init__(self, tenant, n):
        self.tenant = tenant
        self.n = n

    def __repr__(self):
        return f"{self.tenant}:{self.n}"


def test_fair_select_weighted_stride():
    ten = AdmissionController([
        TenantConfig("heavy", weight=2.0),
        TenantConfig("light", weight=1.0),
    ])
    reqs = [_Req("heavy", i) for i in range(6)] + \
        [_Req("light", i) for i in range(6)]
    took = ten.fair_select(reqs, 6)
    # 2:1 stride — heavy gets ~2 of every 3 slots, FIFO per tenant.
    assert sum(1 for r in took if r.tenant == "heavy") == 4
    assert [r.n for r in took if r.tenant == "heavy"] == [0, 1, 2, 3]
    assert [r.n for r in took if r.tenant == "light"] == [0, 1]


def test_fair_select_idle_tenant_reenters_at_floor():
    ten = AdmissionController([
        TenantConfig("busy"), TenantConfig("idle")])
    # busy alone for a while: its virtual time runs ahead.
    for _ in range(4):
        ten.fair_select([_Req("busy", 0), _Req("busy", 1)], 1)
    # idle arrives: it enters at busy's floor, not vt=0 — it may win
    # ties but must not monopolize the whole flush on stale credit.
    reqs = [_Req("busy", i) for i in range(4)] + \
        [_Req("idle", i) for i in range(4)]
    took = ten.fair_select(reqs, 4)
    assert sum(1 for r in took if r.tenant == "idle") == 2
    assert sum(1 for r in took if r.tenant == "busy") == 2


def test_fair_select_everything_goes_still_advances():
    ten = AdmissionController([
        TenantConfig("a", weight=1.0), TenantConfig("b", weight=1.0)])
    ten.fair_select([_Req("b", 0)], 8)           # b served once: vt=1
    all_a = [_Req("a", i) for i in range(4)]
    assert ten.fair_select(all_a, 8) == all_a    # n >= len: passthrough
    # The passthrough path still advanced a's clock (vt=4 vs b's 1),
    # so the next contended flush favors b. Without the advance a
    # would win the tie at vt=0.
    took = ten.fair_select(
        [_Req("a", 0), _Req("b", 0), _Req("b", 1)], 2)
    assert [r.tenant for r in took] == ["b", "b"]


# -- ModelRegistry / ModelGroup ------------------------------------------

def test_registry_registration_and_resolve():
    clock = Clock()
    tel = ServingTelemetry()
    reg = _registry(clock, tel)
    assert len(reg) == 2 and "a" in reg and "c" not in reg
    assert reg.models() == ["a", "b"]
    assert reg.resolve(None) == "a"          # first registered wins
    assert reg.resolve("b") == "b"
    with pytest.raises(KeyError):
        reg.resolve("typo")
    # Replicas are tagged with their group's model id (labels carry it).
    for g in reg:
        for rep in g.pool.replicas:
            assert rep.model == g.model_id
            assert rep.labels["model"] == g.model_id


def test_registry_rejects_duplicates_and_rid_clashes():
    clock = Clock()
    tel = ServingTelemetry()
    reg = _registry(clock, tel, models=("a",))
    dup_pool = ReplicaPool([_replica("x0", "a", tel, clock)],
                           clock=clock, telemetry=tel)
    with pytest.raises(ValueError):
        reg.add_group("a", dup_pool)         # duplicate model id
    clash = ReplicaPool([_replica("a-r0", "b", tel, clock)],
                        clock=clock, telemetry=tel)
    with pytest.raises(ValueError):
        reg.add_group("b", clash)            # rid owned by group "a"
    # A replica already tagged for another model can't be re-tagged.
    foreign = _replica("z9", "z", tel, clock)
    foreign.model = "other"
    with pytest.raises(ValueError):
        ModelGroup("mine", ReplicaPool([foreign], clock=clock,
                                       telemetry=tel))


def test_model_group_ladder_overrides():
    clock = Clock()
    tel = ServingTelemetry()
    pool = ReplicaPool([_replica("m-r0", "m", tel, clock)],
                       clock=clock, telemetry=tel)
    g = ModelGroup("m", pool, bucket_frames=(8, 64), max_batch=2,
                   tier_max_batch={"bulk": 6})
    assert g.bucket_frames == (8, 64)
    with pytest.raises(ValueError):
        ModelGroup("m2", pool, max_batch=0)
    reg = ModelRegistry()
    reg.register(g)
    sched = MicroBatchScheduler(EDGES, 4, clock=clock, telemetry=tel,
                                registry=reg)
    # The group's own ladder picks the rung, not the scheduler edges.
    sched.submit(_feat(6), model="m")
    assert list(sched._pending[("m", "")].keys()) == [8]
    # The group's max_batch caps the flush.
    assert sched._cap(None, "m") == 2
    assert sched._cap("bulk", "m") == 6


# -- GroupState ----------------------------------------------------------

def test_group_state_breaker_scan_reports_each_open_once():
    clock = Clock()
    tel = ServingTelemetry()
    rep = _replica("r0", "x", tel, clock)
    gs = GroupState()
    gs.note_replica(rep)
    rep.breaker.record_failure()
    rep.breaker.record_failure()         # threshold 2 -> open
    assert [r.rid for r in gs.newly_opened([rep])] == ["r0"]
    assert gs.newly_opened([rep]) == []  # reported exactly once
    gs.forget_replica("r0")
    gs.note_replica(rep)                 # re-join mid-life: no replay
    assert gs.newly_opened([rep]) == []


def test_group_state_cooldown_reason_and_skip():
    clock = Clock()
    tel = ServingTelemetry()
    rep = _replica("r0", "x", tel, clock)
    gs = GroupState()
    rep.breaker.record_failure()
    rep.breaker.record_failure()
    assert gs.breaker_cooldown_reason([rep], clock()) == \
        "breaker_open_r0"
    # The caller's own victim is skippable; cooldown expiry clears it.
    assert gs.breaker_cooldown_reason([rep], clock(), skip=(rep,)) \
        is None
    clock.t += 2.0
    assert gs.breaker_cooldown_reason([rep], clock.t) is None


def test_group_state_holdoff_probes():
    gs = GroupState()
    reasons = {"rollout": None, "autoscale": None}
    gs.attach("rollout", lambda: reasons["rollout"])
    gs.attach("autoscale", lambda: reasons["autoscale"])
    assert gs.holdoff_reason() is None
    reasons["rollout"] = "rollout_running"
    assert gs.holdoff_reason() == "rollout_running"
    # A controller never holds itself off.
    assert gs.holdoff_reason(exclude=("rollout",)) is None
    reasons["autoscale"] = "autoscale_drain_r1"
    assert gs.holdoff_reason(exclude=("rollout",)) == \
        "autoscale_drain_r1"
    gs.detach("autoscale")
    assert gs.holdoff_reason(exclude=("rollout",)) is None


def test_pool_owns_group_state_and_controllers_attach():
    """The pool's GroupState is the shared surface: rollout and
    autoscale register hold-off probes on it at construction."""
    clock = Clock()
    tel = ServingTelemetry()
    pool = ReplicaPool([_replica(f"r{k}", "x", tel, clock)
                        for k in range(3)],
                       clock=clock, telemetry=tel)
    assert isinstance(pool.group, GroupState)
    from deepspeech_tpu.serving.autoscale import AutoscaleController
    from deepspeech_tpu.serving.rollout import RolloutController

    ro = RolloutController(pool, lambda rep: {"decode_fn": _echo("v2")},
                           to_version="v2", clock=clock, telemetry=tel)
    auto = AutoscaleController(
        pool, lambda rid: _replica(rid, "x", tel, clock),
        min_replicas=1, max_replicas=4, clock=clock, telemetry=tel)
    del ro, auto
    # Both probes live on the shared state; neither fires while idle.
    assert set(pool.group._probes) >= {"rollout", "autoscale"}
    assert pool.group.holdoff_reason() is None


# -- scheduler integration -----------------------------------------------

def _sched(clock, tel, reg=None, ten=None, **kw):
    return MicroBatchScheduler(EDGES, 4, max_queue=16,
                               default_deadline=0.05, clock=clock,
                               telemetry=tel, registry=reg,
                               tenancy=ten, **kw)


def test_scheduler_batches_stay_model_homogeneous():
    clock = Clock()
    tel = ServingTelemetry()
    reg = _registry(clock, tel)
    sched = _sched(clock, tel, reg=reg)
    rids = {}
    for i in range(6):                    # interleave a/b on one rung
        mid = ("a", "b")[i % 2]
        rids[sched.submit(_feat(8), model=mid)] = mid
    results = sched.drain()
    assert set(results) == set(rids)
    # The echo backend stamps its model id: any cross-model mixing
    # would have decoded rows under the wrong group's tag.
    for rid, mid in rids.items():
        assert results[rid].status == "ok"
        assert results[rid].text == mid


def test_scheduler_rejects_unknown_model_and_bare_tenant():
    clock = Clock()
    tel = ServingTelemetry()
    reg = _registry(clock, tel)
    sched = _sched(clock, tel, reg=reg, ten=_tenancy())
    with pytest.raises(KeyError):
        sched.submit(_feat(), model="typo")
    with pytest.raises(KeyError):
        sched.submit(_feat(), tenant="ghost")
    # Tenant without model on a registry-less plane: the fairness
    # lint's contract is enforced at submit.
    bare = MicroBatchScheduler(EDGES, 4, clock=clock,
                               telemetry=ServingTelemetry(),
                               tenancy=_tenancy())
    with pytest.raises(ValueError):
        bare.submit(_feat(), tenant="gold")
    with pytest.raises(ValueError):
        _sched(clock, tel, reg=reg, pool=reg.group("a").pool)


def test_scheduler_quota_lifecycle_and_labeled_slo():
    clock = Clock()
    tel = ServingTelemetry()
    reg = _registry(clock, tel)
    ten = _tenancy(gold=2)
    sched = _sched(clock, tel, reg=reg, ten=ten)
    r0 = sched.submit(_feat(), model="a", tenant="gold")
    r1 = sched.submit(_feat(), model="a", tenant="gold")
    with pytest.raises(TenantQuotaExceeded):
        sched.submit(_feat(), model="a", tenant="gold")
    assert ten.inflight("gold") == 2
    results = sched.drain()
    # Terminal results release the quota: the tenant can submit again.
    assert ten.inflight("gold") == 0 and ten.peak("gold") == 2
    assert results[r0].status == "ok" and results[r1].status == "ok"
    sched.submit(_feat(), model="a", tenant="gold")
    sched.drain()
    # The SLO series carry both labels (the fairness-lint contract)
    # and the snapshot passes the real schema lint.
    c = tel.snapshot()["counters"]
    assert any(k.startswith("slo_ok{") and 'tenant="gold"' in k
               and 'model="a"' in k for k in c)
    assert c['tenant_quota_rejected{model="a",tenant="gold"}'] == 1
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import check_obs_schema

    buf = io.StringIO()
    tel.emit_jsonl(buf)
    assert check_obs_schema.scan(buf.getvalue().splitlines()) == []


def test_scheduler_tenant_defaults_thread_through():
    clock = Clock()
    tel = ServingTelemetry()
    reg = _registry(clock, tel)
    ten = AdmissionController([
        TenantConfig("t", quota=4, priority="realtime")])
    sched = _sched(clock, tel, reg=reg, ten=ten)
    sched.submit(_feat(), model="a", tenant="t")
    ((qkey, rungs),) = sched._pending.items()
    ((_, (req,)),) = rungs.items()
    assert qkey == ("a", "")
    assert req.deadline == pytest.approx(
        CLASS_DEADLINES[PRIORITY_REALTIME])
    sched.drain()


def test_scheduler_staged_brownout_shed_order():
    clock = Clock()
    tel = ServingTelemetry()
    reg = _registry(clock, tel)
    ten = _tenancy(gold=8, silver=8, bulk=16)
    bro = BrownoutController(enter_pressure=0.5, exit_pressure=0.0,
                             shed_pressure=0.75, hold_s=0.0,
                             clock=clock, registry=tel)
    sched = _sched(clock, tel, reg=reg, ten=ten, brownout=bro)
    for _ in range(8):                    # fill up to enter (8/16)
        sched.submit(_feat(), model="a", tenant="bulk")
    with pytest.raises(OverloadRejected):  # batch sheds at level 1
        sched.submit(_feat(), model="a", tenant="bulk")
    assert bro.level >= 1
    sid = sched.submit(_feat(), model="b", tenant="silver")
    for _ in range(3):                     # push to shed (12/16)
        sched.submit(_feat(), model="a", tenant="gold")
    with pytest.raises(OverloadRejected):  # standard sheds at level 2
        sched.submit(_feat(), model="b", tenant="silver")
    assert bro.level >= 2
    gid = sched.submit(_feat(), model="a", tenant="gold")  # realtime: in
    results = sched.drain()
    assert results[sid].status == "ok" and results[gid].status == "ok"
    assert all(ten.inflight(t) == 0 for t in ("gold", "silver", "bulk"))


def test_scheduler_contended_rung_is_weighted_fair():
    """A rung holding more eligible requests than one flush takes is
    dequeued by stride scheduling — the saturating bulk tenant cannot
    starve gold out of its own rung."""
    clock = Clock()
    tel = ServingTelemetry()
    reg = _registry(clock, tel, models=("a",))
    ten = _tenancy(gold=8, bulk=16)
    sched = _sched(clock, tel, reg=reg, ten=ten)
    bulk_rids = [sched.submit(_feat(8), model="a", tenant="bulk")
                 for _ in range(8)]
    gold_rids = [sched.submit(_feat(8), model="a", tenant="gold")
                 for _ in range(4)]
    del bulk_rids
    mbs = sched.poll()                    # rung-full: caps of 4
    first = [r.tenant for r in mbs[0].requests]
    # gold (weight 2) vs bulk (weight .5): gold wins 3 of the first 4
    # slots despite 8 bulk requests queued ahead of it.
    assert first.count("gold") >= 3
    sched.dispatch_many(mbs)
    sched.drain()
    assert all(sched.results[r].status == "ok" for r in gold_rids)


def test_set_max_queue_shrink_races_inflight_submit():
    """ISSUE-11 satellite: an autoscaler shrinking ``max_queue`` from
    a clock read INSIDE a tenant submit (the narrowest interleave the
    synchronous design allows) must never cut capacity below the
    already-admitted backlog, and the racing submit itself must shed
    cleanly without leaking its tenant's quota."""
    tel = ServingTelemetry()
    reg_clock = Clock()
    sched_box = {}
    fire = {"arm": False, "applied": None}

    def clock():
        if fire["arm"]:
            fire["arm"] = False          # exactly once, mid-submit
            fire["applied"] = sched_box["s"].set_max_queue(2)
        return reg_clock()

    reg = _registry(clock, tel, models=("a",))
    ten = _tenancy(gold=8, bulk=8)
    sched = MicroBatchScheduler(EDGES, 4, max_queue=16,
                                default_deadline=0.05, clock=clock,
                                telemetry=tel, registry=reg,
                                tenancy=ten)
    sched_box["s"] = sched
    # Quota-subdivided backlog: two tenants share the queue.
    for _ in range(3):
        sched.submit(_feat(), model="a", tenant="bulk")
    for _ in range(3):
        sched.submit(_feat(), model="a", tenant="gold")
    assert sched.pending == 6
    fire["arm"] = True
    # The racing submit reads the clock AFTER admission bookkeeping
    # starts; the shrink lands mid-submit. Capacity is clamped to the
    # backlog (6, not 2), so this submit sheds on the now-full queue —
    # before its quota charge, so nothing leaks.
    with pytest.raises(OverloadRejected):
        sched.submit(_feat(), model="a", tenant="bulk")
    assert fire["applied"] == 6
    assert sched.max_queue == 6
    assert ten.inflight("bulk") == 3      # the shed didn't charge
    results = sched.drain()               # backlog drains clean
    assert len(results) == 6
    assert all(r.status == "ok" for r in results.values())
    assert ten.inflight("bulk") == 0 and ten.inflight("gold") == 0
    # With the backlog retired the shrink target is reachable.
    assert sched.set_max_queue(2) == 2


# -- streaming router ----------------------------------------------------

class _FakeMgr:
    """Duck-typed StreamingSessionManager good enough for routing."""

    def __init__(self, log):
        self.log = log
        self._text = {}

    def join(self, sid):
        self._text[sid] = []

    def feed(self, sid, chunk):
        self._text[sid].append("p")
        return "p"

    def step(self, chunks):
        out = {}
        for sid, chunk in chunks.items():
            if sid in self._text:
                self._text[sid].append("p")
                out[sid] = "p"
        return out

    def flush(self):
        return {}

    def leave(self, sid, tail=None):
        pass

    def final(self, sid):
        return " ".join(self._text.pop(sid))

    def stats(self):
        return {"active": len(self._text), "draining": 0}


def test_router_charges_session_quota_per_join():
    clock = Clock()
    tel = ServingTelemetry()
    log = []
    reg = ModelRegistry()
    for mid in ("a", "b"):
        pool = ReplicaPool(
            [Replica(f"{mid}-r{k}", _echo(mid), telemetry=tel,
                     clock=clock,
                     session_factory=lambda: _FakeMgr(log))
             for k in range(2)],
            clock=clock, telemetry=tel)
        reg.add_group(mid, pool)
    ten = AdmissionController([TenantConfig("acme", quota=1)])
    router = PooledSessionRouter(registry=reg, tenancy=ten)
    home = router.join("s1", model="b", tenant="acme")
    assert home.startswith("b-")
    with pytest.raises(TenantQuotaExceeded):
        router.join("s2", model="a", tenant="acme")
    assert ten.inflight("acme") == 1
    router.step({"s1": np.zeros((4, NF), np.float32)})
    router.leave("s1")
    router.flush()
    assert router.final("s1") == "p"
    assert ten.inflight("acme") == 0      # released at leave
    router.join("s3", model="a", tenant="acme")   # re-admitted
    router.leave("s3")


# -- scenario: three tenants, two models, one plane -----------------------

def test_scenario_shared_plane_isolates_models_and_tenants(obs_lint):
    """gold (realtime), silver (standard) and bulk (batch) share two
    model groups whose decoders stamp their model id on every
    transcript, through a steady mix, a quota flood and a brownout
    flood on the scripted clock. No micro-batch mixed models and every
    transcript is its own model's; the flooding tenant peaks at exactly
    its quota with the overflow rejected, and every tenant's inflight
    returns to 0; under brownout batch sheds first, standard only at
    level 2, realtime never and always inside its deadline; the plane
    recovers; the model+tenant labeled telemetry lints clean."""
    from deepspeech_tpu.resilience.brownout import BrownoutController
    from deepspeech_tpu.serving import OverloadRejected

    quotas = {"gold": 6, "silver": 8, "bulk": 12}
    clock = Clock()
    tel = ServingTelemetry()
    batches, model_of, expected = [], {}, {}

    def decoder(mid):
        def fn(batch, plan):
            uids = [int(batch["features"][i].sum())
                    for i in range(plan.n_valid)]
            batches.append((mid, uids))
            return [f"{mid}:{u}" for u in uids]
        return fn

    reg = ModelRegistry()
    for mid in ("a", "b"):
        reg.add_group(mid, ReplicaPool(
            [Replica(f"{mid}-r{k}", decoder(mid), telemetry=tel,
                     clock=clock) for k in range(2)],
            clock=clock, telemetry=tel))
    ten = _tenancy(**quotas)
    bro = BrownoutController(enter_pressure=0.75, exit_pressure=0.0,
                             shed_pressure=0.9, hold_s=0.0, clock=clock,
                             registry=tel)
    sched = MicroBatchScheduler(EDGES, 4, max_queue=24,
                                default_deadline=0.05, clock=clock,
                                telemetry=tel, registry=reg, tenancy=ten,
                                brownout=bro)
    rng = np.random.default_rng(7)

    def submit(tenant, model, shed):
        uid = len(model_of) + 1
        model_of[uid] = model
        feat = _feat(int(rng.integers(4, max(EDGES), endpoint=True)))
        feat[0, 0] = uid
        clock.t += 0.0005
        try:
            rid = sched.submit(feat, model=model, tenant=tenant)
        except TenantQuotaExceeded:
            return shed.append((tenant, "quota"))
        except OverloadRejected:
            return shed.append((tenant, "brownout"))
        expected[rid] = f"{model}:{uid}"
        return rid

    steady = []
    cycle = [("gold", "a"), ("silver", "b"), ("bulk", "a"),
             ("gold", "a"), ("silver", "b"), ("bulk", "b")]
    for k in range(24):
        submit(*cycle[k % 6], steady)
        clock.t += 0.0015
        sched.pump()
    sched.drain()
    assert steady == [] and sched.pending == 0

    over = []
    admitted = sum(submit("bulk", "ab"[k % 2], over) is not None
                   for k in range(20))
    assert admitted == ten.peak("bulk") == quotas["bulk"]
    assert over == [("bulk", "quota")] * (20 - quotas["bulk"])
    sched.drain()

    flood = []
    for k in range(quotas["bulk"]):
        submit("bulk", "ab"[k % 2], flood)
    for k in range(quotas["silver"]):      # fill passes enter_pressure
        submit("silver", "b", flood)
    assert bro.level >= 1
    for k in range(4):                     # batch sheds at level 1
        submit("bulk", "a", flood)
    gold = [submit("gold", "a", flood) for _ in range(2)]
    submit("silver", "b", flood)           # fill passes shed_pressure
    assert bro.level >= 2
    gold.append(submit("gold", "a", flood))
    first = {}
    for k, (tenant, _) in enumerate(flood):
        first.setdefault(tenant, k)
    assert first["bulk"] < first["silver"] and "gold" not in first
    assert all(rid is not None for rid in gold)
    sched.drain()

    for _ in range(4):
        bro.update(0.0, now=clock.t)
        clock.t += 0.001
    assert bro.level == 0
    assert submit("bulk", "a", []) is not None
    sched.drain()

    assert set(sched.results) == set(expected)
    for rid, text in expected.items():
        assert sched.results[rid].status == "ok"
        assert sched.results[rid].text == text
    assert all(model_of[u] == mid for mid, uids in batches for u in uids)
    assert all(ten.peak(x) <= quotas[x] and ten.inflight(x) == 0
               for x in quotas)
    c = tel.snapshot()["counters"]
    assert not [k for k in c if k.startswith("slo_miss")
                and 'tenant="gold"' in k]
    assert sum(v for k, v in c.items() if k.startswith("slo_ok")
               and 'tenant="gold"' in k) == 8 + len(gold)
    assert obs_lint(tel) == []
