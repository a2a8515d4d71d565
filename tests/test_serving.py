"""Serving gateway: micro-batch scheduler + streaming session manager.

Covers the ISSUE-2 gateway contracts: flush rules (rung-full vs
oldest-deadline, free-slot fill), admission control under overload,
queue timeout and dispatch retry, bit-identity of gateway-batched vs
per-request decoding, session join/leave slot reuse (capacity grows
only when no slot is free), mid-flight join exactness, and the
time-decayed rung-usage eviction in ShapeBucketCache.

Scheduler tests use an injectable virtual clock, so every flush is
deterministic; model-backed tests reuse the tiny ds2_streaming config
from tests/test_serve.py's setup idiom.
"""

import numpy as np
import pytest

from deepspeech_tpu.data.infer_bucket import plan_infer_buckets
from deepspeech_tpu.serving import (MicroBatchScheduler, OverloadRejected,
                                    ServingTelemetry,
                                    StreamingSessionManager)
from deepspeech_tpu.serving.scheduler import warm_rung_chooser
from deepspeech_tpu.serving.telemetry import Histogram
from deepspeech_tpu.utils.cache import ShapeBucketCache

EDGES = (64, 128)
NF = 13


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _sched(clock, **kw):
    kw.setdefault("max_queue", 32)
    kw.setdefault("default_deadline", 1.0)
    return MicroBatchScheduler(EDGES, 4, clock=clock, **kw)


def _feat(n):
    return np.zeros((n, NF), np.float32)


def _echo_decode(batch, plan):
    """Texts encode the dispatched shape — enough to assert routing."""
    return [f"B{plan.batch_pad}T{plan.bucket_frames}"] * plan.n_valid


# -- scheduler flush rules ------------------------------------------------

def test_rung_full_flushes_immediately():
    clock = Clock()
    s = _sched(clock)
    for _ in range(3):
        s.submit(_feat(50))
    assert s.poll() == []          # 3 < max_batch, deadline far away
    s.submit(_feat(50))
    (mb,) = s.poll()
    assert mb.reason == "full" and mb.t_rung == 64 and mb.b_rung == 4
    assert s.pending == 0


def test_deadline_flushes_partial_batch():
    clock = Clock()
    s = _sched(clock)
    s.submit(_feat(50), deadline=0.5)
    assert s.poll() == []
    clock.t = 0.5
    (mb,) = s.poll()
    assert mb.reason == "deadline" and len(mb.requests) == 1
    assert mb.b_rung == 1          # partial flush pads to the B rung
    res = s.dispatch(mb, _echo_decode)
    assert res[0].status == "ok" and res[0].text == "B1T64"
    assert res[0].latency == pytest.approx(0.5)


def test_deadline_flush_fills_free_rows_from_smaller_rungs():
    clock = Clock()
    s = _sched(clock)
    # 3 long-rung requests hit their deadline; rows pad to b_rung=4,
    # so the one pending SHORT request (longer deadline) rides along —
    # free compute, less padding waste, less queueing.
    for _ in range(3):
        s.submit(_feat(100), deadline=0.1)
    s.submit(_feat(30), deadline=9.0)
    clock.t = 0.1
    (mb,) = s.poll()
    assert mb.reason == "deadline" and mb.t_rung == 128
    assert len(mb.requests) == 4 and mb.b_rung == 4
    assert {r.t_rung for r in mb.requests} == {128, 64}
    assert s.pending == 0
    # The filled short request decodes at the larger T rung but stays
    # a first-class row: all 4 get results.
    res = s.dispatch(mb, _echo_decode)
    assert [r.status for r in res] == ["ok"] * 4


def test_free_slot_fill_never_grows_the_batch_rung():
    clock = Clock()
    s = _sched(clock)
    for _ in range(4):
        s.submit(_feat(100), deadline=0.1)   # already a full rung
    s.submit(_feat(30), deadline=9.0)
    clock.t = 0.1
    batches = s.poll()
    # The long rung flushed full (no free rows); the short request
    # must NOT have been pulled in.
    assert batches[0].reason == "full" and len(batches[0].requests) == 4
    assert s.pending == 1


def test_admission_rejects_when_queue_full():
    clock = Clock()
    s = _sched(clock, max_queue=2)
    s.submit(_feat(50))
    s.submit(_feat(80))
    with pytest.raises(OverloadRejected):
        s.submit(_feat(50))
    assert s.telemetry.counter("rejected") == 1
    assert s.pending == 2          # shed load never entered the queue


def test_queue_timeout_fails_before_dispatch():
    clock = Clock()
    s = _sched(clock)
    rid = s.submit(_feat(50), deadline=9.0, timeout=0.2)
    clock.t = 0.3
    assert s.poll() == []          # expired, not flushed
    r = s.results[rid]
    assert r.status == "timeout" and r.attempts == 0


def test_dispatch_retries_then_succeeds():
    clock = Clock()
    s = _sched(clock, max_attempts=2)
    rid = s.submit(_feat(50), deadline=0.0)
    calls = []

    def flaky(batch, plan):
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("transient")
        return _echo_decode(batch, plan)

    res = s.drain(flaky)
    assert res[rid].status == "ok" and res[rid].attempts == 2
    assert s.telemetry.counter("retries") == 1


def test_dispatch_exhausts_attempts_to_error():
    clock = Clock()
    s = _sched(clock, max_attempts=2)
    rid = s.submit(_feat(50), deadline=0.0)

    def broken(batch, plan):
        raise RuntimeError("permanent")

    res = s.drain(broken)
    assert res[rid].status == "error" and res[rid].attempts == 2
    assert "permanent" in res[rid].error


def test_micro_batch_shapes_and_plan():
    clock = Clock()
    s = _sched(clock)
    s.submit(_feat(30))
    s.submit(_feat(50))
    clock.t = 1.0
    (mb,) = s.poll()
    b = mb.batch()
    assert b["features"].shape == (2, 64, NF)
    assert list(b["feat_lens"]) == [30, 50]
    p = mb.plan()
    assert (p.batch_pad, p.bucket_frames, p.n_valid) == (2, 64, 2)
    assert 0.0 < mb.padding_waste() < 1.0


def test_warm_rung_chooser_promotes_cold_rung():
    usage = {(4, 128): 3.0}
    choose = warm_rung_chooser(EDGES, lambda: usage, max_frames_over=1.5)
    assert choose(50) == 128       # 64 is cold, 128 warm and within 1.5x
    usage[(2, 64)] = 1.0
    assert choose(50) == 64        # exact rung is warm again
    choose_tight = warm_rung_chooser(EDGES, lambda: {(4, 128): 3.0},
                                     max_frames_over=0.5)
    assert choose_tight(50) == 64  # promotion too wasteful -> exact
    # The chooser plugs into the planner's rung_of hook.
    choose_warm128 = warm_rung_chooser(EDGES, lambda: {(4, 128): 3.0},
                                       max_frames_over=1.5)
    plans = plan_infer_buckets([50], EDGES, 4, rung_of=choose_warm128)
    assert plans[0].bucket_frames == 128


# -- telemetry ------------------------------------------------------------

def test_histogram_percentiles_and_reservoir_bound():
    h = Histogram(max_samples=64)
    for v in range(1000):
        h.observe(float(v))
    assert h.count == 1000 and len(h._samples) <= 64
    assert h.max == 999.0
    snap = h.snapshot()
    assert snap["p50"] == pytest.approx(500, abs=150)
    assert snap["p95"] == pytest.approx(950, abs=100)
    assert Histogram().snapshot()["p50"] is None


def test_telemetry_snapshot_roundtrip():
    t = ServingTelemetry()
    t.count("admitted", 3)
    t.gauge("queue_depth", 2)
    t.observe("latency_ok", 0.5)
    t.rung(4, 64)
    t.rung(4, 64)
    snap = t.snapshot()
    assert snap["counters"]["admitted"] == 3
    assert snap["per_rung"] == {"4x64": 2}
    assert t.rung_usage() == {(4, 64): 2}
    import io
    import json

    fh = io.StringIO()
    rec = t.emit_jsonl(fh, extra_field=1)
    assert json.loads(fh.getvalue()) == rec and rec["extra_field"] == 1


# -- ShapeBucketCache decayed eviction ------------------------------------

def test_shape_cache_decayed_eviction_keeps_compiles_cumulative():
    c = ShapeBucketCache(max_shapes=2, half_life=4)
    c.note(4, 64, 10)              # cold soon
    for _ in range(8):
        c.note(4, 128, 10)         # hot
    c.note(2, 64, 5)               # third shape -> evict coldest (4,64)
    assert c.evictions == 1
    assert (4, 64) not in c.rung_usage()
    assert set(c.rung_usage()) == {(4, 128), (2, 64)}
    # Eviction is ledger-side only: jit never un-compiles, so the
    # cumulative truths survive.
    assert c.compiles == 3
    assert c.note(4, 64, 10) is True   # still a HIT: executable is warm
    s = c.stats()
    assert s["evictions"] >= 1 and len(s["shapes"]) == 3
    assert set(s["live_shapes"]) == set(c.rung_usage())


def test_shape_cache_usage_decays_on_logical_clock():
    c = ShapeBucketCache(half_life=2)
    c.note(4, 64, 10)
    u0 = c.rung_usage()[(4, 64)]
    for _ in range(6):
        c.note(4, 128, 10)         # ticks pass; (4,64) untouched
    u1 = c.rung_usage()[(4, 64)]
    assert u1 < u0 / 4             # >= 2 half-lives elapsed


# -- gateway end-to-end: batched == per-request ---------------------------

@pytest.fixture(scope="module")
def tiny_infer(tiny_offline):
    return tiny_offline.cfg, tiny_offline.inferencer()


def test_gateway_batched_decode_bit_identical(tiny_infer):
    cfg, inf = tiny_infer
    rng = np.random.default_rng(1)
    lens = [30, 50, 90, 120, 40, 65]
    reqs = [rng.standard_normal((n, NF)).astype(np.float32) for n in lens]
    clock = Clock()
    s = MicroBatchScheduler(EDGES, 4, clock=clock, default_deadline=0.0)
    rids = [s.submit(f) for f in reqs]

    def decode_fn(batch, plan):
        return inf.decode_batch_bucketed(batch, plans=[plan])

    results = s.drain(decode_fn)
    assert all(results[r].status == "ok" for r in rids)
    for rid, f in zip(rids, reqs):
        solo = inf.decode_batch_bucketed({
            "features": f[None],
            "feat_lens": np.full((1,), len(f), np.int32)})[0]
        assert results[rid].text == solo


# -- session manager ------------------------------------------------------

def _mgr(tiny_streaming, **kw):
    cfg, tok, params, stats = tiny_streaming
    return StreamingSessionManager(cfg, params, stats, tok,
                                   chunk_frames=64, **kw)


def _chunks(f, k=64):
    n = f.shape[0] // k
    return [f[i * k:(i + 1) * k] for i in range(n)], f[n * k:]


def _solo_greedy(tiny_streaming, feat):
    """Reference transcript: offline streaming transcribe + greedy."""
    import jax.numpy as jnp

    from deepspeech_tpu.decode import greedy_decode, ids_to_texts
    from deepspeech_tpu.streaming import StreamingTranscriber

    cfg, tok, params, stats = tiny_streaming
    st = StreamingTranscriber(cfg, params, stats, tok, chunk_frames=64)
    logits, out_lens = st.transcribe(feat[None],
                                     np.asarray([feat.shape[0]]))
    ids, id_lens = greedy_decode(jnp.asarray(logits),
                                 jnp.asarray(out_lens))
    return ids_to_texts(ids, id_lens, tok)[0]


def test_session_slot_reuse_and_capacity_grow(tiny_streaming):
    mgr = _mgr(tiny_streaming, capacity=1)
    rng = np.random.default_rng(2)
    f = rng.standard_normal((64, NF)).astype(np.float32)
    assert mgr.join("a") == 0 and mgr.capacity == 1
    mgr.step({"a": f})
    # A second concurrent session outgrows capacity: rung doubles.
    assert mgr.join("b") == 1
    assert mgr.capacity == 2 and mgr.grows == 1
    mgr.step({"a": f, "b": f})
    # "a" leaves; the NEXT session reuses its slot — no new rung.
    mgr.leave("a")
    while "a" not in mgr._finals:
        mgr.step({"b": f})
    assert mgr.final("a") != None  # noqa: E711  (text may be "")
    assert mgr.join("c") == 0      # slot 0 reused
    assert mgr.capacity == 2 and mgr.grows == 1 and mgr.reuses == 1
    stats = mgr.stats()
    assert stats["slot_reuses"] == 1 and stats["capacity"] == 2


def test_session_join_midflight_is_bit_identical(tiny_streaming):
    """A session joining a running batch decodes exactly as if it had
    the batch to itself — the raw_start masking contract."""
    rng = np.random.default_rng(3)
    fa = rng.standard_normal((256, NF)).astype(np.float32)
    fb = rng.standard_normal((128, NF)).astype(np.float32)
    mgr = _mgr(tiny_streaming, capacity=2)
    mgr.join("a")
    ca, _ = _chunks(fa)
    cb, _ = _chunks(fb)
    mgr.step({"a": ca[0]})
    mgr.step({"a": ca[1]})
    mgr.join("b")                  # mid-flight: clock is 128, not 0
    mgr.step({"a": ca[2], "b": cb[0]})
    mgr.step({"a": ca[3], "b": cb[1]})
    mgr.leave("a")
    mgr.leave("b")
    mgr.flush()
    assert mgr.final("a") == _solo_greedy(tiny_streaming, fa)
    assert mgr.final("b") == _solo_greedy(tiny_streaming, fb)


def test_session_leave_with_tail_frames(tiny_streaming):
    rng = np.random.default_rng(4)
    f = rng.standard_normal((100, NF)).astype(np.float32)  # 64 + tail 36
    mgr = _mgr(tiny_streaming, capacity=1)
    mgr.join("a")
    chunks, tail = _chunks(f)
    parts = None
    for c in chunks:
        parts = mgr.step({"a": c})
    assert set(parts) == {"a"}
    mgr.leave("a", tail=tail)
    mgr.flush()
    assert mgr.final("a") == _solo_greedy(tiny_streaming, f)
    assert mgr.stats()["active"] == 0


def test_session_step_validates_active_set(tiny_streaming):
    mgr = _mgr(tiny_streaming, capacity=1)
    mgr.join("a")
    with pytest.raises(ValueError, match="active sessions"):
        mgr.step({})
    with pytest.raises(ValueError, match="already attached"):
        mgr.join("a")


# -- scheduler failure handling (deepspeech_tpu/resilience) ---------------

def test_expire_runs_on_poll_and_releases_admission_slots():
    """Regression: an IDLE gateway (no submits) must still fail
    timed-out requests on poll, AND expiry must release their
    admission slots — a queue of expired ghosts used to keep
    ``pending`` high enough to shed live traffic and hang drain."""
    clock = Clock()
    s = _sched(clock, max_queue=2)
    r1 = s.submit(_feat(50), deadline=9.0, timeout=0.2)
    r2 = s.submit(_feat(80), deadline=9.0, timeout=0.2)
    assert s.pending == 2
    clock.t = 0.5
    assert s.poll() == []                   # nothing dispatchable
    assert s.results[r1].status == "timeout"
    assert s.results[r2].status == "timeout"
    assert s.pending == 0                   # slots released
    # The freed slots admit new traffic (no ghost-queue shedding).
    s.submit(_feat(50))
    s.submit(_feat(80))
    assert s.pending == 2


def test_poison_request_is_quarantined_and_fails_alone():
    """One poison request in a batch of 4 must not keep killing its
    batchmates: after the first batch failure every member retries as
    a singleton, so the innocents succeed and the poison exhausts its
    own attempts."""
    clock = Clock()
    s = _sched(clock, max_attempts=2)
    good = [s.submit(_feat(50)) for _ in range(3)]
    poison = s.submit(_feat(51))            # rung-full flush of 4

    def decode(batch, plan):
        if 51 in list(batch["feat_lens"]):
            raise RuntimeError("poison row")
        return _echo_decode(batch, plan)

    res = s.drain(decode)
    assert s.telemetry.counter("quarantined") == 4
    assert res[poison].status == "error" and res[poison].attempts == 2
    for rid in good:
        assert res[rid].status == "ok" and res[rid].attempts == 2
        assert res[rid].text == "B1T64"     # retried as a singleton
    assert s.telemetry.counter("flush_quarantine") == 4


def test_open_breaker_defers_without_burning_attempts():
    from deepspeech_tpu.resilience import CircuitBreaker

    clock = Clock()
    breaker = CircuitBreaker(failure_threshold=1, cooldown_s=1.0,
                             clock=clock)
    s = _sched(clock, breaker=breaker, max_attempts=2)
    rid = s.submit(_feat(50), deadline=0.0)
    breaker.record_failure()                # backend known-bad: open
    (mb,) = s.poll()
    assert s.dispatch(mb, _echo_decode) == []   # deferred, not failed
    assert s.telemetry.counter("breaker_deferred") == 1
    assert s.pending == 1
    # The deferral burned NO attempts — the backend was at fault.
    clock.t = 1.0                           # cooldown over: probe admitted
    res = s.drain(_echo_decode)
    assert res[rid].status == "ok" and res[rid].attempts == 1
    assert breaker.state == "closed"


def test_dispatch_failures_trip_breaker_and_recovery_closes_it():
    from deepspeech_tpu.resilience import CircuitBreaker

    clock = Clock()
    breaker = CircuitBreaker(failure_threshold=2, cooldown_s=0.5,
                             clock=clock)
    s = _sched(clock, breaker=breaker, max_attempts=6)
    rid = s.submit(_feat(50), deadline=0.0)
    calls = []

    def flaky(batch, plan):
        calls.append(clock.t)
        if clock.t < 0.2:
            raise RuntimeError("outage")
        return _echo_decode(batch, plan)

    for mb in s.poll():
        s.dispatch(mb, flaky)               # failure 1 (closed)
    for mb in s.flush_all():
        s.dispatch(mb, flaky)               # failure 2 -> OPEN
    assert breaker.state == "open" and breaker.opens == 1
    for mb in s.flush_all():
        assert s.dispatch(mb, flaky) == []  # open: deferred, no decode
    assert len(calls) == 2
    clock.t = 0.6                           # past cooldown, outage over
    res = s.drain(flaky)
    assert res[rid].status == "ok"
    assert breaker.state == "closed" and breaker.recovery_s() > 0


def test_brownout_halves_flush_rung_and_sheds_admissions():
    from deepspeech_tpu.resilience import BrownoutController

    clock = Clock()
    tel = ServingTelemetry()
    brown = BrownoutController(enter_pressure=0.5, exit_pressure=0.1,
                               shed_pressure=0.9, hold_s=0.0,
                               clock=clock, registry=tel)
    s = _sched(clock, max_queue=8, brownout=brown, telemetry=tel)
    for _ in range(8):                      # pressure crosses 0.5 ...
        s.submit(_feat(50))
    assert brown.level >= 1                 # ... entering degraded
    batches = s.poll()                      # flush cap halved: 4 -> 2
    assert batches and all(len(mb.requests) == 2 for mb in batches)
    # Refill to brownout pressure: the next admission is shed.
    for _ in range(8):
        s.submit(_feat(50))
    with pytest.raises(OverloadRejected, match="brownout"):
        s.submit(_feat(50))
    assert s.telemetry.counter("brownout_shed") == 1
    assert s.telemetry.gauges["degraded"] == 2


def test_session_leave_with_inflight_tail_then_join_before_flush(
        tiny_streaming):
    """Fault path: a stream leaves (with tail frames still in flight)
    and a NEW stream joins the draining manager before the flush —
    the drain must not eat the newcomer's slot state, and both finals
    must stay exact."""
    rng = np.random.default_rng(5)
    fa = rng.standard_normal((100, NF)).astype(np.float32)  # 64 + tail
    fb = rng.standard_normal((128, NF)).astype(np.float32)
    mgr = _mgr(tiny_streaming, capacity=1)
    mgr.join("a")
    ca, tail = _chunks(fa)
    mgr.step({"a": ca[0]})
    mgr.leave("a", tail=tail)               # draining with in-flight tail
    cb, _ = _chunks(fb)
    mgr.join("b")                           # races the drain
    mgr.step({"b": cb[0]})
    mgr.step({"b": cb[1]})
    mgr.leave("b")
    mgr.flush()
    assert mgr.final("a") == _solo_greedy(tiny_streaming, fa)
    assert mgr.final("b") == _solo_greedy(tiny_streaming, fb)


def test_capacity_grow_racing_drain_keeps_streams_exact(tiny_streaming):
    """Fault path: a join forces a capacity grow while another session
    is mid-drain — the grow's state migration must not corrupt either
    the draining or the live stream."""
    rng = np.random.default_rng(6)
    fa = rng.standard_normal((128, NF)).astype(np.float32)
    fb = rng.standard_normal((192, NF)).astype(np.float32)
    mgr = _mgr(tiny_streaming, capacity=1)
    mgr.join("a")
    ca, _ = _chunks(fa)
    cb, _ = _chunks(fb)
    mgr.step({"a": ca[0]})
    mgr.step({"a": ca[1]})
    mgr.leave("a")                          # draining, slot still held
    mgr.join("b")                           # must GROW, not steal a's slot
    assert mgr.capacity == 2 and mgr.grows == 1
    for c in cb:
        mgr.step({"b": c})
    mgr.leave("b")
    mgr.flush()
    assert mgr.final("a") == _solo_greedy(tiny_streaming, fa)
    assert mgr.final("b") == _solo_greedy(tiny_streaming, fb)


def test_quarantined_request_writes_postmortem():
    """Serving-side quarantine feeds the same audit trail as the
    training-side one: one quarantined_request postmortem per isolated
    request, plus postmortems_written in the gateway telemetry."""
    import io

    from deepspeech_tpu.obs.metrics import MetricsRegistry
    from deepspeech_tpu.resilience import postmortem

    sink = io.StringIO()
    # Own registry: the writer must not double-count postmortems_written
    # into the scheduler's telemetry (which counts it separately).
    pm = postmortem.configure(sink=sink, registry=MetricsRegistry())
    try:
        clock = Clock()
        s = _sched(clock, max_attempts=2)
        good = [s.submit(_feat(50)) for _ in range(3)]
        poison = s.submit(_feat(51))

        def decode(batch, plan):
            if 51 in list(batch["feat_lens"]):
                raise RuntimeError("poison row")
            return _echo_decode(batch, plan)

        s.drain(decode)
        recs = pm.recent("quarantined_request")
        assert len(recs) == 4               # every batchmate isolated
        assert {r["trigger"] for r in recs} == {"batch_error"}
        assert {r["rung"] for r in recs} == {"4x64"}
        assert all("poison row" in r["error"] for r in recs)
        assert {r["rid"] for r in recs} == set(good) | {poison}
        assert s.telemetry.counter("postmortems_written") == 4
        lines = [l for l in sink.getvalue().splitlines() if l]
        assert len(lines) == 4
        import json as _json
        assert all(_json.loads(l)["event"] == "postmortem"
                   for l in lines)
    finally:
        postmortem.configure()              # restore the default writer


# -- quality tiers --------------------------------------------------------

def test_tier_queues_are_homogeneous_and_capped():
    """Per-tier pending queues: each tier flushes at its OWN ladder
    height (tier_max_batch), and a micro-batch never mixes tiers."""
    clock = Clock()
    s = _sched(clock, tier_max_batch={"premium": 2, "bulk": 4})
    s.submit(_feat(50), tier="premium")
    s.submit(_feat(50), tier="bulk")
    assert s.poll() == []              # neither tier at its cap
    s.submit(_feat(50), tier="premium")
    (mb,) = s.poll()                   # premium hits cap 2; bulk at 1/4
    assert mb.tier == "premium" and len(mb.requests) == 2
    assert all(r.tier == "premium" for r in mb.requests)
    for _ in range(3):
        s.submit(_feat(50), tier="bulk")
    (mb2,) = s.poll()                  # the taller int8 ladder: cap 4
    assert mb2.tier == "bulk" and len(mb2.requests) == 4
    assert all(r.tier == "bulk" for r in mb2.requests)
    assert s.pending == 0


def test_tier_free_slot_fill_never_crosses_tiers():
    """Deadline-flush free rows only donate within the SAME tier: a
    bulk (int8) request must never ride a premium (bf16) batch — that
    would silently upgrade it and break per-tier bit-identity."""
    clock = Clock()
    s = _sched(clock)
    for _ in range(3):
        s.submit(_feat(100), deadline=0.1, tier="premium")
    s.submit(_feat(30), deadline=9.0, tier="bulk")
    clock.t = 0.1
    (mb,) = s.poll()
    assert mb.reason == "deadline" and mb.tier == "premium"
    assert len(mb.requests) == 3       # bulk did NOT fill the free row
    assert s.pending == 1
    # Positive control: a SAME-tier short request does ride along.
    clock2 = Clock()
    s2 = _sched(clock2)
    for _ in range(3):
        s2.submit(_feat(100), deadline=0.1, tier="premium")
    s2.submit(_feat(30), deadline=9.0, tier="premium")
    clock2.t = 0.1
    (mb2,) = s2.poll()
    assert len(mb2.requests) == 4 and mb2.tier == "premium"


def test_tier_finish_metrics_and_slo_are_tier_labeled():
    """requests_*/latency_*/slo_* carry the tier label for tiered
    requests (and stay unlabeled for tierless — the all-or-nothing
    family rule tools/check_obs_schema.py lints)."""
    clock = Clock()
    s = _sched(clock, tier_max_batch={"bulk": 2})
    for _ in range(2):
        s.submit(_feat(50), deadline=0.5, tier="bulk")
    (mb,) = s.poll()
    clock.t = 0.2                      # dispatch inside the deadline
    s.dispatch(mb, _echo_decode)
    tel = s.telemetry
    assert tel.counter("requests_ok", labels={"tier": "bulk"}) == 2
    assert tel.counter("slo_ok", labels={"tier": "bulk"}) == 2
    assert tel.counter("requests_ok") == 0      # unlabeled twin absent
    # A deadline-flushed request dispatched LATE is an SLO miss even
    # though it completed ok.
    s.submit(_feat(50), deadline=0.2, tier="bulk")
    clock.t = 0.5                      # past its deadline, not timed out
    (mb2,) = s.poll()
    clock.t = 0.9
    s.dispatch(mb2, _echo_decode)
    assert tel.counter("slo_miss", labels={"tier": "bulk"}) == 1


def test_brownout_degrades_premium_to_bulk_and_restores():
    """The tier-degradation rung: at level >= DEGRADED new premium
    admissions are served as bulk (counted tier_degraded under the
    REQUESTED tier), and recover to premium once pressure exits."""
    from deepspeech_tpu.resilience import BrownoutController

    clock = Clock()
    tel = ServingTelemetry()
    brown = BrownoutController(enter_pressure=0.5, exit_pressure=0.1,
                               shed_pressure=0.95, hold_s=0.0,
                               clock=clock, registry=tel)
    s = _sched(clock, max_queue=8, brownout=brown, telemetry=tel,
               tier_max_batch={"premium": 4, "bulk": 4})
    for _ in range(4):                 # fill to enter_pressure
        s.submit(_feat(50), tier="premium")
    # submit() reads queue pressure BEFORE admitting, so the 4th
    # submit saw 3/8 — still normal.
    assert brown.level == 0
    # The 5th submit's update sees 4/8 = enter_pressure, trips the
    # level, and the same request is then admitted degraded to bulk.
    degraded_rid = s.submit(_feat(50), tier="premium")
    assert brown.level >= 1
    assert tel.counter("tier_degraded", labels={"tier": "premium"}) == 1
    batches = s.flush_all()
    by_tier = {mb.tier: mb for mb in batches}
    assert set(by_tier) == {"premium", "bulk"}
    assert [r.rid for r in by_tier["bulk"].requests] == [degraded_rid]
    s.dispatch_many(batches, _echo_decode)
    assert s.results[degraded_rid].status == "ok"
    assert s.pending == 0
    # Recovered: pressure is back under exit, premium stays premium.
    rid = s.submit(_feat(50), tier="premium")
    assert brown.level == 0
    clock.t += 10.0                    # deadline flush
    (mb,) = s.poll()
    assert mb.tier == "premium"
    s.dispatch(mb, _echo_decode)
    assert s.results[rid].status == "ok"
    assert tel.counter("tier_degraded", labels={"tier": "premium"}) == 1


def test_nbest_threads_through_dispatch_bit_identical():
    # decode_fn's optional (texts, nbest) form surfaces per-request
    # n-best on GatewayResult.nbest — the feed for the async rescoring
    # plane. Batched (rung-full) and solo (deadline) dispatch must hand
    # each request the same n-best, bit for bit: row->rid mapping is
    # positional and padding rows never leak.
    def decode(batch, plan):
        texts, nb = [], []
        for i in range(plan.n_valid):
            uid = int(batch["features"][i, 0, 0])
            nb.append([(f"top {uid}", 1.0 - 0.125 * uid),
                       (f"alt {uid}", 0.5 - 0.125 * uid)])
            texts.append(nb[-1][0][0])
        return texts, nb

    def uid_feat(uid):
        f = _feat(50)
        f[0, 0] = uid
        return f

    def run(batched):
        clock = Clock()
        s = _sched(clock)
        got = {}
        if batched:
            rids = [s.submit(uid_feat(uid)) for uid in range(4)]
            (mb,) = s.poll()
            s.dispatch(mb, decode)
            for uid, rid in enumerate(rids):
                got[uid] = s.results[rid]
        else:
            for uid in range(4):
                rid = s.submit(uid_feat(uid), deadline=0.5)
                clock.t += 0.5
                (mb,) = s.poll()
                s.dispatch(mb, decode)
                got[uid] = s.results[rid]
        return got

    batched, solo = run(True), run(False)
    for uid in range(4):
        assert batched[uid].status == "ok" and solo[uid].status == "ok"
        assert batched[uid].nbest == solo[uid].nbest
        assert batched[uid].text == batched[uid].nbest[0][0]
    # texts-only backends are untouched: no n-best, no behavior change.
    clock = Clock()
    s = _sched(clock)
    s.submit(_feat(50), deadline=0.1)
    clock.t = 0.1
    (mb,) = s.poll()
    (res,) = s.dispatch(mb, _echo_decode)
    assert res.status == "ok" and res.nbest is None


# -- scenario: a seeded replay through the gateway ------------------------

def test_scenario_traffic_replay_accounts_for_every_request(
        tiny_infer, obs_lint):
    """Seeded Poisson traffic through the scheduler into a real (tiny)
    engine, on a manual clock with a queue small enough to shed: every
    request ends in exactly one outcome, every finished one left a
    flight-recorder trace whose phases sum to its latency, batching
    never changed a transcript, and the telemetry snapshot (per-rung
    usage, occupancy, padding waste, a named latency exemplar) lints
    clean."""
    from scenario import (ManualClock, poisson_requests, replay,
                          solo_decode)
    from deepspeech_tpu.obs import FlightRecorder

    _, inf = tiny_infer
    n = 24
    arrivals, reqs = poisson_requests(n)
    clock = ManualClock()
    tel = ServingTelemetry()
    frec = FlightRecorder(capacity=4 * n)
    s = MicroBatchScheduler(EDGES, 4, clock=clock, max_queue=3,
                            default_deadline=0.02, telemetry=tel,
                            flight_recorder=frec)
    results = replay(
        s, clock, arrivals, reqs,
        lambda batch, plan: inf.decode_batch_bucketed(batch,
                                                      plans=[plan]))
    c = tel.snapshot()["counters"]
    done = int(c.get("requests_ok", 0))
    assert done + int(c.get("rejected", 0)) \
        + int(c.get("requests_timeout", 0)) \
        + int(c.get("requests_error", 0)) == n
    assert done == len(results) > 0 and int(c["rejected"]) > 0
    assert int(c["admitted"]) == done
    traces = {t["rid"]: t for t in frec.recent()}
    for rid, r in results.items():
        assert r.status == "ok"
        assert r.text == solo_decode(inf, reqs[int(rid[1:])])
        t = traces[rid]
        assert sum(t["phases"].values()) \
            == pytest.approx(t["latency_ms"], abs=1e-3)
        assert t["latency_ms"] == pytest.approx(r.latency * 1e3)
    snap = tel.snapshot()
    assert snap["per_rung"]
    assert 0 < snap["histograms"]["batch_occupancy"]["mean"] <= 1
    assert 0 <= snap["histograms"]["padding_waste"]["mean"] < 1
    assert snap["histograms"]["latency_ok"]["max_exemplar"] in results
    assert obs_lint(tel) == []
