"""Resilience layer (deepspeech_tpu/resilience): fault plans, unified
retry/backoff + circuit breaker, brownout control, checkpoint
partial-write fallback, preemption-safe (SIGTERM) training, and the
self-healing training guardian (guardrails, LR backoff, ring rollback,
corrupt-sample postmortems, stall watchdog).

Every time-dependent contract runs on injected clocks/sleeps, so the
whole module is deterministic and fast — except the SIGTERM resume
test, which deliberately uses a REAL signal through a real Trainer.fit
to pin the end-to-end bit-identical-resume guarantee.
"""

import dataclasses
import json
import os
import signal

import numpy as np
import pytest

from deepspeech_tpu import obs
from deepspeech_tpu.checkpoint import CheckpointManager
from deepspeech_tpu.obs.metrics import MetricsRegistry
from deepspeech_tpu.resilience import (BrownoutController, CircuitBreaker,
                                       CircuitOpen, FaultPlan, FaultSpec,
                                       GuardianConfig, GuardianHalt,
                                       InjectedFault, PostmortemWriter,
                                       PreemptionGuard, Retry, StallWatchdog,
                                       TrainingGuardian, faults,
                                       validate_plan_dict)
from deepspeech_tpu.resilience.faults import lint_plan_points


class Clock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


# -- fault plans ----------------------------------------------------------

def test_fault_spec_window_count_and_prob():
    clock = Clock()
    plan = FaultPlan(
        [FaultSpec("p", "error", after_s=1.0, until_s=2.0, count=1)],
        clock=clock).start()
    assert plan.check("p") is None          # before the window
    assert plan.check("other") is None      # wrong point
    clock.t = 1.5
    spec = plan.check("p")
    assert spec is not None and spec.kind == "error"
    assert plan.check("p") is None          # count=1 exhausted
    assert plan.fired() == 1
    # until_s is exclusive at the edge
    plan2 = FaultPlan([FaultSpec("p", "error", after_s=1.0, until_s=2.0)],
                      clock=clock).start()
    clock.t = 2.0
    assert plan2.check("p") is None


def test_fault_plan_prob_is_seed_deterministic():
    def fires(seed):
        clock = Clock()
        plan = FaultPlan([FaultSpec("p", "error", prob=0.5)],
                         seed=seed, clock=clock).start()
        return [plan.check("p") is not None for _ in range(32)]

    a, b = fires(7), fires(7)
    assert a == b                           # same seed -> same schedule
    assert any(a) and not all(a)            # prob actually thins
    assert fires(8) != a                    # seed matters


def test_inject_kinds_and_disabled_path():
    faults.clear()
    assert faults.inject("p") is None       # no plan: cheap no-op
    slept = []
    clock = Clock()
    plan = FaultPlan(
        [FaultSpec("err", "error", count=1),
         FaultSpec("out", "unavailable", count=1),
         FaultSpec("slow", "latency", latency_s=0.25, count=1),
         FaultSpec("torn", "partial_write", count=1)],
        clock=clock, sleep=slept.append)
    faults.install(plan)
    try:
        with pytest.raises(InjectedFault) as ei:
            faults.inject("err")
        assert ei.value.point == "err" and ei.value.kind == "error"
        # unavailable carries the UNAVAILABLE marker so the bench's
        # retryable-error classifier composes with injected outages.
        with pytest.raises(InjectedFault, match="UNAVAILABLE"):
            faults.inject("out")
        spec = faults.inject("slow")
        assert spec.kind == "latency" and slept == [0.25]
        spec = faults.inject("torn")        # returned, caller acts
        assert spec.kind == "partial_write"
        assert faults.active() is plan
    finally:
        faults.clear()
    assert faults.active() is None


def test_fault_counts_land_in_registry():
    from deepspeech_tpu.serving import ServingTelemetry

    reg = ServingTelemetry()
    clock = Clock()
    plan = FaultPlan([FaultSpec("p", "partial_write")],
                     clock=clock, registry=reg).start()
    plan.check("p")
    assert reg.counter("faults_injected",
                       labels={"point": "p",
                               "kind": "partial_write"}) == 1


def test_validate_plan_dict_catches_schema_violations():
    good = {"seed": 3, "faults": [
        {"point": "gateway.dispatch", "kind": "error", "prob": 0.5,
         "count": 2, "after_s": 0.1, "until_s": 0.2},
        {"point": "x", "kind": "latency", "latency_s": 0.01}]}
    assert validate_plan_dict(good) == []
    assert FaultPlan.from_dict(good).to_dict()["seed"] == 3

    def bad(problem_substr, obj):
        probs = validate_plan_dict(obj)
        assert any(problem_substr in p for p in probs), (problem_substr,
                                                         probs)

    bad("not an object", [1, 2])
    bad("unknown top-level key", {"faults": [], "oops": 1})
    bad("'seed' must be an integer", {"seed": True, "faults": []})
    bad("'faults'", {"seed": 0})
    bad("unknown key 'probz'",
        {"faults": [{"point": "p", "kind": "error", "probz": 1}]})
    bad("'kind'", {"faults": [{"point": "p", "kind": "bogus"}]})
    bad("'prob'", {"faults": [{"point": "p", "kind": "error",
                               "prob": 1.5}]})
    bad("'count'", {"faults": [{"point": "p", "kind": "error",
                                "count": 0}]})
    bad("'until_s' must be > 'after_s'",
        {"faults": [{"point": "p", "kind": "error", "after_s": 2.0,
                     "until_s": 1.0}]})
    bad("requires numeric 'latency_s'",
        {"faults": [{"point": "p", "kind": "latency"}]})
    with pytest.raises(ValueError, match="invalid fault plan"):
        FaultPlan.from_dict({"faults": [{"point": "p", "kind": "bogus"}]})


def test_fault_plan_json_roundtrip(tmp_path):
    import json

    p = tmp_path / "plan.json"
    p.write_text(json.dumps({"seed": 5, "faults": [
        {"point": "checkpoint.restore", "kind": "unavailable", "count": 2}]}))
    plan = FaultPlan.from_json(str(p))
    assert plan.seed == 5 and plan.specs[0].point == "checkpoint.restore"


def test_fault_spec_skip_gives_step_exact_schedule():
    """``skip`` consumes would-fire checks, so a plan can name exact
    batch ordinals (the train-chaos bench's scheduling primitive)."""
    clock = Clock()
    plan = FaultPlan([FaultSpec("p", "nan_grad", skip=3, count=2)],
                     clock=clock).start()
    hits = [plan.check("p") is not None for _ in range(8)]
    # skip=3, count=2: fires on exactly the 4th and 5th eligible checks.
    assert hits == [False, False, False, True, True, False, False, False]
    assert plan.fired() == 2
    # skip participates in the schema and the dict roundtrip.
    d = plan.to_dict()
    assert d["faults"][0]["skip"] == 3
    assert validate_plan_dict(d) == []
    probs = validate_plan_dict(
        {"faults": [{"point": "p", "kind": "error", "skip": -1}]})
    assert any("'skip'" in p for p in probs)


def test_lint_plan_points_flags_typos_and_inert_kinds():
    good = {"faults": [
        {"point": "train.step", "kind": "nan_grad", "skip": 10, "count": 2},
        {"point": "pipeline.materialize", "kind": "corrupt_batch"}]}
    assert lint_plan_points(good) == []
    warns = lint_plan_points({"faults": [
        {"point": "train.stpe", "kind": "error"},       # typo'd point
        {"point": "gateway.dispatch", "kind": "nan_grad"}]})  # inert kind
    assert len(warns) == 2
    assert "not wired" in warns[0]
    assert "nothing simulates" in warns[1]


# -- retry ---------------------------------------------------------------

def test_retry_backoff_sequence_and_success():
    import random

    slept = []
    r = Retry(attempts=4, base_s=1.0, multiplier=2.0, max_s=3.0,
              jitter=0.0, sleep=slept.append, rng=random.Random(0))
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("transient")
        return "ok"

    assert r.call(flaky) == "ok"
    assert slept == [1.0, 2.0]              # exp backoff, capped at max_s
    assert r.delay(5) == 3.0                # cap holds


def test_retry_exhausts_and_counts():
    from deepspeech_tpu.serving import ServingTelemetry

    reg = ServingTelemetry()
    slept = []
    r = Retry(attempts=3, base_s=0.1, jitter=0.0, sleep=slept.append,
              name="t", registry=reg)
    with pytest.raises(RuntimeError, match="permanent"):
        r.call(lambda: (_ for _ in ()).throw(RuntimeError("permanent")))
    assert len(slept) == 2                  # no sleep after the last try
    assert reg.counter("retry_attempts", labels={"name": "t"}) == 3
    assert reg.counter("retry_exhausted", labels={"name": "t"}) == 1


def test_retry_exhaustion_publishes_timeline_event():
    """Exhaustion is a fleet decision, not just a counter: the retry
    publishes one kind="retry_exhausted" timeline event carrying the
    policy name, the attempt count, and (when the caller set
    ``retry.replica``) the causal edge to that replica's last event —
    the ISSUE-20 hook the remote-handoff ladder leans on."""
    from deepspeech_tpu.obs import timeline as tl_mod
    from deepspeech_tpu.obs.timeline import EventLog

    log = tl_mod.install(EventLog())
    try:
        root = log.publish("remote_begin", "migration", replica="peerX",
                           sid="s0", transfer_id="t1", peer="peerX")
        r = Retry(attempts=2, base_s=0.1, jitter=0.0,
                  sleep=lambda s: None, name="handoff")
        r.replica = "peerX"
        with pytest.raises(RuntimeError, match="down"):
            r.call(lambda: (_ for _ in ()).throw(RuntimeError("down")))
    finally:
        tl_mod.clear()
    evs = [e for e in log.recent() if e["kind"] == "retry_exhausted"]
    assert len(evs) == 1
    ev = evs[0]
    assert ev["detail"]["name"] == "handoff"
    assert ev["detail"]["attempts"] == 2
    assert ev["detail"]["why"] == "attempts"
    assert ev["cause_seq"] == root              # edge to the begin event
    assert ev["replica"] == "peerX"


def test_retry_non_retryable_propagates_immediately():
    slept = []
    r = Retry(attempts=5, sleep=slept.append)
    calls = []

    def fatal():
        calls.append(1)
        raise ValueError("config error")

    with pytest.raises(ValueError):
        r.call(fatal, retryable=lambda e: isinstance(e, RuntimeError))
    assert len(calls) == 1 and slept == []


def test_retry_budget_caps_total_sleep():
    slept = []
    r = Retry(attempts=10, base_s=1.0, multiplier=1.0, jitter=0.0,
              budget_s=2.5, sleep=slept.append)
    with pytest.raises(RuntimeError):
        r.call(lambda: (_ for _ in ()).throw(RuntimeError("x")))
    assert slept == [1.0, 1.0]              # third sleep would blow 2.5s


def test_retry_jitter_stays_in_band():
    r = Retry(base_s=1.0, jitter=0.2)
    for k in range(1, 4):
        d = r.delay(k)
        lo = 1.0 * 2.0 ** (k - 1) * 0.8
        hi = min(1.0 * 2.0 ** (k - 1), 60.0) * 1.2
        assert lo <= d <= hi


# -- circuit breaker ------------------------------------------------------

def test_breaker_opens_after_threshold_and_recovers():
    clock = Clock()
    b = CircuitBreaker(failure_threshold=2, cooldown_s=5.0, clock=clock)
    assert b.allow()
    b.record_failure()
    assert b.state == "closed" and b.allow()  # one short of threshold
    b.record_failure()
    assert b.state == "open" and b.opens == 1
    assert not b.allow()                    # cooling down
    assert b.recovery_s() is None           # still open
    clock.t = 5.0
    assert b.allow()                        # half-open probe admitted
    assert b.state == "half_open"
    assert not b.allow()                    # only one probe in flight
    b.record_success()
    assert b.state == "closed"
    assert b.recovery_s() == pytest.approx(5.0)


def test_breaker_failed_probe_reopens_and_recovery_is_last_episode():
    clock = Clock()
    b = CircuitBreaker(failure_threshold=1, cooldown_s=1.0, clock=clock)
    b.record_failure()                      # open at t=0
    clock.t = 1.0
    assert b.allow()
    b.record_failure()                      # failed probe: reopen at t=1
    assert b.state == "open" and b.opens == 2
    clock.t = 2.5
    assert b.allow()
    b.record_success()                      # closed at t=2.5
    # recovery measures the LAST episode (1.0 -> 2.5), not the first.
    assert b.recovery_s() == pytest.approx(1.5)


def test_breaker_call_wraps_protocol():
    clock = Clock()
    b = CircuitBreaker(failure_threshold=1, cooldown_s=9.0, clock=clock)
    with pytest.raises(RuntimeError, match="boom"):
        b.call(lambda: (_ for _ in ()).throw(RuntimeError("boom")))
    with pytest.raises(CircuitOpen):
        b.call(lambda: "never runs")
    clock.t = 9.0
    assert b.call(lambda: "ok") == "ok" and b.state == "closed"


# -- brownout -------------------------------------------------------------

def test_brownout_levels_escalate_and_recover_with_hold():
    clock = Clock()
    b = BrownoutController(enter_pressure=0.5, exit_pressure=0.2,
                           shed_pressure=0.8, hold_s=1.0, clock=clock)
    assert b.update(0.6, now=0.0) == 0      # pressure high, hold not met
    assert b.update(0.6, now=0.5) == 0
    assert b.update(0.6, now=1.0) == 1      # sustained -> degraded
    assert b.decode_mode("beam") == "greedy"
    assert b.decode_mode("greedy") == "greedy"
    assert b.effective_max_batch(8) == 4
    assert not b.should_shed()
    # Escalation to brownout needs the HIGHER shed bar.
    assert b.update(0.6, now=2.5) == 1      # above enter, below shed
    b.update(0.9, now=3.0)
    assert b.update(0.9, now=4.0) == 2      # sustained above shed
    assert b.should_shed()
    # A pressure blip below exit does NOT de-escalate before hold_s.
    b.update(0.1, now=4.5)
    assert b.update(0.5, now=5.0) == 2      # blip ended; timer reset
    b.update(0.1, now=6.0)
    assert b.update(0.1, now=7.0) == 1      # one level per hold window
    b.update(0.1, now=8.0)
    assert b.update(0.1, now=9.0) == 0
    assert b.effective_max_batch(8) == 8


def test_brownout_gauge_and_counters():
    from deepspeech_tpu.serving import ServingTelemetry

    reg = ServingTelemetry()
    clock = Clock()
    b = BrownoutController(hold_s=0.0, clock=clock, registry=reg)
    assert reg.gauges["degraded"] == 0      # visible before any trouble
    b.update(1.0, now=0.0)
    assert reg.gauges["degraded"] == 1
    assert reg.counter("brownout_enter") == 1
    b.update(0.0, now=1.0)
    assert reg.gauges["degraded"] == 0
    assert reg.counter("brownout_exit") == 1


def test_brownout_validates_threshold_ordering():
    with pytest.raises(ValueError):
        BrownoutController(enter_pressure=0.2, exit_pressure=0.5)
    with pytest.raises(ValueError):
        BrownoutController(enter_pressure=0.9, shed_pressure=0.5)
    with pytest.raises(ValueError):
        BrownoutController(device_budget_s=0.0)


def test_brownout_device_pressure_drives_every_transition():
    """The device-side signal alone (p95 of gateway.dispatch_s over the
    budget) must walk the full ladder — normal -> degraded -> brownout
    and back — while the queue looks idle the whole time."""
    from deepspeech_tpu.serving import ServingTelemetry

    reg = ServingTelemetry()
    clock = Clock()
    b = BrownoutController(enter_pressure=0.5, exit_pressure=0.2,
                           shed_pressure=0.9, hold_s=1.0, clock=clock,
                           registry=reg, device_budget_s=0.1)
    # No dispatches yet: no device evidence -> no pressure.
    assert b.device_pressure() == 0.0
    assert b.update(0.0, now=0.0) == 0
    # Dispatches blow the budget: p95 = 0.25s against 0.1s, capped at 1.
    for _ in range(20):
        reg.observe("gateway.dispatch_s", 0.25)
    assert b.device_pressure() == 1.0
    # normal -> degraded after a sustained hold window...
    assert b.update(0.0, now=1.0) == 0
    assert b.update(0.0, now=2.0) == 1
    assert b.decode_mode("beam") == "greedy"
    # ... -> brownout after another (pressure clears the shed bar too).
    assert b.update(0.0, now=3.0) == 1
    assert b.update(0.0, now=4.0) == 2
    assert b.should_shed()
    # Recovery: fast dispatches drag the p95 below exit * budget.
    for _ in range(1000):
        reg.observe("gateway.dispatch_s", 0.001)
    assert b.device_pressure() <= 0.2
    assert b.update(0.0, now=5.0) == 2
    assert b.update(0.0, now=6.0) == 1      # one level per hold window
    assert b.update(0.0, now=7.0) == 1
    assert b.update(0.0, now=8.0) == 0
    assert not b.should_shed()


def test_brownout_effective_pressure_is_max_of_queue_and_device():
    from deepspeech_tpu.serving import ServingTelemetry

    reg = ServingTelemetry()
    clock = Clock()
    # No device budget configured: a slow histogram must be ignored.
    b0 = BrownoutController(hold_s=0.0, clock=clock, registry=reg)
    reg.observe("gateway.dispatch_s", 99.0)
    assert b0.device_pressure() == 0.0
    assert b0.update(0.0, now=0.0) == 0
    # With a budget, queue pressure still dominates when it's higher.
    b1 = BrownoutController(hold_s=0.0, clock=clock, registry=reg,
                            device_budget_s=1000.0)  # device ~ 0.099
    assert b1.device_pressure() < 0.5
    assert b1.update(1.0, now=0.0) == 1     # the queue signal escalated


# -- checkpoint partial-write fallback ------------------------------------

def test_checkpoint_restore_falls_back_to_intact_step(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "ck"), keep=3)
    mgr.save(1, {"state": {"w": np.full((4,), 1.0)}, "epoch": 0})
    mgr.wait()
    plan = FaultPlan([FaultSpec("checkpoint.save", "partial_write",
                                count=1)])
    faults.install(plan)
    try:
        mgr.save(2, {"state": {"w": np.full((4,), 2.0)}, "epoch": 1})
        mgr.wait()
    finally:
        faults.clear()
    fb0 = obs.registry().counter("checkpoint_restore_fallbacks")
    # Default restore: newest step is torn -> warn, count, fall back.
    got = mgr.restore()
    assert float(np.asarray(got["state"]["w"])[0]) == 1.0
    assert got["epoch"] == 0
    assert obs.registry().counter("checkpoint_restore_fallbacks") == fb0 + 1
    # strict=True and an explicit step keep the hard raise.
    with pytest.raises(Exception):
        mgr.restore(strict=True)
    with pytest.raises(Exception):
        mgr.restore(step=2)
    mgr.close()


def test_checkpoint_restore_raises_when_no_step_is_intact(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "ck"), keep=3)
    plan = FaultPlan([FaultSpec("checkpoint.save", "partial_write")])
    faults.install(plan)
    try:
        mgr.save(1, {"state": {"w": np.zeros((2,))}, "epoch": 0})
        mgr.wait()
    finally:
        faults.clear()
    with pytest.raises(Exception):
        mgr.restore()
    mgr.close()


def test_restore_walks_past_torn_and_guardian_rejected_steps(tmp_path):
    """Regression for the last-good ring landing on top of the torn-
    checkpoint fallback: the default restore must walk past BOTH a torn
    newest step and a guardian-rejected step to the older intact one."""
    mgr = CheckpointManager(str(tmp_path / "ck"), keep=5)
    mgr.save(1, {"state": {"w": np.full((2,), 1.0)}, "epoch": 0})
    mgr.save(2, {"state": {"w": np.full((2,), 2.0)}, "epoch": 0})
    mgr.wait()
    plan = FaultPlan([FaultSpec("checkpoint.save", "partial_write",
                                count=1)])
    faults.install(plan)
    try:
        mgr.save(3, {"state": {"w": np.full((2,), 3.0)}, "epoch": 0})
        mgr.wait()
    finally:
        faults.clear()
    mgr.mark_rejected(2)            # guardian judged step 2 anomalous
    got = mgr.restore()             # 3 is torn, 2 is rejected -> 1
    assert float(np.asarray(got["state"]["w"])[0]) == 1.0
    mgr.close()
    # The judgment persists (rejected_steps.json): a restarted process
    # must not resume from the poisoned-regime checkpoint either.
    mgr2 = CheckpointManager(str(tmp_path / "ck"), keep=5)
    assert mgr2.rejected_steps() == (2,)
    got = mgr2.restore()
    assert float(np.asarray(got["state"]["w"])[0]) == 1.0
    # An explicit step may still name the rejected one (forensics).
    got2 = mgr2.restore(step=2)
    assert float(np.asarray(got2["state"]["w"])[0]) == 2.0
    mgr2.close()


def test_checkpoint_last_good_ring_is_bounded_and_newest_first(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "ck"), keep=2, last_good_keep=2)
    assert mgr.restore_last_good() is None
    for s in (4, 8, 12):
        mgr.save_last_good(s, {"w": np.full((2,), float(s))},
                           meta={"applied_len": s})
    assert mgr.last_good_steps() == (8, 12)     # ring bound evicted 4
    step, state, meta = mgr.restore_last_good()
    assert step == 12 and meta == {"applied_len": 12}
    np.testing.assert_array_equal(np.asarray(state["w"]), 12.0)
    mgr.close()


# -- preemption guard -----------------------------------------------------

def test_preemption_guard_latches_real_sigterm_and_restores_handler():
    prev = signal.getsignal(signal.SIGTERM)
    with PreemptionGuard() as g:
        assert not g.requested()
        os.kill(os.getpid(), signal.SIGTERM)
        assert g.requested() and g.signum == signal.SIGTERM
        g.reset()
        assert not g.requested() and g.signum is None
        g.trigger()                         # cooperative (no signal)
        assert g.requested()
    assert signal.getsignal(signal.SIGTERM) is prev


def test_sigterm_midepoch_then_resume_is_bit_identical(tmp_path):
    """The tentpole acceptance: SIGTERM mid-epoch -> emergency
    checkpoint -> a fresh ``fit`` resumes and lands on the SAME final
    step and bit-identical params as the uninterrupted run."""
    import jax

    from deepspeech_tpu.config import get_config
    from deepspeech_tpu.data import CharTokenizer
    from deepspeech_tpu.train import Trainer, _SyntheticPipeline
    from deepspeech_tpu.utils.logging import JsonlLogger

    def cfg_for(ckdir):
        cfg = get_config("dev_slice")
        return dataclasses.replace(
            cfg,
            model=dataclasses.replace(cfg.model, rnn_hidden=96,
                                      rnn_layers=1, dtype="float32",
                                      conv_channels=(8, 8)),
            data=dataclasses.replace(cfg.data, batch_size=8,
                                     bucket_frames=(64,),
                                     max_label_len=16),
            train=dataclasses.replace(cfg.train, checkpoint_dir=ckdir,
                                      warmup_steps=20,
                                      learning_rate=3e-3,
                                      log_every=1000))

    class KillAfter:
        """Pipeline wrapper: SIGTERMs the process after N batches of
        each epoch have been yielded — the handler latches and fit's
        per-step poll takes the emergency-checkpoint path."""

        provides_global_batches = True

        def __init__(self, inner, after):
            self.inner = inner
            self.after = after

        def epoch(self, e):
            def gen():
                for i, b in enumerate(self.inner.epoch(e)):
                    yield b
                    if i + 1 == self.after:
                        os.kill(os.getpid(), signal.SIGTERM)
            return gen()

        def batches_per_epoch(self, e):
            return self.inner.batches_per_epoch(e)

        def peek(self):
            return self.inner.peek()

    tok = CharTokenizer.english()

    # Reference: uninterrupted 2-epoch run (4 batches/epoch -> 8 steps).
    cfg_a = cfg_for(str(tmp_path / "a"))
    pipe = _SyntheticPipeline(cfg_a, n_utts=32, frames=64, label_len=4)
    assert pipe.batches_per_epoch(0) == 4
    ta = Trainer(cfg_a, pipe, tok, logger=JsonlLogger(echo=False))
    ta.fit(epochs=2)
    assert int(ta.state.step) == 8

    # Interrupted run: SIGTERM lands mid-epoch-0.
    cfg_b = cfg_for(str(tmp_path / "b"))
    guard = PreemptionGuard().install()
    try:
        tb = Trainer(cfg_b, KillAfter(pipe, after=2), tok,
                     logger=JsonlLogger(echo=False), preempt=guard)
        last = tb.fit(epochs=2)
    finally:
        guard.uninstall()
    stopped_at = int(tb.state.step)
    assert last.get("preempted") is True
    assert 0 < stopped_at < 8               # genuinely mid-run
    tb.ckpt.wait()
    assert tb.ckpt.latest_step() == stopped_at  # emergency save landed
    tb.ckpt.close()

    # Resume from the emergency checkpoint and finish the run.
    tc = Trainer(cfg_b, pipe, tok, logger=JsonlLogger(echo=False))
    tc.maybe_restore()
    assert int(tc.state.step) == stopped_at
    tc.fit(epochs=2)
    assert int(tc.state.step) == 8
    # Bit-identical: every param leaf equals the uninterrupted run's.
    flat_a = jax.tree.leaves(ta.state.params)
    flat_c = jax.tree.leaves(tc.state.params)
    assert len(flat_a) == len(flat_c)
    for xa, xc in zip(flat_a, flat_c):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xc))


# -- training guardian ----------------------------------------------------

def _guardian(cfg=None, **kw):
    reg = MetricsRegistry()
    pm = PostmortemWriter(registry=reg)
    g = TrainingGuardian(cfg if cfg is not None else GuardianConfig(),
                         registry=reg, postmortem=pm, **kw)
    return g, reg, pm


def _metrics(loss=1.0, grad=2.0, upd=0.1):
    return {"loss": loss, "grad_norm": grad, "update_norm": upd}


def test_guardian_classifies_each_nonfinite_scalar_as_hard():
    g, _, _ = _guardian()
    assert g.classify(1.0, 2.0, 0.1) == ("ok", "")
    assert g.classify(float("nan"), 2.0, 0.1) == ("hard", "nonfinite_loss")
    assert g.classify(1.0, float("inf"), 0.1) == \
        ("hard", "nonfinite_grad_norm")
    assert g.classify(1.0, 2.0, float("nan")) == \
        ("hard", "nonfinite_update_norm")


def test_guardian_skip_ladder_escalates_to_rollback_decision():
    g, reg, pm = _guardian(GuardianConfig(max_consecutive_skips=2))
    assert g.observe_step(0, 0, _metrics()).action == "ok"
    assert g.applied == [0]
    nan = _metrics(loss=float("nan"))
    assert g.observe_step(1, 1, nan).action == "skip"
    assert g.observe_step(1, 2, nan).action == "skip"
    d = g.observe_step(1, 3, nan)               # third consecutive: cap
    assert d.action == "rollback" and d.classify == "hard"
    assert d.trigger == "nonfinite_loss"
    # Skipped batches never join the applied (surviving) list.
    assert g.applied == [0]
    assert reg.counter("guardian_skipped_batches") == 3
    recs = pm.recent("anomaly")
    assert len(recs) == 3
    assert all(r["trigger"] == "nonfinite_loss" for r in recs)
    # A clean step in between resets the consecutive counter.
    g2, _, _ = _guardian(GuardianConfig(max_consecutive_skips=2))
    for i in range(6):                          # alternate bad / good
        bad = g2.observe_step(i, 2 * i, nan)
        assert bad.action == "skip"
        assert g2.observe_step(i, 2 * i + 1, _metrics()).action == "ok"


def test_guardian_total_skip_budget_forces_rollback():
    g, _, _ = _guardian(GuardianConfig(max_skips=2,
                                       max_consecutive_skips=99))
    nan = _metrics(loss=float("nan"))
    assert g.observe_step(0, 0, nan).action == "skip"
    assert g.observe_step(0, 1, nan).action == "skip"
    assert g.observe_step(0, 2, nan).action == "rollback"


def test_guardian_soft_spike_backs_off_lr_and_recovers():
    cfg = GuardianConfig(stats_warmup_steps=5, soft_grad_factor=10.0,
                         backoff_factor=0.5, min_lr_scale=0.25,
                         recovery_steps=3)
    g, reg, pm = _guardian(cfg)
    # Before warmup even a huge spike is ok (no trusted stats yet).
    for i in range(4):
        assert g.observe_step(i, i, _metrics(grad=1.0)).action == "ok"
    assert g.observe_step(4, 4, _metrics(grad=500.0)).action == "ok"
    g.observe_step(5, 5, _metrics(grad=1.0))
    # Warmed up (median grad-norm ~1): a 50x spike is a soft anomaly.
    d = g.observe_step(6, 6, _metrics(grad=50.0))
    assert d.action == "backoff" and d.classify == "soft"
    assert d.trigger == "grad_norm_spike"
    assert g.lr_scale == 0.5
    # Soft steps still APPLY (finite update; only the LR shrank) ...
    assert len(g.applied) == 7
    # ... and repeated spikes floor at min_lr_scale.
    g.observe_step(7, 7, _metrics(grad=50.0))
    g.observe_step(8, 8, _metrics(grad=50.0))
    assert g.lr_scale == 0.25
    assert reg.counter("guardian_soft_anomalies") == 3
    assert len(pm.recent("anomaly")) == 3
    # recovery_steps clean steps walk the scale back up, one notch per
    # streak.
    for i in range(9, 12):
        assert g.observe_step(i, i, _metrics(grad=1.0)).action == "ok"
    assert g.lr_scale == 0.5
    for i in range(12, 15):
        g.observe_step(i, i, _metrics(grad=1.0))
    assert g.lr_scale == 1.0


def test_guardian_rollback_restores_ring_and_rejects_newer_disk(tmp_path):
    reg = MetricsRegistry()
    pm = PostmortemWriter(registry=reg)
    mgr = CheckpointManager(str(tmp_path / "ck"), keep=3, last_good_keep=2)
    g = TrainingGuardian(GuardianConfig(max_rollbacks=1), ckpt=mgr,
                         registry=reg, postmortem=pm)
    g.applied.extend([0, 1, 2])
    assert g.snapshot(3, {"w": np.full((4,), 7.0)})
    assert mgr.last_good_steps() == (3,)
    g.applied.extend([3, 4])        # two more updates stood after it
    # An on-disk save landed after the snapshot too — it may embed the
    # poisoned regime and must be rejected by the rollback.
    mgr.save(5, {"state": {"w": np.full((4,), 9.0)}, "epoch": 0})
    mgr.wait()
    step, host = g.rollback("nonfinite_loss")
    assert step == 3
    np.testing.assert_array_equal(np.asarray(host["w"]), 7.0)
    assert g.applied == [0, 1, 2]   # post-snapshot applied steps dropped
    assert mgr.rejected_steps() == (5,)
    assert reg.counter("guardian_rollbacks") == 1
    (rb,) = pm.recent("rollback")
    assert rb["to_step"] == 3 and rb["dropped_applied_steps"] == 2
    # The budget is a hard stop: one more rollback than allowed halts.
    with pytest.raises(GuardianHalt, match="budget"):
        g.rollback("again")
    mgr.close()
    # No CheckpointManager / empty ring: halt loudly, never no-op.
    g2 = TrainingGuardian(GuardianConfig(), ckpt=None,
                          registry=reg, postmortem=pm)
    with pytest.raises(GuardianHalt, match="CheckpointManager"):
        g2.rollback("x")
    mgr3 = CheckpointManager(str(tmp_path / "ck2"))
    g3 = TrainingGuardian(GuardianConfig(), ckpt=mgr3,
                          registry=reg, postmortem=pm)
    with pytest.raises(GuardianHalt, match="ring"):
        g3.rollback("x")
    mgr3.close()


def test_guardian_snapshot_cadence_counts_applied_steps(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "ck"), last_good_keep=3)
    g = TrainingGuardian(GuardianConfig(snapshot_every=2), ckpt=mgr,
                         registry=MetricsRegistry(),
                         postmortem=PostmortemWriter(
                             registry=MetricsRegistry()))
    state = {"w": np.zeros((2,))}
    for i in range(5):
        g.observe_step(i, i, _metrics())
        g.maybe_snapshot(i + 1, state)
    # Snapshots at applied-lengths 2 and 4 only.
    assert mgr.last_good_steps() == (2, 4)
    mgr.close()


def test_guardian_config_from_env(tmp_path, monkeypatch):
    monkeypatch.delenv("DS2_GUARDIAN", raising=False)
    assert GuardianConfig.from_env() is None
    for off in ("0", "false", "off", "no", ""):
        monkeypatch.setenv("DS2_GUARDIAN", off)
        assert GuardianConfig.from_env() is None
    monkeypatch.setenv("DS2_GUARDIAN", "1")
    assert GuardianConfig.from_env() == GuardianConfig()
    monkeypatch.setenv("DS2_GUARDIAN",
                       '{"ring_size": 5, "watchdog": false}')
    cfg = GuardianConfig.from_env()
    assert cfg.ring_size == 5 and cfg.watchdog is False
    p = tmp_path / "g.json"
    p.write_text('{"max_skips": 3}')
    monkeypatch.setenv("DS2_GUARDIAN", str(p))
    assert GuardianConfig.from_env().max_skips == 3


# -- stall watchdog -------------------------------------------------------

def test_stall_watchdog_timeout_tracks_p95_and_fires_once():
    reg = MetricsRegistry()
    pm = PostmortemWriter(registry=reg)
    clock = Clock()
    guard = PreemptionGuard()       # not installed: trigger() only
    w = StallWatchdog(k=10.0, min_timeout_s=5.0, registry=reg,
                      postmortem=pm, preempt=guard, clock=clock)
    assert w.timeout_s() == 5.0     # no step history yet: the floor
    for _ in range(20):
        reg.observe("train.step_s", 1.0)
    assert w.timeout_s() == 10.0    # k * p95 once it clears the floor
    assert not w.check()            # never armed: no heartbeat yet
    w.heartbeat()                   # beat at t=0
    clock.t = 9.0
    assert not w.check()            # inside the timeout
    clock.t = 11.0
    assert w.check()                # wedged: fires
    assert guard.requested()        # emergency-checkpoint path armed
    assert reg.counter("stall_watchdog_fires") == 1
    assert not w.check()            # one fire per wedge
    (rec,) = pm.recent("stall")
    assert rec["trigger"] == "no_heartbeat"
    assert rec["stacks"]            # all-thread stack evidence attached
    assert rec["timeout_s"] == 10.0
    # A fresh heartbeat re-arms it for the next wedge.
    w.heartbeat()
    clock.t = 30.0
    assert w.check()
    assert reg.counter("stall_watchdog_fires") == 2


def test_stall_watchdog_thread_lifecycle():
    w = StallWatchdog(poll_s=0.01, min_timeout_s=1e9,
                      registry=MetricsRegistry(),
                      postmortem=PostmortemWriter(
                          registry=MetricsRegistry()))
    with w as started:
        assert started is w
        assert w._thread is not None and w._thread.is_alive()
    assert w._thread is None        # stop() joined the poller


# -- postmortem writer ----------------------------------------------------

def test_postmortem_writer_counts_sinks_and_recent_tail():
    import io

    reg = MetricsRegistry()
    sink = io.StringIO()
    pm = PostmortemWriter(sink=sink, registry=reg, wall=lambda: 12.5)
    pm.write("corrupt_sample", "nan_features", utt="u3", row=3)
    pm.write("stall", "no_heartbeat", stalled_s=9.9)
    assert pm.written() == 2
    assert reg.counter("postmortems_written") == 2
    assert reg.counter("postmortems_written",
                       labels={"kind": "stall"}) == 1
    recs = [json.loads(l) for l in sink.getvalue().splitlines()]
    assert len(recs) == 2
    # Every line rides the shared obs schema check_obs_schema enforces.
    for r in recs:
        assert r["event"] == "postmortem" and r["ts"] == 12.5
        assert isinstance(r["kind"], str) and r["kind"]
        assert isinstance(r["trigger"], str)
    assert recs[0]["utt"] == "u3" and recs[0]["row"] == 3
    # The bounded tail is queryable by kind (the no-file default path).
    assert [r["kind"] for r in pm.recent()] == ["corrupt_sample", "stall"]
    (st,) = pm.recent("stall")
    assert st["stalled_s"] == 9.9
    pm.close()


def test_brownout_effective_tier_degrades_premium_only():
    """The tier-degradation rung (level >= 1): premium is served as
    bulk while degraded; bulk and tierless pass through untouched at
    every level; premium comes back the moment the level recovers."""
    clock = Clock()
    b = BrownoutController(hold_s=0.0, clock=clock)
    assert b.effective_tier("premium") == "premium"
    assert b.effective_tier("bulk") == "bulk"
    assert b.effective_tier(None) is None
    b.update(1.0, now=0.0)
    assert b.level >= 1
    assert b.effective_tier("premium") == "bulk"
    assert b.effective_tier("bulk") == "bulk"
    assert b.effective_tier(None) is None
    while b.level > 0:
        clock.t += 1.0
        b.update(0.0, now=clock.t)
    assert b.effective_tier("premium") == "premium"


# -- scenario: modeled traffic under a pinned fault plan ------------------

def test_scenario_traffic_under_fault_plan_loses_nothing(tiny_offline):
    """Arrivals from the seeded ``TrafficModel`` through the scheduler
    with the whole resilience stack (requeue, breaker, brownout) into a
    real (tiny) engine, under a pinned plan: two dispatch errors, then
    an unavailable window. Both kinds fire and are counted, the breaker
    opens in the window and closes through a probe after it, failed
    dispatches are retried, and every admitted request still completes
    with the transcript it gets alone. (The plan's third classic leg,
    the torn checkpoint write, is
    ``test_checkpoint_restore_falls_back_to_intact_step``.)"""
    from scenario import (EDGES, NF, ManualClock, replay, solo_decode)
    from deepspeech_tpu.serving import (MicroBatchScheduler,
                                        ServingTelemetry, TrafficModel)

    n, rps = 16, 120.0
    traffic = TrafficModel(
        seed=0, duration_s=n / rps, base_rps=rps, day_s=n / rps,
        diurnal_amplitude=0.5, burst_rate_mult=2.0, burst_enter_p=0.15,
        burst_exit_p=0.3, burst_step_s=0.05,
        len_log_mean=float(np.log(64)), len_log_sigma=0.6,
        len_min=16, len_max=max(EDGES), max_arrivals=n).schedule()
    arrivals = [a.t for a in traffic.arrivals]
    rng = np.random.default_rng(0)
    reqs = [rng.standard_normal((a.feat_len, NF)).astype(np.float32)
            for a in traffic.arrivals]
    assert 0 < len(reqs) <= n

    clock = ManualClock()
    tel = ServingTelemetry()
    breaker = CircuitBreaker(failure_threshold=2, cooldown_s=0.05,
                             name="gateway", clock=clock, registry=tel)
    brownout = BrownoutController(enter_pressure=0.7, exit_pressure=0.2,
                                  shed_pressure=0.95, hold_s=0.03,
                                  clock=clock, registry=tel)
    sched = MicroBatchScheduler(EDGES, 4, clock=clock, max_queue=32,
                                default_deadline=0.03, max_attempts=12,
                                telemetry=tel, breaker=breaker,
                                brownout=brownout)
    inf = tiny_offline.inferencer()
    t_mid = arrivals[len(arrivals) // 2]
    plan = FaultPlan([
        FaultSpec("gateway.dispatch", "error", prob=1.0, count=2,
                  message="injected decode error"),
        FaultSpec("gateway.dispatch", "unavailable",
                  after_s=t_mid, until_s=arrivals[-1] + 0.01),
    ], seed=0, clock=clock, registry=tel)
    faults.install(plan)
    try:
        results = replay(
            sched, clock, arrivals, reqs,
            lambda batch, p: inf.decode_batch_bucketed(batch, plans=[p]))
    finally:
        faults.clear()

    c = tel.snapshot()["counters"]
    kinds = {k.split('kind="')[1].split('"')[0]
             for k in c if k.startswith("faults_injected{")}
    assert kinds == {"error", "unavailable"}
    assert int(c["retries"]) > 0
    assert breaker.opens >= 1 and breaker.state == "closed"
    assert breaker.recovery_s() > 0
    assert int(c["admitted"]) == int(c["requests_ok"]) == len(results)
    assert int(c["admitted"]) + int(c.get("rejected", 0)) == len(reqs)
    for rid, r in results.items():
        assert r.status == "ok"
        assert r.text == solo_decode(inf, reqs[int(rid[1:])])


# -- scenario: a training run that heals itself ---------------------------

def test_scenario_training_survives_poison_and_leaves_no_trace(tmp_path):
    """``Trainer.fit`` under a pinned plan (one NaN-poisoned sample at
    batch 5, NaN gradients at steps 11 and 12): the run ends without an
    exception and with a finite loss, having quarantined the sample,
    skipped a batch, rolled back to the ring once and written a
    postmortem for each; and a clean trainer fed only the surviving
    batches ends on bit-identical params."""
    import jax

    from deepspeech_tpu.config import get_config
    from deepspeech_tpu.data import CharTokenizer
    from deepspeech_tpu.data.pipeline import scrub_padded_batch
    from deepspeech_tpu.parallel import shard_batch
    from deepspeech_tpu.train import Trainer, _SyntheticPipeline
    from deepspeech_tpu.utils.logging import JsonlLogger

    cfg = get_config("dev_slice")
    cfg = dataclasses.replace(
        cfg,
        model=dataclasses.replace(cfg.model, rnn_hidden=96, rnn_layers=1,
                                  dtype="float32", conv_channels=(8, 8)),
        data=dataclasses.replace(cfg.data, batch_size=8,
                                 bucket_frames=(64,), max_label_len=16),
        train=dataclasses.replace(
            cfg.train, checkpoint_dir=str(tmp_path / "ck"), epochs=1,
            warmup_steps=20, log_every=1, checkpoint_every_steps=0,
            guardian=True))
    knobs = GuardianConfig(snapshot_every=4, max_consecutive_skips=1,
                           stats_warmup_steps=10 ** 6, watchdog=False)

    class Recording:
        """Scrubs every batch through the quarantine path (where the
        ``pipeline.materialize`` fault fires) and keeps the post-scrub
        copies for the clean replay."""

        provides_global_batches = True

        def __init__(self, inner):
            self.inner, self.seen = inner, []
            self.peek = inner.peek
            self.batches_per_epoch = inner.batches_per_epoch
            self.eval_epoch = inner.eval_epoch

        def epoch(self, e):
            for b in self.inner.epoch(e):
                b = {k: np.array(v, copy=True) for k, v in b.items()}
                b, _ = scrub_padded_batch(b, step=len(self.seen))
                self.seen.append({k: v.copy() for k, v in b.items()})
                yield b

    def trainer_for(cfg, pipe):
        t = Trainer(cfg, pipe, tok, logger=JsonlLogger(echo=False))
        t.guardian = TrainingGuardian(knobs, ckpt=t.ckpt)
        return t

    tok = CharTokenizer.english()
    pipe = Recording(_SyntheticPipeline(cfg, 16 * 8, label_len=12))
    names = ("guardian_skipped_batches", "guardian_rollbacks",
             "samples_quarantined", "postmortems_written")
    reg = obs.registry()
    base = {k: int(reg.counter(k)) for k in names}
    plan = FaultPlan.from_dict({"seed": 7, "faults": [
        {"point": "train.step", "kind": "nan_grad", "skip": 10,
         "count": 2},
        {"point": "pipeline.materialize", "kind": "corrupt_batch",
         "skip": 4, "count": 1}]})
    chaos = trainer_for(cfg, pipe)
    faults.install(plan)
    try:
        last = chaos.fit()
    finally:
        faults.clear()
    chaos.ckpt.close()
    got = {k: int(reg.counter(k)) - base[k] for k in names}
    assert plan.fired() == 3
    assert got["samples_quarantined"] >= 1
    assert got["guardian_skipped_batches"] >= 1
    assert got["guardian_rollbacks"] == 1
    assert got["postmortems_written"] >= got["guardian_skipped_batches"]
    assert np.isfinite(last["loss"])
    survivors = list(chaos.guardian.applied)
    assert 0 < len(survivors) < len(pipe.seen)

    clean = trainer_for(dataclasses.replace(cfg, train=dataclasses.replace(
        cfg.train, checkpoint_dir="")), pipe)
    state = clean.state
    for i in survivors:
        state, _ = clean.train_step(
            state, shard_batch(clean.mesh, pipe.seen[i]),
            {"lr_scale": np.float32(1.0)})
    for a, b in zip(jax.tree.leaves(chaos.state.params),
                    jax.tree.leaves(state.params), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
