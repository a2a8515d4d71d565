"""SLO burn-rate engine, live ops surface, and request-trace wiring.

The burn-rate math and alert lifecycle run entirely under an injected
clock (the engine never sleeps), so the multi-window semantics — fast
window pages on a sharp blip the slow window dilutes, alerts re-arm on
recovery — are scripted exactly. The scheduler integration drives the
same fake clock through the flush rules, pinning the TraceContext
telescoping invariant end to end.
"""

import io
import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from deepspeech_tpu.obs import (FlightRecorder, SloBurnEngine,
                                StatusServer)
from deepspeech_tpu.obs.metrics import MetricsRegistry, parse_series


class Clock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# -- burn math ------------------------------------------------------------

def test_burn_rate_is_miss_rate_over_budget():
    reg = MetricsRegistry()
    clk = Clock()
    eng = SloBurnEngine(target=0.9, registry=reg, clock=clk,
                        recorder=FlightRecorder(capacity=4),
                        postmortem_fn=lambda *a, **kw: {})
    eng.update()                       # baseline sample
    reg.count("slo_ok", 90)
    reg.count("slo_miss", 10)
    clk.advance(60.0)
    burn = eng.update()
    # 10% misses against a 10% error budget: burn exactly 1.0, in
    # every window (history shorter than both).
    assert burn[("fast", "")] == pytest.approx(1.0)
    assert burn[("slow", "")] == pytest.approx(1.0)
    assert eng.worst_burn() == pytest.approx(1.0)
    # Published as gauges, window-labeled (the schema lint's rule).
    got = {parse_series(k)[1]["window"]: v
           for k, v in reg.gauges.items()
           if parse_series(k)[0] == "slo_burn_rate"}
    assert got == {"fast": pytest.approx(1.0),
                   "slow": pytest.approx(1.0)}


def test_fast_window_fires_slow_window_holds_then_rearms():
    """The SRE-workbook shape: 55 minutes of clean traffic, then a
    sharp 5-minute blip. The fast window pages (the blip dominates
    it); the slow window dilutes the same blip below its threshold
    and holds. Recovery drains the blip out of the fast window, the
    alert re-arms, and a second episode pages again."""
    reg = MetricsRegistry()
    clk = Clock()
    pm_sink = io.StringIO()

    def pm(kind, trigger="", **ev):
        rec = {"event": "postmortem", "ts": 0.0, "kind": kind,
               "trigger": trigger, **ev}
        pm_sink.write(json.dumps(rec) + "\n")
        return rec

    eng = SloBurnEngine(target=0.99, registry=reg, clock=clk,
                        recorder=FlightRecorder(capacity=4),
                        postmortem_fn=pm)
    eng.update()                       # t=0 baseline
    for _ in range(55):                # 55 min of clean traffic
        clk.advance(60.0)
        reg.count("slo_ok", 100)
        eng.update()
    assert eng.alerts == []
    clk.advance(240.0)                 # the blip: misses only
    reg.count("slo_miss", 40)
    eng.update()
    # Fast window: 40 misses vs ~1 round of oks -> burn >> 14.4.
    assert eng.burn[("fast", "")] > 14.4
    # Slow window: the same 40 misses against 5500 oks -> burn < 6.
    assert eng.burn[("slow", "")] < 6.0
    assert eng.alert_active("fast") and not eng.alert_active("slow")
    assert [a["window"] for a in eng.alerts] == ["fast"]
    # Holding the breach does NOT re-fire (one page per episode).
    clk.advance(30.0)
    reg.count("slo_miss", 10)
    eng.update()
    assert len(eng.alerts) == 1
    # Recovery: the blip ages out of the fast window; re-arm.
    clk.advance(400.0)
    reg.count("slo_ok", 100)
    eng.update()
    assert eng.burn[("fast", "")] == pytest.approx(0.0)
    assert not eng.alert_active("fast")
    assert reg.counter("slo_alerts_recovered",
                       labels={"window": "fast"}) == 1
    # A second episode pages again: the alert actually re-armed.
    clk.advance(60.0)
    reg.count("slo_miss", 40)
    eng.update()
    assert [a["window"] for a in eng.alerts] == ["fast", "fast"]
    assert reg.counter("slo_alerts_fired",
                       labels={"window": "fast"}) == 2


def test_tiered_counters_burn_independently():
    """Tier-labeled slo counters produce per-tier burn and per-tier
    gauges; a bulk-only breach must not page premium."""
    reg = MetricsRegistry()
    clk = Clock()
    eng = SloBurnEngine(target=0.99, registry=reg, clock=clk,
                        windows={"fast": 300.0},
                        recorder=FlightRecorder(capacity=4),
                        postmortem_fn=lambda kind, **ev: {"kind": kind,
                                                          **ev})
    eng.update()
    clk.advance(60.0)
    reg.count("slo_ok", 100, labels={"tier": "premium"})
    reg.count("slo_miss", 50, labels={"tier": "bulk"})
    reg.count("slo_ok", 50, labels={"tier": "bulk"})
    eng.update()
    assert eng.burn[("fast", "premium")] == pytest.approx(0.0)
    assert eng.burn[("fast", "bulk")] == pytest.approx(50.0)
    assert eng.alert_active("fast", "bulk")
    assert not eng.alert_active("fast", "premium")
    alert, = eng.alerts
    assert alert["tier"] == "bulk"
    assert alert["postmortem"]["tier"] == "bulk"
    fams = {parse_series(k)[1].get("tier")
            for k in reg.gauges if k.startswith("slo_burn_rate{")}
    assert fams == {"premium", "bulk"}


def test_alert_postmortem_carries_slowest_requests():
    """The page diagnoses itself: kind="slo_burn" evidence names the
    slowest recent requests from the flight recorder — slowest first,
    slimmed to rid/cause/phases size — and lints clean."""
    import importlib
    import os
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    import check_obs_schema
    importlib.reload(check_obs_schema)

    reg = MetricsRegistry()
    clk = Clock()
    rec = FlightRecorder(capacity=16)
    for i, ms in enumerate([5.0, 80.0, 20.0, 60.0]):
        rec.record({"event": "trace", "ts": 0.0, "rid": f"q{i}",
                    "status": "ok", "latency_ms": ms,
                    "cause": "queue" if ms > 50 else "decode",
                    "phases": {"queue": ms / 2, "decode": ms / 2},
                    "features_debug": "never-in-evidence"})
    writes = []
    eng = SloBurnEngine(target=0.99, registry=reg, clock=clk,
                        recorder=rec, slowest_n=3,
                        postmortem_fn=lambda kind, **ev: writes.append(
                            {"event": "postmortem", "ts": 0.0,
                             "kind": kind, "trigger": ev.pop("trigger"),
                             **ev}) or writes[-1])
    eng.update()
    clk.advance(60.0)
    reg.count("slo_miss", 10)
    eng.update()
    assert writes, "breach did not page"
    page = writes[0]
    assert page["kind"] == "slo_burn"
    assert page["window"] in ("fast", "slow")
    assert page["burn_rate"] == pytest.approx(100.0)
    slowest = page["slowest_requests"]
    assert [s["rid"] for s in slowest] == ["q1", "q3", "q2"]
    assert slowest[0]["cause"] == "queue"
    # Slimmed: bulky attrs don't ride into the page.
    assert all("features_debug" not in s for s in slowest)
    assert check_obs_schema.validate_record(page) == []


def test_brownout_reads_burn_gauges_as_pressure():
    """The burn-rate family is a brownout pressure input: worst gauge
    over the budget, saturating at 1 — inert until configured AND
    published."""
    from deepspeech_tpu.resilience.brownout import BrownoutController

    reg = MetricsRegistry()
    clk = Clock()
    bro = BrownoutController(registry=reg, clock=clk, hold_s=0.0,
                             slo_burn_budget=10.0)
    assert bro.slo_burn_pressure() == 0.0        # nothing published
    reg.gauge("slo_burn_rate", 4.0, labels={"window": "slow"})
    reg.gauge("slo_burn_rate", 7.0,
              labels={"window": "fast", "tier": "bulk"})
    assert bro.slo_burn_pressure() == pytest.approx(0.7)  # worst/10
    reg.gauge("slo_burn_rate", 50.0, labels={"window": "fast"})
    assert bro.slo_burn_pressure() == 1.0        # saturates
    # Pressure drives the ladder even with an idle queue.
    clk.advance(1.0)
    assert bro.update(0.0) == 1
    clk.advance(1.0)
    assert bro.update(0.0) == 2 and bro.should_shed()
    # Unconfigured controllers never read the family (back-compat).
    assert BrownoutController(registry=reg).slo_burn_pressure() == 0.0


# -- live ops surface -----------------------------------------------------

def test_status_server_serves_live_state():
    reg = MetricsRegistry()
    reg.count("admitted", 3)
    state = {"level": 0}
    traces = [{"rid": "q0"}, {"rid": "q1"}, {"rid": "q2"}]
    with StatusServer(port=0, registry=reg,
                      health_fn=lambda: {"status": "ok",
                                         "level": state["level"]},
                      slo_fn=lambda: {"burn": {"fast": 0.5}},
                      traces_fn=lambda: list(traces)) as srv:
        def get(path):
            with urllib.request.urlopen(srv.url(path), timeout=5) as r:
                return r.status, r.read().decode()

        code, body = get("/metrics")
        assert code == 200 and "ds2_admitted 3" in body
        code, body = get("/healthz")
        assert code == 200 and json.loads(body)["status"] == "ok"
        # Live, not a snapshot: provider state changes are visible.
        state["level"] = 2
        assert json.loads(get("/healthz")[1])["level"] == 2
        code, body = get("/slo")
        assert json.loads(body) == {"burn": {"fast": 0.5}}
        code, body = get("/traces?n=2")
        assert [t["rid"] for t in json.loads(body)["traces"]] \
            == ["q1", "q2"]
        with pytest.raises(urllib.error.HTTPError) as e:
            get("/nope")
        assert e.value.code == 404
        # A raising provider surfaces as 500, not a dead thread.
        srv.slo_fn = lambda: 1 / 0
        with pytest.raises(urllib.error.HTTPError) as e:
            get("/slo")
        assert e.value.code == 500
        assert "ZeroDivisionError" in e.value.read().decode()
        # And the server is still alive afterwards.
        assert get("/healthz")[0] == 200
    assert srv.port is None                      # stopped on exit


# -- scheduler integration ------------------------------------------------

def test_scheduler_traces_telescoping_under_fake_clock():
    """End to end through the real scheduler with an injected clock:
    every finished request's phase ledger sums exactly to its result
    latency, retries land in retry_backoff, and the latency histogram
    keeps a trace-id exemplar for its extreme sample."""
    from deepspeech_tpu.serving import MicroBatchScheduler, ServingTelemetry

    clk = Clock()
    tel = ServingTelemetry()
    frec = FlightRecorder(capacity=32)
    sched = MicroBatchScheduler((64, 128), 2, default_deadline=0.05,
                                clock=clk, telemetry=tel,
                                flight_recorder=frec)
    calls = {"n": 0}

    def decode_fn(batch, plan):
        calls["n"] += 1
        clk.advance(0.02)
        if calls["n"] == 1:            # first batch fails once
            raise RuntimeError("transient")
        return ["ok"] * int(batch["features"].shape[0])

    for i in range(2):
        sched.submit(np.zeros((50, 13), np.float32), rid=f"q{i}")
        clk.advance(0.005)
    sched.pump(decode_fn)              # first attempt fails, requeues
    clk.advance(0.003)                 # backoff time actually passes
    results = sched.drain(decode_fn)
    assert {r.status for r in results.values()} == {"ok"}
    traces = {t["rid"]: t for t in frec.recent()}
    for rid, r in results.items():
        t = traces[rid]
        assert t["status"] == "ok"
        assert sum(t["phases"].values()) \
            == pytest.approx(t["latency_ms"], abs=1e-3)
        assert t["latency_ms"] == pytest.approx(r.latency * 1e3)
        assert t["attempts"] == 2 and "retry_backoff" in t["phases"]
        assert "rung" in t and "flush" in t and "slo_ok" in t
    # The batch failure quarantines both requests to solo redispatch:
    # the first retries after the 3ms backoff, the second's backoff
    # additionally absorbs the first's 20ms solo decode — the ledger
    # attributes the serialization, it doesn't hide it.
    backoffs = sorted(t["phases"]["retry_backoff"]
                      for t in traces.values())
    assert backoffs == pytest.approx([3.0, 23.0], abs=1e-3)
    # The extreme latency sample carries its trace id.
    assert tel.hists["latency_ok"].max_exemplar in results


def test_status_server_timeline_and_incidents_endpoints():
    """The two incident surfaces: explicit providers, the
    installed-EventLog fallback for /timeline, the empty default for
    /incidents, and ?n= truncation."""
    from deepspeech_tpu.obs import timeline as tl
    from deepspeech_tpu.obs.timeline import EventLog

    events = [{"seq": 1, "kind": "fault_fire"},
              {"seq": 2, "kind": "breaker_open"},
              {"seq": 3, "kind": "drain_cancel"}]
    incidents = {"open": [], "closed": [{"incident_id": 1}],
                 "orphans": 0}
    with StatusServer(port=0, registry=MetricsRegistry(),
                      timeline_fn=lambda: list(events),
                      incidents_fn=lambda: dict(incidents)) as srv:
        def get(path):
            with urllib.request.urlopen(srv.url(path), timeout=5) as r:
                return r.status, r.read().decode()

        code, body = get("/timeline")
        assert code == 200
        assert [e["seq"] for e in json.loads(body)["events"]] \
            == [1, 2, 3]
        assert [e["seq"]
                for e in json.loads(get("/timeline?n=2")[1])["events"]] \
            == [2, 3]
        code, body = get("/incidents")
        assert code == 200
        assert json.loads(body)["closed"] == [{"incident_id": 1}]

    # No providers wired: /timeline falls back to the process-wide
    # installed log (empty list when none), /incidents to the empty
    # correlator shape — both stay 200, never 500.
    clk = Clock()
    tl.clear()
    with StatusServer(port=0, registry=MetricsRegistry()) as srv:
        def get(path):
            with urllib.request.urlopen(srv.url(path), timeout=5) as r:
                return r.status, r.read().decode()

        assert json.loads(get("/timeline")[1]) == {"events": []}
        assert json.loads(get("/incidents")[1]) \
            == {"open": [], "closed": [], "orphans": 0}
        try:
            log = tl.install(EventLog(clock=clk,
                                      wall=lambda: 1.7e9 + clk.t))
            log.publish("breaker_open", "pool", replica="r1")
            evs = json.loads(get("/timeline")[1])["events"]
            assert [e["kind"] for e in evs] == ["breaker_open"]
        finally:
            tl.clear()


def test_status_server_500_on_every_endpoint_and_silent_handler(capsys):
    """A raising provider maps to a 500 (with the error text) on EVERY
    endpoint — including /timeline and /incidents — the server thread
    survives, and the handler writes nothing to stdout/stderr across
    200s, 404s, and 500s (serve JSONL streams must stay clean)."""
    class _BadRegistry(MetricsRegistry):
        def render_text(self):
            raise RuntimeError("scrape exploded")

    def boom():
        raise RuntimeError("provider exploded")

    with StatusServer(port=0, registry=_BadRegistry(),
                      health_fn=boom, slo_fn=boom, traces_fn=boom,
                      timeline_fn=boom, incidents_fn=boom) as srv:
        def get_err(path):
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(srv.url(path), timeout=5)
            return e.value

        for path in ("/metrics", "/healthz", "/slo", "/traces",
                     "/timeline", "/incidents"):
            err = get_err(path)
            assert err.code == 500, path
            assert "RuntimeError" in err.read().decode(), path
        assert get_err("/nope").code == 404
        # Still alive after six provider failures in a row.
        srv.health_fn = lambda: {"status": "ok"}
        with urllib.request.urlopen(srv.url("/healthz"), timeout=5) as r:
            assert r.status == 200
    out = capsys.readouterr()
    assert out.out == "" and out.err == ""


# -- scenario: breach -> page -> brownout -> recovery ---------------------

def test_scenario_breach_pages_browns_out_and_recovers():
    """One scripted day through the real scheduler: healthy rounds burn
    nothing; with every decode at 4x the deadline the fast window pages
    ONCE, its postmortem names the slowest requests and their causes,
    the burn gauges walk brownout up until admissions shed; once the
    breach ages out the alert re-arms and the ladder is back at 0. The
    status endpoints answer in every phase."""
    import urllib.request

    from deepspeech_tpu.resilience.brownout import BrownoutController
    from deepspeech_tpu.resilience.postmortem import PostmortemWriter
    from deepspeech_tpu.serving import (MicroBatchScheduler,
                                        OverloadRejected,
                                        ServingTelemetry)

    clk = Clock()
    tel = ServingTelemetry()
    frec = FlightRecorder(capacity=512)
    pm = PostmortemWriter(registry=tel)
    bro = BrownoutController(registry=tel, clock=clk, hold_s=0.0,
                             slo_burn_budget=10.0)
    eng = SloBurnEngine(target=0.99, registry=tel, clock=clk,
                        recorder=frec, postmortem_fn=pm.write)
    deadline, bs = 0.05, 4
    sched = MicroBatchScheduler([64, 128], bs, max_queue=8 * bs,
                                default_deadline=deadline, clock=clk,
                                telemetry=tel, brownout=bro,
                                flight_recorder=frec)
    cost = {"s": 0.01}
    seen = {"shed": 0, "level": 0}

    def decode_fn(batch, plan):
        clk.advance(cost["s"])
        return ["ok"] * int(batch["features"].shape[0])

    def rounds(tag, n):
        for k in range(n):
            for j in range(bs):
                try:
                    sched.submit(np.zeros((48, 8), np.float32),
                                 rid=f"{tag}{k}-{j}")
                except OverloadRejected:
                    seen["shed"] += 1
            sched.pump(decode_fn)
            eng.update()
            seen["level"] = max(seen["level"], bro.level)
            clk.advance(30.0)

    def poll(srv):
        for p in ("/metrics", "/healthz", "/slo", "/traces?n=8"):
            with urllib.request.urlopen(srv.url(p), timeout=5) as r:
                assert r.status == 200 and r.read()

    with StatusServer(port=0, registry=tel, slo_fn=eng.status,
                      health_fn=lambda: {"status": "ok",
                                         "brownout_level": bro.level},
                      traces_fn=lambda: frec.recent(64)) as srv:
        rounds("h", 6)
        assert eng.worst_burn("fast") < 14.4 and not eng.alerts
        poll(srv)
        cost["s"] = 4 * deadline
        rounds("b", 6)
        assert eng.worst_burn("fast") >= 14.4
        assert eng.alert_active("fast")
        poll(srv)
        cost["s"] = 0.01
        clk.advance(max(eng.windows.values()) + 60.0)
        rounds("r", 8)
        poll(srv)

    fast = [a for a in eng.alerts if a["window"] == "fast"]
    assert len(fast) == 1 and not eng.alert_active("fast")
    slowest = fast[0]["postmortem"]["slowest_requests"]
    assert slowest[0]["rid"].startswith("b")
    assert all(r["rid"] and r["cause"] for r in slowest)
    assert len(pm.recent("slo_burn")) == len(eng.alerts)
    assert seen["level"] >= 2 and seen["shed"] >= 1
    assert bro.level == 0
    assert int(tel.counter("slo_miss")) > 0
