"""Observability layer: spans, metrics registry, exports, trace report.

Covers the ISSUE 3 acceptance surface: nested-span timing on an
injected clock, registry thread-safety under concurrent gateway
dispatch, the shared JSONL schema round-trip, ``tools/trace_report.py``
on a synthetic trace, the ``Histogram`` thinning-percentile
regression, Prometheus text exposition, and compile-event attribution
through ``ShapeBucketCache``.
"""

import gc
import io
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from deepspeech_tpu import obs
from deepspeech_tpu.obs.metrics import Histogram, MetricsRegistry
from deepspeech_tpu.obs.trace import Tracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Clock:
    """Deterministic monotonic clock (seconds)."""

    def __init__(self):
        self.t = 100.0

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


@pytest.fixture(autouse=True)
def no_collection_mid_test():
    """An enabled tracer writes a ``host.gc`` record for every garbage
    collection (``tests/test_host_turn.py``); the tests here count and
    order records, so nothing is collected while one runs."""
    gc.disable()
    yield
    gc.enable()


# -- spans ----------------------------------------------------------------

def test_nested_span_timing_with_injected_clock():
    clk = Clock()
    reg = MetricsRegistry()
    tr = Tracer(registry=reg, clock=clk, wall=clk)
    sink = io.StringIO()
    tr.configure(enabled=True, sink=sink)
    with tr.span("outer", step=3):
        clk.advance(0.010)
        with tr.span("inner"):
            clk.advance(0.005)
        clk.advance(0.001)
    inner, outer = [json.loads(l) for l in sink.getvalue().splitlines()]
    # Children close (and therefore serialize) first.
    assert inner["name"] == "inner" and outer["name"] == "outer"
    assert inner["dur_ms"] == pytest.approx(5.0)
    assert outer["dur_ms"] == pytest.approx(16.0)
    assert inner["parent"] == outer["id"] and outer["parent"] is None
    assert outer["step"] == 3
    assert inner["event"] == "span" and "ts" in inner
    # Every span duration also lands in the registry as a labeled
    # histogram sample, so render_text()/snapshot() see the breakdown.
    snap = reg.snapshot()
    assert snap["histograms"]['span_ms{name="inner"}']["count"] == 1
    assert snap["histograms"]['span_ms{name="outer"}']["p50"] \
        == pytest.approx(16.0)


def test_disabled_span_is_shared_noop():
    tr = Tracer()
    assert tr.span("a") is tr.span("b")  # no allocation on the off path
    with tr.span("a"):
        pass  # and it is a usable context manager


def test_span_nesting_is_per_thread():
    clk = Clock()
    tr = Tracer(registry=MetricsRegistry(), clock=clk, wall=clk)
    sink = io.StringIO()
    tr.configure(enabled=True, sink=sink)
    with tr.span("main_outer"):
        done = threading.Event()

        def other():
            with tr.span("worker"):
                pass
            done.set()

        t = threading.Thread(target=other)
        t.start()
        t.join()
        assert done.is_set()
    recs = {r["name"]: r for r in
            (json.loads(l) for l in sink.getvalue().splitlines())}
    # The worker thread's span must NOT adopt the train-loop parent.
    assert recs["worker"]["parent"] is None


# -- registry -------------------------------------------------------------

def test_registry_thread_safety_under_gateway_dispatch():
    """One shared telemetry registry, many schedulers dispatching
    concurrently (the gateway pattern: per-worker schedulers, one
    metrics sink): every count/observe/rung must land exactly once."""
    from deepspeech_tpu.serving import MicroBatchScheduler, ServingTelemetry

    tel = ServingTelemetry()
    n_threads, n_req = 6, 40

    def echo(batch, plan):
        return [""] * batch["features"].shape[0]

    def worker(tid):
        sched = MicroBatchScheduler((64, 128), 4, telemetry=tel)
        for i in range(n_req):
            sched.submit(np.zeros((50, 13), np.float32),
                         rid=f"{tid}-{i}")
        sched.drain(echo)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = tel.snapshot()
    assert snap["counters"]["requests_ok"] == n_threads * n_req
    assert snap["histograms"]["latency_ok"]["count"] == n_threads * n_req
    assert sum(snap["per_rung"].values()) \
        == sum(tel.rung_usage().values()) > 0


def test_registry_labels_are_distinct_series():
    reg = MetricsRegistry()
    reg.count("compiles")
    reg.count("compiles", labels={"rung": "4x64"})
    reg.count("compiles", 2, labels={"rung": "8x128"})
    assert reg.counter("compiles") == 1
    assert reg.counter("compiles", labels={"rung": "4x64"}) == 1
    assert reg.counter("compiles", labels={"rung": "8x128"}) == 2


def test_render_text_prometheus_exposition():
    reg = MetricsRegistry()
    reg.count("admitted", 3)
    reg.gauge("queue_depth", 2)
    reg.observe("latency_ok", 0.5)
    reg.observe("latency_ok", 1.5)
    reg.rung(4, 64)
    text = reg.render_text(prefix="ds2")
    assert "# TYPE ds2_admitted counter" in text
    assert "ds2_admitted 3" in text
    assert "# TYPE ds2_queue_depth gauge" in text
    assert "# TYPE ds2_latency_ok summary" in text
    assert 'ds2_latency_ok{quantile="0.50"} 0.5' in text
    assert "ds2_latency_ok_count 2" in text
    assert 'ds2_rung_usage{rung="4x64"} 1' in text
    # obs.render_text() is the process-wide surface of the same thing.
    assert isinstance(obs.render_text(), str)


# -- JSONL schema ---------------------------------------------------------

def test_jsonl_schema_roundtrip():
    """Registry snapshots, the serving-telemetry shim, and span records
    all ride ONE schema that tools/check_obs_schema.py accepts."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import importlib

    import check_obs_schema
    importlib.reload(check_obs_schema)

    from deepspeech_tpu.serving import ServingTelemetry

    fh = io.StringIO()
    reg = MetricsRegistry()
    reg.count("a")
    rec = reg.emit_jsonl(fh, extra_field=1)
    tel = ServingTelemetry()
    tel.rung(4, 64)
    trec = tel.emit_jsonl(fh, wall_s=0.5)
    assert trec["event"] == "serving_telemetry"

    clk = Clock()
    tr = Tracer(registry=MetricsRegistry(), clock=clk, wall=clk)
    tr.configure(enabled=True, sink=fh)
    with tr.span("phase", step=1):
        clk.advance(0.001)
    tr.compile_event(4, 64, site="x.py:1")

    lines = fh.getvalue().splitlines()
    parsed = [json.loads(l) for l in lines]
    # Round-trip: what emit_jsonl returned is exactly what hit the
    # stream.
    assert parsed[0] == rec and parsed[1] == trec
    assert check_obs_schema.scan(lines) == []
    for p in parsed:
        assert check_obs_schema.validate_record(p) == []


def test_check_obs_schema_flags_bad_records():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import importlib

    import check_obs_schema
    importlib.reload(check_obs_schema)

    assert check_obs_schema.validate_record({"event": "x"})  # no ts
    assert check_obs_schema.validate_record(
        {"event": "span", "ts": 1.0})  # span without dur_ms/name
    assert check_obs_schema.validate_record([1, 2])  # not an object
    problems = check_obs_schema.scan(
        ['{"event": "metrics", "ts": 1.0}', "not json",
         '{"ts": 2.0}'])
    assert [n for n, _ in problems] == [2, 3]


# -- Histogram thinning ---------------------------------------------------

def test_histogram_thinning_percentiles_stay_calibrated():
    """Regression for the reservoir-thinning drift: after many
    thin-by-2 rounds the kept samples must stay uniformly spaced over
    the WHOLE stream (no aliasing to one side), keeping percentile
    estimates of a monotone ramp within one stride of truth."""
    n = 100_000
    h = Histogram(max_samples=64)
    for v in range(n):
        h.observe(float(v))
    assert h.count == n and len(h._samples) <= 64
    kept = np.asarray(h._samples)
    # Uniform spacing across the stream: constant stride, both ends
    # covered.
    d = np.diff(kept)
    assert len(set(d.tolist())) == 1
    assert kept[0] < h._stride
    assert kept[-1] > n - 2 * h._stride
    snap = h.snapshot()
    assert snap["p50"] == pytest.approx(n / 2, rel=0.05)
    assert snap["p95"] == pytest.approx(0.95 * n, rel=0.05)
    assert snap["max"] == float(n - 1)
    # Same calibration when the stream is not sorted.
    rng = np.random.default_rng(0)
    h2 = Histogram(max_samples=64)
    for v in rng.permutation(n):
        h2.observe(float(v))
    assert h2.snapshot()["p50"] == pytest.approx(n / 2, rel=0.25)


# -- compile events -------------------------------------------------------

def test_shape_cache_compile_events_attributed():
    from deepspeech_tpu.utils.cache import ShapeBucketCache

    reg = MetricsRegistry()
    sink = io.StringIO()
    obs.configure(enabled=True, sink=sink, registry=reg)
    try:
        cache = ShapeBucketCache(max_shapes=4)
        cache.note(4, 64, 100)
        cache.note(4, 64, 100)   # hit: no new compile
        cache.note(8, 128, 900)
    finally:
        obs.configure(enabled=False, registry=obs.registry())
    assert reg.counter("compiles", labels={"rung": "4x64"}) == 1
    assert reg.counter("compiles", labels={"rung": "8x128"}) == 1
    recs = [json.loads(l) for l in sink.getvalue().splitlines()
            if json.loads(l)["event"] == "compile"]
    assert [r["rung"] for r in recs] == ["4x64", "8x128"]
    # Attribution points at THIS file, not the cache or obs internals.
    assert all("test_obs.py" in r["site"] for r in recs)


# -- jax's compile phases as spans ---------------------------------------

def test_jax_compile_phases_are_spans_under_the_open_span():
    import jax
    import jax.numpy as jnp

    reg = MetricsRegistry()
    tr = Tracer(registry=reg)
    sink = io.StringIO()
    tr.configure(enabled=True, sink=sink)

    def never_jitted_before(x):
        return x * 3 + 1

    with tr.span("train.step"):
        jax.jit(never_jitted_before)(jnp.ones(4)).block_until_ready()
    tr.configure(enabled=False)
    jax.jit(lambda x: x - 2)(jnp.ones(5))  # off: nothing more is written
    recs = [json.loads(l) for l in sink.getvalue().splitlines()]
    step = recs[-1]
    assert step["name"] == "train.step"
    mine = {r["name"]: r for r in recs
            if "never_jitted_before" in str(r.get("fun"))}
    assert set(mine) == {"jax.trace", "jax.lower", "jax.compile"}
    for r in mine.values():
        assert r["event"] == "span" and r["parent"] == step["id"]
        assert r["dur_ms"] > 0
        # Reported at its end, stamped at its start, inside the parent.
        assert step["ts"] <= r["ts"]
        assert r["ts"] + r["dur_ms"] / 1e3 \
            <= step["ts"] + step["dur_ms"] / 1e3 + 1e-3
    assert mine["jax.trace"]["ts"] <= mine["jax.lower"]["ts"] \
        <= mine["jax.compile"]["ts"]
    assert all(r["name"].startswith("jax.") for r in recs[:-1])
    assert reg.snapshot()["histograms"][
        'span_ms{name="jax.compile"}']["count"] >= 1


def test_jax_phase_listener_is_silent_and_free_when_off(monkeypatch):
    import tracemalloc

    import jax.monitoring

    from deepspeech_tpu.obs import trace as trace_mod

    listeners = []
    monkeypatch.setattr(jax.monitoring,
                        "register_event_duration_secs_listener",
                        listeners.append)
    tr = Tracer(registry=MetricsRegistry())
    assert not listeners  # lazily: a tracer never enabled hears nothing
    sink = io.StringIO()
    tr.configure(enabled=True, sink=sink)
    tr.configure(enabled=False)
    tr.configure(enabled=True, sink=sink)   # registers once, not twice
    on_duration, = listeners
    event = "/jax/core/compile/backend_compile_duration"
    on_duration(event, 0.25, fun_name="f")
    on_duration("/jax/some/other_duration", 0.25)
    tr.configure(enabled=False, sink=sink)
    kwargs = {"fun_name": "f"}
    on_duration(event, 0.25, **kwargs)  # warm: specialise the bytecode
    tracemalloc.start()
    try:
        a = tracemalloc.take_snapshot()
        for _ in range(200):
            on_duration(event, 0.25, **kwargs)
        b = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    only = [tracemalloc.Filter(True, trace_mod.__file__)]
    grown = sum(s.size_diff for s in b.filter_traces(only).compare_to(
        a.filter_traces(only), "lineno"))
    assert grown == 0
    recs = [json.loads(l) for l in sink.getvalue().splitlines()]
    assert [(r["name"], r["fun"], r["dur_ms"], r["parent"])
            for r in recs] == [("jax.compile", "f", 250.0, None)]
    # The listener does not keep a dropped tracer alive.
    import weakref
    ref = weakref.ref(tr)
    del tr
    gc.collect()
    assert ref() is None
    on_duration(event, 0.25, fun_name="f")


# -- trace report ---------------------------------------------------------

def test_trace_report_on_synthetic_trace(tmp_path):
    recs = [
        {"event": "span", "name": "root", "ts": 0.0, "dur_ms": 100.0,
         "id": 1, "parent": None},
        {"event": "span", "name": "mid", "ts": 0.01, "dur_ms": 60.0,
         "id": 2, "parent": 1},
        {"event": "span", "name": "leaf", "ts": 0.02, "dur_ms": 20.0,
         "id": 3, "parent": 2},
        {"event": "span", "name": "other", "ts": 0.1, "dur_ms": 50.0,
         "id": 4, "parent": None},
        {"event": "compile", "name": "compile", "ts": 0.0,
         "dur_ms": 0.0, "id": 5, "parent": None, "rung": "4x64",
         "site": "infer.py:1"},
        {"event": "compile", "name": "compile", "ts": 0.05,
         "dur_ms": 0.0, "id": 6, "parent": None, "rung": "4x64",
         "site": "infer.py:1"},
    ]
    p = tmp_path / "trace.jsonl"
    p.write_text("".join(json.dumps(r) + "\n" for r in recs))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_report.py"),
         str(p), "--json"], capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    agg = json.loads(out.stdout)
    ph = agg["phases"]
    # Cumulative vs self: root spends 60 of its 100 ms inside mid.
    assert ph["root"]["cum_ms"] == pytest.approx(100.0)
    assert ph["root"]["self_ms"] == pytest.approx(40.0)
    assert ph["mid"]["self_ms"] == pytest.approx(40.0)
    assert ph["leaf"]["self_ms"] == pytest.approx(20.0)
    # Wall = earliest start to latest end; both top-level spans cover
    # it exactly.
    assert agg["wall_ms"] == pytest.approx(150.0)
    assert agg["top_level_ms"] == pytest.approx(150.0)
    assert agg["coverage_pct"] == pytest.approx(100.0)
    assert agg["compiles"]["4x64"]["count"] == 2
    assert agg["compiles"]["4x64"]["sites"] == {"infer.py:1": 2}
    # Human-readable mode renders the same table.
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_report.py"),
         str(p)], capture_output=True, text=True, timeout=120)
    assert out.returncode == 0
    assert "root" in out.stdout and "recompiles per rung" in out.stdout


# -- series label round-trip ----------------------------------------------

def test_parse_series_roundtrips_topology_labels():
    """parse_series must invert the registry's series-key encoding for
    the full deployment-topology label set (tier + replica + version)
    — the SLO burn engine and brownout controller both navigate series
    keys through it, so a drifting encoding would silently zero their
    signals."""
    from deepspeech_tpu.obs.metrics import parse_series

    reg = MetricsRegistry()
    labels = {"tier": "premium", "replica": "r1", "version": "v2"}
    reg.count("slo_ok", 3, labels=labels)
    reg.count("slo_ok", 2)                       # bare twin
    reg.gauge("slo_burn_rate", 1.5,
              labels={"window": "fast", "tier": "premium"})
    series = [s for s in reg.counters if s.startswith("slo_ok{")]
    assert len(series) == 1
    name, parsed = parse_series(series[0])
    assert name == "slo_ok" and parsed == labels
    assert parse_series("slo_ok") == ("slo_ok", {})
    gseries, = list(reg.gauges)
    assert parse_series(gseries) == (
        "slo_burn_rate", {"window": "fast", "tier": "premium"})


def test_histogram_exemplar_tracks_extreme_sample():
    """observe(..., exemplar=rid) keeps the trace id of the max sample
    (the p99 request an operator wants to pull up), clears it when an
    exemplar-less observation takes the max, and rides the snapshot."""
    reg = MetricsRegistry()
    reg.observe("latency_ok", 0.02, exemplar="q1")
    reg.observe("latency_ok", 0.09, exemplar="q7")
    reg.observe("latency_ok", 0.04, exemplar="q9")  # not the max
    h = reg.hists["latency_ok"]
    assert h.max_exemplar == "q7"
    assert reg.snapshot()["histograms"]["latency_ok"]["max_exemplar"] \
        == "q7"
    # A new max with no exemplar must not keep pointing at q7.
    reg.observe("latency_ok", 0.5)
    assert h.max_exemplar is None
    assert "max_exemplar" not in \
        reg.snapshot()["histograms"]["latency_ok"]


# -- request trace context ------------------------------------------------

def test_trace_context_phase_ledger_telescopes():
    """Every moment of a request's life lands in exactly one phase, so
    the parts sum to the measured latency exactly — including across
    breaker deferrals and retry backoffs."""
    from deepspeech_tpu.obs.context import (PHASE_BACKOFF, PHASE_BREAKER,
                                            PHASE_DECODE, TraceContext)

    ctx = TraceContext("q0", 10.0, tier="bulk")
    ctx.to(PHASE_BREAKER, 10.02)    # 20 ms queued
    ctx.event("breaker_defer", 10.02)
    ctx.to(PHASE_DECODE, 10.05)     # 30 ms deferred
    ctx.to(PHASE_BACKOFF, 10.06)    # 10 ms failed decode
    ctx.to(PHASE_DECODE, 10.09)     # 30 ms backing off
    ctx.finish(10.11, "ok")         # 20 ms final decode
    assert ctx.complete()
    assert ctx.total_s == pytest.approx(0.11)
    assert sum(ctx.phases.values()) == pytest.approx(ctx.total_s)
    assert ctx.phases[PHASE_DECODE] == pytest.approx(0.03)
    assert ctx.cause() == PHASE_BREAKER
    rec = ctx.summary()
    assert rec["event"] == "trace" and rec["rid"] == "q0"
    assert rec["status"] == "ok" and rec["tier"] == "bulk"
    assert rec["cause"] == "breaker_defer"
    assert sum(rec["phases"].values()) == pytest.approx(rec["latency_ms"])
    assert rec["events"][0]["name"] == "breaker_defer"
    # finish is idempotent: a double-finalize can't stretch the ledger.
    ctx.finish(99.0, "error")
    assert ctx.status == "ok" and ctx.total_s == pytest.approx(0.11)


def test_flight_recorder_ring_and_slowest():
    from deepspeech_tpu.obs.context import FlightRecorder

    rec = FlightRecorder(capacity=4)
    for i in range(6):
        rec.record({"rid": f"q{i}", "latency_ms": float(i)})
    rec.record({"rid": "inflight"})  # no latency: never "slowest"
    assert len(rec) == 4
    assert [r["rid"] for r in rec.recent(2)] == ["q5", "inflight"]
    assert [r["rid"] for r in rec.slowest(2)] == ["q5", "q4"]
    rec.clear()
    assert len(rec) == 0 and rec.slowest() == []


# -- concurrent JSONL writers ---------------------------------------------

def test_tracer_concurrent_writers_never_tear_lines():
    """Interleaving audit (threaded per-replica fan-out): many threads
    pushing span + trace records through ONE tracer into ONE sink must
    produce only complete, parseable lines — the serialize-outside,
    write-inside-the-lock contract in Tracer._write."""
    clk = Clock()
    tr = Tracer(registry=MetricsRegistry(), clock=clk, wall=clk)
    sink = io.StringIO()
    tr.configure(enabled=True, sink=sink)
    n_threads, n_recs = 8, 50
    barrier = threading.Barrier(n_threads)

    def worker(tid):
        barrier.wait()   # maximize overlap
        for i in range(n_recs):
            if i % 2:
                with tr.span(f"work.t{tid}", i=i):
                    pass
            else:
                tr.emit({"event": "trace", "ts": 0.0,
                         "rid": f"{tid}-{i}", "status": "ok",
                         "phases": {"decode": 1.0},
                         "latency_ms": 1.0,
                         "pad": "x" * 256})  # widen the tear window

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    lines = sink.getvalue().splitlines()
    assert len(lines) == n_threads * n_recs
    recs = [json.loads(l) for l in lines]   # raises on a torn line
    # Nothing lost or duplicated, and the trace records pass the lint.
    got = {r["rid"] for r in recs if r["event"] == "trace"}
    assert got == {f"{t}-{i}" for t in range(n_threads)
                   for i in range(0, n_recs, 2)}
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import importlib

    import check_obs_schema
    importlib.reload(check_obs_schema)
    assert check_obs_schema.scan(lines) == []


# -- what the always-on hooks do when nothing is listening ---------------

def test_scenario_disabled_hooks_hand_out_noops_and_record_nothing(
        obs_lint):
    """The hooks the hot paths call unconditionally (``obs.span``,
    ``faults.inject``/``notify``, ``timeline.publish``/``last_for``)
    are, with nothing installed, the shared no-op: one object for every
    call, nothing written, nothing counted. Switched on, the same calls
    give one lint-clean record each."""
    from deepspeech_tpu.obs import timeline
    from deepspeech_tpu.obs.trace import _NOOP
    from deepspeech_tpu.resilience import faults

    faults.clear()
    timeline.clear()
    reg = MetricsRegistry()
    sink = io.StringIO()
    obs.configure(enabled=False, sink=sink, registry=reg)
    try:
        before = reg.snapshot()
        for k in range(100):
            with obs.span("train.step", step=k) as sp:
                assert sp is _NOOP
            assert faults.inject("train.step") is None
            assert faults.inject("gateway.dispatch", replica="r0") is None
            assert faults.notify("autoscale.scale_up") == 0
            assert timeline.publish("breaker_open", "pool",
                                    replica="r0", cause_seq=None) is None
            assert timeline.last_for("r0") is None
        assert sink.getvalue() == ""
        assert reg.snapshot() == before
        assert faults.active() is None and timeline.active() is None

        obs.configure(enabled=True, sink=sink, registry=reg)
        for k in range(5):
            with obs.span("train.step", step=k):
                pass
        recs = [json.loads(l) for l in sink.getvalue().splitlines()]
        assert [r["step"] for r in recs] == list(range(5))
        assert obs_lint(sink.getvalue()) == []
    finally:
        obs.configure(enabled=False, registry=obs.registry())
