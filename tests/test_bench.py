"""bench.py is a graded driver artifact — test its contract.

The driver runs ``python bench.py`` and parses stdout as ONE JSON line;
everything else (sweep failures, fallback decisions, markers) must stay
on stderr / on disk. These tests run the real main() on the CPU
backend with a tiny config.
"""

import importlib.util
import io
import json
import os
import sys

import pytest

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench.py")


def _load_bench():
    spec = importlib.util.spec_from_file_location("bench", _BENCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture()
def bench_env(tmp_path, monkeypatch):
    monkeypatch.setenv("BENCH_CONFIG", "dev_slice")
    # conftest forces 8 virtual CPU devices; the bench mesh spans all
    # of them, so the global batch must divide by 8.
    monkeypatch.setenv("BENCH_BATCH", "8")
    monkeypatch.setenv("BENCH_FRAMES", "32")
    monkeypatch.setenv("BENCH_STEPS", "1")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    # Keep the prior-session state file out of the repo during tests.
    monkeypatch.setenv("BENCH_STATE_FILE", str(tmp_path / "last_bench.json"))
    monkeypatch.delenv("BENCH_RNN_IMPL", raising=False)
    monkeypatch.delenv("BENCH_LOSS_IMPL", raising=False)
    return tmp_path


@pytest.mark.slow  # ~54 s: real main() end-to-end (r5 durations data)
def test_bench_prints_single_json_line(bench_env, monkeypatch):
    bench = _load_bench()
    out = io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    bench.main()
    lines = [l for l in out.getvalue().splitlines() if l.strip()]
    assert len(lines) == 1, lines
    rec = json.loads(lines[0])
    assert rec["metric"] == "utt_per_sec_per_chip"
    assert rec["unit"] == "utt/s/chip"
    assert rec["value"] > 0
    # VERDICT r4 #6: a CPU-backend row has no honest ratio against the
    # per-chip north-star target — vs_baseline must be null, with the
    # target band carried alongside for context.
    assert rec["vs_baseline"] is None
    assert rec["target_band_utt_s_chip"] == [4.8, 9.7]
    # impl records which rnn/loss implementations produced the number
    # (the cold-compile fallback would show "xla/jnp" here).
    assert rec["impl"] == "auto/auto"


def test_bench_writes_no_warm_marker_on_cpu(bench_env, monkeypatch):
    """CPU compiles a different graph; a CPU marker must never convince
    a TPU invocation that the Pallas step's cache is warm."""
    bench = _load_bench()
    monkeypatch.setattr(sys, "stdout", io.StringIO())
    bench.main()
    cache = bench_env / "cache"
    markers = (list(cache.glob("DS2N_WARM_*")) if cache.exists() else [])
    assert markers == []


def test_bench_empty_sweep_is_an_error(bench_env, monkeypatch):
    monkeypatch.setenv("BENCH_BATCH", " , ")
    bench = _load_bench()
    with pytest.raises(SystemExit):
        bench.main()


def test_bench_records_result_state(bench_env, monkeypatch):
    """A successful run persists its row (with provenance fields) to
    BENCH_STATE_FILE for the prior-session fallback."""
    bench = _load_bench()
    out = io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    bench.main()
    live = json.loads(out.getvalue().strip())
    assert live["source"] == "measured"
    assert live["backend"] == "cpu"
    assert live["measured_at"]
    assert (live["preset"], live["frames"], live["batch"]) == \
        ("dev_slice", 32, 8)
    with open(bench_env / "last_bench.json") as f:
        stored = json.load(f)
    assert stored["synthetic:dev_slice:f32"] == live


def test_bench_prior_session_fallback_shape(bench_env, monkeypatch):
    """Backend-never-up path: the ONE JSON line is the persisted prior
    row relabelled source=prior_session, and main() exits 0 (VERDICT r3
    #6 — a wedged claim at driver time must not erase a number measured
    hours earlier)."""
    bench = _load_bench()
    prior = {"metric": "utt_per_sec_per_chip", "value": 123.4,
             "unit": "utt/s/chip", "vs_baseline": 1.0, "impl": "auto/auto",
             "source": "measured", "backend": "tpu",
             "device_kind": "TPU v5 lite", "pipeline": "synthetic",
             "preset": "dev_slice", "frames": 32,
             "measured_at": "2026-07-29T20:50:00Z"}
    with open(bench_env / "last_bench.json", "w") as f:
        json.dump({"synthetic:dev_slice:f32": prior}, f)

    def boom(*a, **k):
        raise bench.BackendNeverUp(
            "backend never became available: UNAVAILABLE")

    monkeypatch.setattr(bench, "_wait_for_backend", boom)
    out = io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    bench.main()  # must NOT raise
    lines = [l for l in out.getvalue().splitlines() if l.strip()]
    assert len(lines) == 1, lines
    rec = json.loads(lines[0])
    assert rec["source"] == "prior_session"
    assert rec["value"] == 123.4
    assert rec["backend"] == "tpu"
    assert rec["measured_at"] == "2026-07-29T20:50:00Z"
    assert "UNAVAILABLE" in rec["backend_error"]
    # TPU-backed prior row: ratio recomputed on emit against the
    # H100-parity midpoint (123.4 / 7.3).
    assert rec["vs_baseline"] == pytest.approx(123.4 / 7.3, abs=1e-3)
    # Consumers reject recycled rows by this EXACT byte sequence; a
    # serialization change must not break it silently.
    assert '"source": "prior_session"' in lines[0]


def test_bench_cpu_prior_row_emits_null_vs_baseline(bench_env, monkeypatch):
    """VERDICT r4 #6 pin: a recycled CPU-floor row must NOT report
    vs_baseline 1.0 against its own floor — the ratio is null on a
    non-target backend, and the target band is attached so the
    artifact's consumer sees what the missing number is scored
    against."""
    bench = _load_bench()
    prior = {"metric": "utt_per_sec_per_chip", "value": 0.031,
             "unit": "utt/s/chip", "vs_baseline": 1.0, "impl": "auto/auto",
             "source": "measured", "backend": "cpu",
             "device_kind": "cpu", "pipeline": "synthetic",
             "preset": "dev_slice", "frames": 32,
             "measured_at": "2026-07-31T00:00:00Z"}
    with open(bench_env / "last_bench.json", "w") as f:
        json.dump({"synthetic:dev_slice:f32": prior}, f)

    def boom(*a, **k):
        raise bench.BackendNeverUp(
            "backend never became available: UNAVAILABLE")

    monkeypatch.setattr(bench, "_wait_for_backend", boom)
    out = io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    bench.main()
    rec = json.loads(out.getvalue().strip())
    assert rec["source"] == "prior_session"
    assert rec["vs_baseline"] is None
    assert rec["target_band_utt_s_chip"] == [4.8, 9.7]


def test_vs_baseline_helper_semantics():
    """Unit pin for the ratio rule: cpu -> None; target hardware ->
    value / 7.3 (H100-parity midpoint) while no published baseline."""
    bench = _load_bench()
    assert bench._vs_baseline(5.0, "cpu") is None
    assert bench._vs_baseline(7.3, "tpu") == pytest.approx(1.0)
    assert bench._vs_baseline(14.6, "tpu") == pytest.approx(2.0)


def test_bench_prior_fallback_disabled_stays_loud(bench_env, monkeypatch):
    """BENCH_PRIOR_FALLBACK=0 (the chip session's setting): a wedged
    backend must fail rc!=0 even when a prior row exists — the session
    stage gating and watchdog must never mistake a recycled row for a
    fresh on-chip measurement."""
    bench = _load_bench()
    monkeypatch.setenv("BENCH_CONFIG", "ds2_full")
    monkeypatch.setenv("BENCH_FRAMES", "800")
    bench._record_result({"metric": "utt_per_sec_per_chip", "value": 9.0,
                          "unit": "utt/s/chip", "vs_baseline": 1.0,
                          "backend": "tpu", "measured_at": "t",
                          "pipeline": "synthetic", "preset": "ds2_full",
                          "frames": 800})
    monkeypatch.setenv("BENCH_PRIOR_FALLBACK", "0")

    def boom(*a, **k):
        raise bench.BackendNeverUp(
            "backend never became available: UNAVAILABLE")

    monkeypatch.setattr(bench, "_wait_for_backend", boom)
    monkeypatch.setattr(sys, "stdout", io.StringIO())
    with pytest.raises(RuntimeError):
        bench.main()


def test_bench_no_prior_row_still_raises(bench_env, monkeypatch):
    """With no usable prior row the wedged-claim failure stays loud."""
    bench = _load_bench()

    def boom(*a, **k):
        raise bench.BackendNeverUp(
            "backend never became available: UNAVAILABLE")

    monkeypatch.setattr(bench, "_wait_for_backend", boom)
    monkeypatch.setattr(sys, "stdout", io.StringIO())
    with pytest.raises(RuntimeError):
        bench.main()


def test_record_result_retention_policy(bench_env):
    """TPU rows dominate CPU rows; best TPU wins; newest CPU wins."""
    bench = _load_bench()
    path = bench_env / "last_bench.json"

    def row(backend, value, at, pipeline="synthetic"):
        return {"metric": "utt_per_sec_per_chip", "value": value,
                "unit": "utt/s/chip", "vs_baseline": 1.0,
                "backend": backend, "measured_at": at,
                "pipeline": pipeline, "preset": "ds2_full", "frames": 800}

    def stored(mode="synthetic"):
        return json.load(open(path))[f"{mode}:ds2_full:f800"]

    bench._record_result(row("cpu", 5.0, "t0"))
    assert stored()["value"] == 5.0
    bench._record_result(row("cpu", 3.0, "t1"))  # newest CPU wins
    assert stored()["measured_at"] == "t1"
    bench._record_result(row("tpu", 50.0, "t2"))  # TPU displaces CPU
    assert stored()["backend"] == "tpu"
    bench._record_result(row("cpu", 999.0, "t3"))  # CPU never displaces TPU
    assert stored()["backend"] == "tpu"
    bench._record_result(row("tpu", 40.0, "t4"))  # worse TPU loses
    assert stored()["value"] == 50.0
    bench._record_result(row("tpu", 60.0, "t5"))  # better TPU wins
    assert stored()["value"] == 60.0
    # Modes are independent: a slow manifest row persists alongside the
    # fast synthetic row, and the fallback never cross-serves them.
    bench._record_result(row("tpu", 8.0, "t6", pipeline="manifest"))
    assert stored("manifest")["value"] == 8.0
    assert stored()["value"] == 60.0
    # A corrupt/null-value state file is ignored, not fatal.
    with open(path, "w") as f:
        f.write('{"synthetic:ds2_full:f800": {"value": null}}')
    bench._record_result(row("tpu", 70.0, "t7"))
    assert stored()["value"] == 70.0


def test_bench_fallback_respects_workload_key(bench_env, monkeypatch):
    """A prior row only answers an invocation of the SAME workload:
    pipeline mode, preset, and frames all participate in the key."""
    bench = _load_bench()
    monkeypatch.setenv("BENCH_CONFIG", "ds2_full")
    monkeypatch.setenv("BENCH_FRAMES", "800")
    bench._record_result({"metric": "utt_per_sec_per_chip", "value": 60.0,
                          "unit": "utt/s/chip", "vs_baseline": 1.0,
                          "backend": "tpu", "measured_at": "t",
                          "pipeline": "synthetic", "preset": "ds2_full",
                          "frames": 800})
    err = RuntimeError("UNAVAILABLE")
    # other mode / frames / preset: no answer
    assert not bench._emit_prior_result(err, "manifest", "ds2_full", 800)
    assert not bench._emit_prior_result(err, "synthetic", "ds2_full", 32)
    assert not bench._emit_prior_result(err, "synthetic", "dev_slice", 800)
    out = io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    assert bench._emit_prior_result(err, "synthetic", "ds2_full", 800)
    assert json.loads(out.getvalue())["value"] == 60.0


def test_bench_nonbackend_runtime_errors_stay_loud(bench_env, monkeypatch):
    """Only BackendNeverUp may fall back to a prior row; any other
    RuntimeError (e.g. PJRT misconfiguration) must keep failing loud
    even when a prior row exists."""
    bench = _load_bench()
    monkeypatch.setenv("BENCH_CONFIG", "ds2_full")
    monkeypatch.setenv("BENCH_FRAMES", "800")
    bench._record_result({"metric": "utt_per_sec_per_chip", "value": 60.0,
                          "unit": "utt/s/chip", "vs_baseline": 1.0,
                          "backend": "tpu", "measured_at": "t",
                          "pipeline": "synthetic", "preset": "ds2_full",
                          "frames": 800})

    def boom(*a, **k):
        raise RuntimeError("PJRT plugin config error")

    monkeypatch.setattr(bench, "_wait_for_backend", boom)
    monkeypatch.setattr(sys, "stdout", io.StringIO())
    with pytest.raises(RuntimeError, match="PJRT"):
        bench.main()


@pytest.mark.slow  # ~49 s: real host pipeline feed (r5 durations data)
def test_bench_manifest_pipeline_mode(bench_env, monkeypatch):
    """BENCH_PIPELINE=manifest feeds the timed loop from the REAL host
    pipeline (wav corpus -> featurize -> bucket -> prefetch), one fresh
    batch per step, and records the mode in the JSON line."""
    monkeypatch.setenv("BENCH_PIPELINE", "manifest")
    monkeypatch.setenv("BENCH_STEPS", "2")
    bench = _load_bench()
    out = io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    bench.main()
    lines = [l for l in out.getvalue().splitlines() if l.strip()]
    assert len(lines) == 1, lines
    rec = json.loads(lines[0])
    assert rec["pipeline"] == "manifest"
    assert rec["value"] > 0


def test_bench_infer_bucketed_smoke(bench_env, monkeypatch):
    """--bench=infer_bucketed on the CPU backend: ONE JSON line whose
    padding-waste beats the single-max-shape baseline and whose compile
    count is bounded by the (B, T) ladder. BENCH_OVERRIDES shrinks the
    model so the jit compiles stay cheap."""
    monkeypatch.setenv(
        "BENCH_OVERRIDES",
        "model.rnn_hidden=32 model.rnn_layers=1 model.conv_channels=4,4 "
        "model.dtype=float32 data.bucket_frames=64,128 data.batch_size=4")
    bench = _load_bench()
    out = io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    bench.main(["--bench=infer_bucketed", "--steps=1"])
    lines = [l for l in out.getvalue().splitlines() if l.strip()]
    assert len(lines) == 1, lines
    rec = json.loads(lines[0])
    assert rec["metric"] == "infer_utt_per_sec_per_chip"
    assert rec["pipeline"] == "infer_bucketed"
    assert rec["value"] > 0
    # The whole point of bucketing: strictly less padding compute than
    # decoding every batch at the single max shape.
    assert 0 < rec["padding_waste_pct"] < rec["baseline_padding_waste_pct"]
    # Compiled-shape discipline: the ladder bounds recompiles.
    assert rec["compiles"] <= rec["ladder_size"]
    assert rec["shape_cache_hits"] >= 0
    assert rec["source"] == "measured" and rec["backend"] == "cpu"


def test_bench_warm_restart_smoke(bench_env, monkeypatch):
    """--bench=warm_restart on the CPU backend: ONE JSON line proving
    the zero-compile restart — a restarted replica preloads the full
    (tiny) ladder from the serialized-executable store, decodes
    bit-identically with zero runtime compiles, the
    fingerprint-mismatch leg rejects every rung back to jit, and the
    autoscale/rollout consumers report compiles_avoided > 0."""
    monkeypatch.setenv(
        "BENCH_OVERRIDES",
        "model.rnn_hidden=32 model.rnn_layers=1 model.conv_channels=4,4 "
        "model.dtype=float32 data.bucket_frames=64,128 data.batch_size=4")
    bench = _load_bench()
    out = io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    bench.main(["--bench=warm_restart", "--steps=1"])
    lines = [l for l in out.getvalue().splitlines() if l.strip()]
    assert len(lines) == 1, lines
    rec = json.loads(lines[0])
    assert rec["metric"] == "warm_restart_speedup"
    assert rec["pipeline"] == "warm_restart"
    # 100% ladder coverage from the store, nothing recompiled.
    assert rec["compile_cache_hits"] == rec["ladder_size"]
    assert rec["compile_cache_rejects"] == rec["ladder_size"]
    assert rec["warm_pct"] == 100.0
    assert rec["criteria"]["zero_runtime_compiles"] is True
    assert rec["criteria"]["bit_identical"] is True
    assert rec["schema_problems"] == []
    assert rec["ok"] is True
    assert rec["source"] == "measured" and rec["backend"] == "cpu"


def test_bench_serve_traffic_smoke(bench_env, monkeypatch):
    """--bench=serve_traffic on the CPU backend: ONE JSON line with the
    gateway acceptance metrics — per-rung usage, padding-waste %, batch
    occupancy, p50/p95 latency — and gateway-batched transcripts
    bit-identical to per-request decoding."""
    monkeypatch.setenv(
        "BENCH_OVERRIDES",
        "model.rnn_hidden=32 model.rnn_layers=1 model.conv_channels=4,4 "
        "model.dtype=float32 data.bucket_frames=64,128 data.batch_size=4")
    monkeypatch.setenv("BENCH_REQUESTS", "12")
    monkeypatch.setenv("BENCH_RPS", "300")
    monkeypatch.setenv("BENCH_DEADLINE_MS", "20")
    tel_path = bench_env / "serving_telemetry.jsonl"
    monkeypatch.setenv("BENCH_TELEMETRY_FILE", str(tel_path))
    bench = _load_bench()
    out = io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    bench.main(["--bench=serve_traffic"])
    lines = [l for l in out.getvalue().splitlines() if l.strip()]
    assert len(lines) == 1, lines
    rec = json.loads(lines[0])
    assert rec["metric"] == "serve_p95_latency_ms"
    assert rec["pipeline"] == "serve_traffic"
    assert rec["completed"] + rec["rejected"] + rec["timeouts"] \
        + rec["errors"] == 12
    assert rec["completed"] > 0
    assert rec["latency_p50_ms"] > 0
    assert rec["latency_p95_ms"] >= rec["latency_p50_ms"]
    assert 0 < rec["batch_occupancy_mean"] <= 1
    assert 0 <= rec["padding_waste_pct"] < 100
    assert rec["per_rung"]  # at least one (B, T) rung dispatched
    # The acceptance criterion: gateway batching never changes text.
    assert rec["bit_identical"] is True and rec["mismatches"] == 0
    assert rec["source"] == "measured" and rec["backend"] == "cpu"
    # Request tracing: every finished request left a flight-recorder
    # summary whose phase ledger telescopes to the measured latency.
    assert rec["traces_recorded"] == rec["completed"] + rec["timeouts"] \
        + rec["errors"]
    assert rec["trace_complete_pct"] == 100.0
    # The latency histogram's extreme sample names its request.
    assert isinstance(rec["latency_max_exemplar"], str)
    assert rec["latency_max_exemplar"].strip()
    # The embedded SLO chaos leg: forced breach -> fast page with
    # slowest-request evidence -> brownout -> recovery, endpoints live.
    chaos = rec["slo_chaos"]
    assert chaos["alert_fired_fast"] is True
    assert chaos["alert_fired_while_breaching"] is True
    assert chaos["postmortem_has_slowest"] is True
    assert chaos["brownout_engaged"] is True
    assert chaos["brownout_recovered"] is True
    assert chaos["alert_rearmed_fast"] is True
    assert chaos["status_endpoints_ok"] is True
    # The raw telemetry snapshot landed as consumable JSONL.
    tel = [json.loads(l) for l in
           tel_path.read_text().splitlines() if l.strip()]
    assert len(tel) == 1 and tel[0]["event"] == "serving_telemetry"
    assert tel[0]["per_rung"] == rec["per_rung"]


def test_bench_serve_traffic_two_replicas(bench_env, monkeypatch):
    """--bench=serve_traffic with BENCH_REPLICAS=2: the ISSUE-6
    acceptance bundle in one run — bit-identical transcripts across
    routing choices (pinned / spilled / single-replica baseline),
    >= 1.6x aggregate throughput on the synthetic pipeline, zero lost
    requests despite a forced mid-replay breaker-open, a streaming
    re-pin with every session finalized, and per-replica
    occupancy/latency in the output."""
    monkeypatch.setenv(
        "BENCH_OVERRIDES",
        "model.rnn_hidden=32 model.rnn_layers=1 model.conv_channels=4,4 "
        "model.dtype=float32 data.bucket_frames=64,128 data.batch_size=4")
    monkeypatch.setenv("BENCH_REQUESTS", "10")
    monkeypatch.setenv("BENCH_RPS", "300")
    monkeypatch.setenv("BENCH_DEADLINE_MS", "20")
    monkeypatch.setenv("BENCH_STREAMS", "2")
    monkeypatch.setenv("BENCH_REPLICAS", "2")
    tel_path = bench_env / "pooled_telemetry.jsonl"
    monkeypatch.setenv("BENCH_TELEMETRY_FILE", str(tel_path))
    bench = _load_bench()
    out = io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    bench.main(["--bench=serve_traffic"])
    lines = [l for l in out.getvalue().splitlines() if l.strip()]
    assert len(lines) == 1, lines
    rec = json.loads(lines[0])
    assert rec["replicas"] == 2
    assert rec["completed"] + rec["rejected"] + rec["timeouts"] \
        + rec["errors"] == 10
    # Bit-identity across routing choices.
    assert rec["bit_identical"] is True and rec["mismatches"] == 0
    assert rec["cross_replica_identical"] is True
    # The chaos invariant pool-wide: a forced breaker-open mid-replay
    # loses nothing.
    assert rec["breaker_opens"] >= 1
    assert rec["lost"] == 0 and rec["zero_lost"] is True
    # Synthetic-pipeline scaling: >= 1.6x at 2 replicas.
    assert rec["synthetic_speedup"] >= 1.6 and rec["scaling_ok"] is True
    # Streaming re-pin: sessions moved off the tripped home replica
    # and every one of them still finalized (no lost chunks).
    assert rec["session_repins"] >= 1
    assert rec["repin_finals_ok"] is True
    # Per-replica breakdown present for every pool member, and the
    # replay's dispatches are attributed to labeled series only.
    assert set(rec["per_replica"]) == {"r0", "r1"}
    total_rows = sum(v["rows"] for v in rec["per_replica"].values())
    assert total_rows >= rec["completed"]
    # Grow events rode along from the pooled session managers.
    assert rec["session_grows"] >= 1
    assert len(rec["session_grow_events"]) == rec["session_grows"]
    # The telemetry snapshot passes the shared obs schema lint,
    # replica labels included (no mixed families).
    sys.path.insert(0, os.path.join(os.path.dirname(_BENCH), "tools"))
    import check_obs_schema
    problems = check_obs_schema.scan(
        tel_path.read_text().splitlines())
    assert problems == [], problems


def test_bench_chaos_traffic_smoke(bench_env, monkeypatch):
    """--bench=chaos_traffic under a deterministic fault plan: three
    fault kinds actually fire, the breaker opens and recovers, the torn
    checkpoint falls back to the intact step, and despite all of it no
    admitted request is lost and transcripts stay bit-identical.

    The plan is pinned (prob=1.0 error burst + a 350 ms unavailable
    window + one torn checkpoint write) so the assertions don't ride a
    seeded coin flip."""
    monkeypatch.setenv(
        "BENCH_OVERRIDES",
        "model.rnn_hidden=32 model.rnn_layers=1 model.conv_channels=4,4 "
        "model.dtype=float32 data.bucket_frames=64,128 data.batch_size=4")
    monkeypatch.setenv("BENCH_REQUESTS", "12")
    monkeypatch.setenv("BENCH_RPS", "300")
    plan_path = bench_env / "chaos_plan.json"
    plan_path.write_text(json.dumps({"seed": 0, "faults": [
        {"point": "gateway.dispatch", "kind": "error",
         "prob": 1.0, "count": 2, "message": "injected decode error"},
        {"point": "gateway.dispatch", "kind": "unavailable",
         "after_s": 0.0, "until_s": 0.35},
        {"point": "checkpoint.save", "kind": "partial_write", "count": 1},
    ]}))
    monkeypatch.setenv("BENCH_FAULT_PLAN", str(plan_path))
    bench = _load_bench()
    out = io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    bench.main(["--bench=chaos_traffic"])
    lines = [l for l in out.getvalue().splitlines() if l.strip()]
    assert len(lines) == 1, lines
    rec = json.loads(lines[0])
    assert rec["metric"] == "chaos_availability_pct"
    assert rec["pipeline"] == "chaos_traffic"
    assert rec["wall_capped"] is False
    # Acceptance: >=99% of admitted requests complete, none vanish.
    assert rec["value"] >= 99.0
    assert rec["lost"] == 0
    assert rec["admitted"] == rec["completed"]
    assert rec["completed"] + rec["rejected"] == 12
    # All three planned fault kinds demonstrably fired.
    assert set(rec["fault_kinds"]) == \
        {"error", "unavailable", "partial_write"}
    assert rec["retries"] > 0
    # The breaker tripped during the unavailable window and closed
    # again once probes started succeeding.
    assert rec["breaker_opens"] >= 1
    assert rec["breaker_recovered"] is True
    assert rec["breaker_recovery_s"] > 0
    # The torn write was detected and restore fell back to the intact
    # step (step 1, not the corrupted step 2).
    assert rec["checkpoint_fallbacks"] >= 1
    assert rec["checkpoint_fell_back_to_intact"] is True
    # Chaos must never change decoded text.
    assert rec["bit_identical"] is True and rec["mismatches"] == 0
    assert rec["source"] == "measured" and rec["backend"] == "cpu"


@pytest.mark.slow  # ~45 s: big-corpus native loader path (r5 durations)
def test_bench_manifest_native_pipeline_mode(bench_env, monkeypatch):
    """manifest_native forces the no-cache path (threaded C++ loader
    when built) and records the mode."""
    from deepspeech_tpu import native

    if not native.available():
        import pytest

        pytest.skip("native library not built")
    monkeypatch.setenv("BENCH_PIPELINE", "manifest_native")
    monkeypatch.setenv("BENCH_STEPS", "2")
    bench = _load_bench()
    out = io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    bench.main()
    rec = json.loads(out.getvalue().strip())
    assert rec["pipeline"] == "manifest_native" and rec["value"] > 0


def test_bench_train_chaos_smoke(bench_env, monkeypatch):
    """--bench=train_chaos on the CPU backend: the chaos plan fires a
    nan_grad plus a corrupt_batch mid-run, yet ONE JSON line reports a
    finished run — at least one skipped batch, one rollback, one
    quarantined sample, a finite final loss, and params bit-identical
    to the clean run over the same surviving batches."""
    monkeypatch.setenv(
        "BENCH_OVERRIDES",
        "model.rnn_hidden=96 model.rnn_layers=1 model.conv_channels=8,8 "
        "model.dtype=float32 data.batch_size=8 data.bucket_frames=64 "
        "data.max_label_len=16 train.warmup_steps=20")
    bench = _load_bench()
    out = io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    bench.main(["--bench=train_chaos"])
    lines = [l for l in out.getvalue().splitlines() if l.strip()]
    assert len(lines) == 1, lines
    rec = json.loads(lines[0])
    assert rec["metric"] == "train_chaos_steps_survived"
    assert rec["pipeline"] == "train_chaos"
    assert rec["unhandled_exception"] is None
    assert rec["faults_fired"] >= 3
    assert rec["skipped_batches"] >= 1
    assert rec["rollbacks"] >= 1
    assert rec["samples_quarantined"] >= 1
    assert rec["postmortems_written"] >= rec["skipped_batches"]
    assert rec["final_loss_finite"] is True
    # The self-healing acceptance bar: recovery must be exact, not
    # approximate — the surviving-batch replay reproduces the chaos
    # run's params bit for bit.
    assert rec["bit_identical"] is True
    assert rec["source"] == "measured" and rec["backend"] == "cpu"


def test_bench_quant_serving_smoke(bench_env, monkeypatch):
    """--bench=quant_serving on the CPU backend: ONE JSON line proving
    the int8-tier acceptance legs — WER delta inside the guardrail,
    int8 ladder strictly taller than bf16 under the same budget,
    mixed-tier traffic bit-identical per tier to single-tier decodes,
    and quantization exactly once per replica."""
    monkeypatch.setenv(
        "BENCH_OVERRIDES",
        "model.rnn_hidden=32 model.rnn_layers=1 model.conv_channels=4,4 "
        "model.dtype=float32 model.rnn_impl=pallas "
        "data.bucket_frames=64,128 data.batch_size=4")
    monkeypatch.setenv("BENCH_REQUESTS", "12")
    monkeypatch.setenv("BENCH_RPS", "300")
    monkeypatch.setenv("BENCH_DEADLINE_MS", "20")
    tel_path = bench_env / "quant_telemetry.jsonl"
    monkeypatch.setenv("BENCH_TELEMETRY_FILE", str(tel_path))
    bench = _load_bench()
    out = io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    bench.main(["--bench=quant_serving"])
    lines = [l for l in out.getvalue().splitlines() if l.strip()]
    assert len(lines) == 1, lines
    rec = json.loads(lines[0])
    assert rec["metric"] == "quant_serving_wer_delta"
    assert rec["pipeline"] == "quant_serving"
    # (a) WER guardrail.
    assert rec["wer_delta_ok"] is True
    assert rec["value"] <= rec["wer_guardrail"]
    # (b) The HBM headroom -> throughput conversion: strictly taller
    # int8 rung under the identical synthetic budget.
    assert rec["ladder_ok"] is True
    assert rec["tier_max_batch"]["bulk"] > rec["tier_max_batch"]["premium"] > 0
    assert rec["bytes_after"] < rec["bytes_before"]
    assert rec["quantized_leaves"] > 0
    # (b') The streamed-bytes leg: charging s8 stream bytes instead of
    # the old fp working copy raises the flagship-geometry bulk rung,
    # and each replica's kernel regime is recorded (dev-slice H=32:
    # premium runs fp kernels, bulk the resident int8 kernel).
    assert rec["stream_ladder_ok"] is True
    assert (rec["stream_tier_max_batch"]["bulk"]
            > rec["stream_tier_max_batch_fp_copy"]["bulk"] > 0)
    assert rec["kernel_regime"] == {"r0": "fp", "r1": "resident-q"}
    # (c) Per-tier bit-identity against single-tier decodes.
    assert rec["tier_identical"] is True
    assert rec["tier_mismatches"] == {"premium": 0, "bulk": 0}
    # (d) Quantize once per int8 replica, never per request.
    assert rec["quantize_once"] is True and rec["quantize_calls"] == 1
    assert rec["ok"] is True
    # Both tiers actually served traffic, with tier-labeled latency
    # and SLO attainment in the output.
    assert rec["completed"]["premium"] > 0
    assert rec["completed"]["bulk"] > 0
    assert set(rec["latency_by_tier_ms"]) == {"premium", "bulk"}
    assert rec["slo_ok"] + rec["slo_miss"] > 0
    assert set(rec["slo_attainment_by_tier"]) <= {"premium", "bulk"}
    # The telemetry snapshot is schema-clean (tier family rule).
    sys.path.insert(0, os.path.join(os.path.dirname(_BENCH), "tools"))
    import check_obs_schema
    tel_lines = tel_path.read_text().splitlines()
    assert len([l for l in tel_lines if l.strip()]) == 1
    assert check_obs_schema.scan(tel_lines) == []


def test_bench_rolling_swap_smoke(bench_env, monkeypatch):
    """--bench=rolling_swap: the ISSUE-8 acceptance bundle in one run —
    a full-pool v1->v2 swap under live traffic + pinned streaming
    sessions reaches done with zero lost requests/chunks, 100%
    availability, and at most one re-pin per session; a forced canary
    regression rolls back bit-exactly with a postmortem; an injected
    rollout.swap fault leaves the pool fully routable on v1; and the
    version-labeled rollout metrics pass the obs schema lint."""
    monkeypatch.setenv(
        "BENCH_OVERRIDES",
        "model.rnn_hidden=32 model.rnn_layers=1 model.conv_channels=4,4 "
        "model.dtype=float32 data.bucket_frames=64,128 data.batch_size=4")
    monkeypatch.setenv("BENCH_REQUESTS", "8")
    monkeypatch.setenv("BENCH_RPS", "300")
    monkeypatch.setenv("BENCH_DEADLINE_MS", "20")
    monkeypatch.setenv("BENCH_STREAMS", "2")
    monkeypatch.setenv("BENCH_REPLICAS", "2")
    bench = _load_bench()
    out = io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    bench.main(["--bench=rolling_swap"])
    lines = [l for l in out.getvalue().splitlines() if l.strip()]
    assert len(lines) == 1, lines
    rec = json.loads(lines[0])
    assert rec["pipeline"] == "rolling_swap"
    assert rec["metric"] == "rolling_swap_availability_pct"
    # Leg 1: the accept path.
    assert rec["swap_ok"] is True and rec["swaps"] == 2
    assert rec["zero_lost"] is True and rec["lost"] == 0
    assert rec["zero_lost_chunks"] is True and rec["chunks_fed"] > 0
    assert rec["availability_ok"] is True
    assert rec["availability_pct"] == 100.0
    assert rec["max_session_repins"] <= 1 and rec["repins_ok"] is True
    assert rec["bit_identical"] is True and rec["finals_ok"] is True
    # Leg 2: forced canary regression -> bit-exact rollback.
    leg2 = rec["canary_leg"]
    assert leg2["rolled_back"] is True
    assert leg2["bit_exact_after_rollback"] is True
    assert leg2["versions_old"] is True
    assert leg2["candidate_parked"] is True
    assert leg2["postmortem_written"] is True
    # Leg 3: injected rollout.swap fault -> still routable on v1.
    leg3 = rec["fault_leg"]
    assert leg3["rolled_back"] is True
    assert leg3["routable_all"] is True and leg3["pool_serves"] is True
    assert leg3["versions_old"] is True
    # The version-labeled metric families pass the shared schema lint.
    assert rec["schema_ok"] is True and rec["schema_problems"] == []
    assert rec["ok"] is True


def test_bench_slo_chaos(bench_env, monkeypatch):
    """--bench=slo: the pure-host SLO burn-rate chaos proof. A forced
    breach (decode pinned at 4x the deadline) fires the fast-window
    page whose postmortem names the slowest requests, brownout pressure
    rises off the burn gauges until admissions shed, the status
    endpoints answer throughout, and recovery re-arms the alert and
    walks the brownout ladder back down. No model, no device — the
    whole timeline runs on a scripted clock."""
    bench = _load_bench()
    out = io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    bench.main(["--bench=slo"])
    lines = [l for l in out.getvalue().splitlines() if l.strip()]
    assert len(lines) == 1, lines
    rec = json.loads(lines[0])
    assert rec["metric"] == "slo_chaos_ok"
    assert rec["pipeline"] == "slo"
    assert rec["value"] is True
    # Healthy phase: burn stays under the page threshold.
    assert rec["burn_healthy_fast"] < 14.4
    # Breach phase: fast-window burn blows past it and pages ONCE
    # while the breach holds.
    assert rec["burn_peak_fast"] >= 14.4
    assert rec["alert_fired_fast"] is True
    assert rec["alert_fired_while_breaching"] is True
    assert rec["postmortem_has_slowest"] is True
    assert rec["postmortem_slowest_rids"]
    assert rec["postmortems_written"] >= 1
    # Burn-as-pressure: the gateway browned out and shed admissions.
    assert rec["brownout_level_peak"] >= 2
    assert rec["brownout_engaged"] is True
    assert rec["brownout_shed"] >= 1
    # Recovery: burn drains, the alert re-arms, the ladder descends.
    assert rec["brownout_recovered"] is True
    assert rec["alert_rearmed_fast"] is True
    # The live ops surface answered every poll across all phases.
    assert rec["status_endpoints_ok"] is True
    assert rec["status_polls"] >= 12
    assert rec["source"] == "measured"


def test_traffic_model_is_seed_deterministic():
    """The autoscale bench's load layer must replay bit-identically:
    same seed -> the same arrivals, lengths, and session plans; a
    different seed -> a different schedule."""
    from deepspeech_tpu.serving import TrafficModel

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


def test_bench_autoscale_smoke(bench_env, monkeypatch):
    """--bench=autoscale: the closed-loop acceptance — the controller
    scales up under the modeled burst and back down in the trough,
    loses nothing, re-pins each session at most once per resize, and
    beats the peak-sized static fleet on replica-seconds at equal or
    better SLO attainment. ONE JSON line; ok=False exits nonzero."""
    tel_path = bench_env / "autoscale_telemetry.jsonl"
    monkeypatch.setenv("BENCH_TELEMETRY_FILE", str(tel_path))
    bench = _load_bench()
    out = io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    bench.main(["--bench=autoscale"])
    lines = [l for l in out.getvalue().splitlines() if l.strip()]
    assert len(lines) == 1, lines
    rec = json.loads(lines[0])
    assert rec["metric"] == "autoscale_slo_attainment_pct"
    assert rec["pipeline"] == "autoscale"
    assert rec["ok"] is True
    assert all(rec["checks"].values()), rec["checks"]
    assert rec["scale_ups"] >= 1 and rec["scale_downs"] >= 1
    assert rec["fleet_peak"] > rec["fleet_min"]
    assert rec["lost"] == 0 and rec["lost_chunks"] == 0
    assert rec["completed"] + rec["rejected"] == rec["requests"]
    assert rec["max_repins_per_session"] <= max(rec["resizes"], 1)
    # The cost-vs-SLO tradeoff the subsystem exists for.
    assert rec["replica_seconds"] < rec["replica_seconds_static"]
    assert rec["replica_seconds_saved_pct"] > 0
    assert rec["slo_attainment_pct"] >= rec["slo_attainment_static_pct"]
    # Every episode is direction-tagged with fleet before/after.
    for ep in rec["episodes"]:
        assert ep["direction"] in ("up", "down")
        assert abs(ep["from_replicas"] - ep["to_replicas"]) == 1
    # The traffic header proves the deterministic load layer drove it.
    assert rec["traffic"]["seed"] == 0
    assert rec["traffic"]["peak_rps"] > rec["traffic"]["trough_rps"]
    assert rec["schema_ok"] is True
    assert rec["source"] == "measured" and rec["backend"] == "cpu"
    # The autoscaled leg's telemetry snapshot landed as JSONL and the
    # obs lint accepts it (directional autoscale_events included).
    tel = [json.loads(l) for l in
           tel_path.read_text().splitlines() if l.strip()]
    assert len(tel) == 1 and tel[0]["event"] == "serving_telemetry"
    assert any(k.startswith("autoscale_events{")
               for k in tel[0]["counters"])
    sys.path.insert(0, os.path.join(os.path.dirname(_BENCH), "tools"))
    try:
        import check_obs_schema
    finally:
        sys.path.pop(0)
    assert check_obs_schema.scan(
        [l for l in tel_path.read_text().splitlines() if l.strip()]) == []


def test_bench_migration_smoke(bench_env, monkeypatch):
    """--bench=migration: forced mass re-pins over real tiny
    streaming models, drain baseline vs the snapshot/handoff plane —
    bit-identical migrated transcripts (greedy AND beam), single
    segment on the handoff path, p95 chunk latency strictly below the
    drain baseline, exactly one migration per session per topology
    change, schema-linted stream. ONE JSON line; ok=False exits
    nonzero."""
    tel_path = bench_env / "migration_telemetry.jsonl"
    monkeypatch.setenv("BENCH_TELEMETRY_FILE", str(tel_path))
    monkeypatch.setenv("BENCH_MIG_SESSIONS", "2")
    monkeypatch.setenv("BENCH_MIG_TRIPS", "2")
    monkeypatch.setenv("BENCH_MIG_STEPS", "5")
    bench = _load_bench()
    out = io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    bench.main(["--bench=migration"])
    lines = [l for l in out.getvalue().splitlines() if l.strip()]
    assert len(lines) == 1, lines
    rec = json.loads(lines[0])
    assert rec["metric"] == "migration_chunk_p95_ms"
    assert rec["pipeline"] == "migration"
    assert rec["ok"] is True
    assert all(rec["checks"].values()), rec["checks"]
    # The headline tradeoff: the handoff path is strictly faster
    # through a forced mass re-pin than waiting out the drain.
    assert rec["p95_handoff_ms"] < rec["p95_drain_ms"]
    assert rec["drain_over_handoff"] > 1.0
    # Zero-loss is proven as bit-identity (greedy and beam legs).
    assert rec["checks"]["bit_identity_greedy"] is True
    assert rec["checks"]["bit_identity_beam"] is True
    # Segment accounting: handoff never splits, drain splits per trip.
    assert rec["segments_handoff"] == 1
    assert rec["segments_drain"] == rec["trips"] + 1
    # 2 sessions x 2 trips (greedy) + 2 beam sessions x 1 trip.
    assert rec["migrations"] == rec["sessions"] * rec["trips"] + 2
    assert rec["migration_fallbacks"] == 0
    assert rec["max_per_session"] == rec["trips"]
    assert rec["schema_ok"] is True
    assert rec["source"] == "measured" and rec["backend"] == "cpu"
    # The handoff legs' telemetry landed as JSONL with the migration
    # families and kind="migration" postmortems, and the lint is
    # clean end to end.
    tel = [json.loads(l) for l in
           tel_path.read_text().splitlines() if l.strip()]
    snap = next(r for r in tel if r["event"] == "serving_telemetry")
    assert any(k.startswith("session_migrations{")
               for k in snap["counters"])
    pms = [r for r in tel if r.get("event") == "postmortem"
           and r.get("kind") == "migration"]
    assert pms and all(p["outcome"] == "handoff" for p in pms)
    sys.path.insert(0, os.path.join(os.path.dirname(_BENCH), "tools"))
    try:
        import check_obs_schema
    finally:
        sys.path.pop(0)
    assert check_obs_schema.scan(
        [l for l in tel_path.read_text().splitlines() if l.strip()]) == []


def test_bench_incident_timeline_smoke(bench_env, monkeypatch):
    """--bench=incident_timeline: ONE JSON line proving the scripted
    fault day folds into exactly one resolved incident — root is the
    injected fault fire, the breaker/migration/vertical/drain-cancel
    reactions all join through causal edges (zero orphans), event
    counts are exact, the emitted streams pass the schema lint, and
    the offline incident_report replay reconstructs the same story."""
    bench = _load_bench()
    out = io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    bench.main(["--bench=incident_timeline"])
    lines = [l for l in out.getvalue().splitlines() if l.strip()]
    assert len(lines) == 1, lines
    rec = json.loads(lines[0])
    assert rec["metric"] == "incident_timeline"
    assert rec["value"] == 1.0 and rec["unit"] == "incidents"
    assert rec["one_incident"] is True
    assert rec["root_is_fault_fire"] is True
    assert rec["resolved_by_breaker_close"] is True
    assert rec["zero_orphans"] is True and rec["orphans"] == 0
    assert rec["exact_event_counts"] is True
    assert rec["event_counts"]["fault_fire"] == 2
    assert rec["event_counts"]["migration"] == rec["migrations"] >= 1
    assert rec["report_roundtrip"] is True
    assert rec["schema_ok"] is True
    assert rec["zero_lost_requests"] is True
    assert rec["zero_lost_chunks"] is True
    assert rec["ok"] is True
    assert rec["source"] == "measured" and rec["backend"] == "host"


def test_bench_crash_recovery_smoke(bench_env, monkeypatch):
    """--bench=crash_recovery: real tiny streaming models journaling
    every chunk, killed mid-stream, cold-restarted through
    RecoveryController — bit-identical greedy+beam continuation,
    every-byte-offset torn-tail fuzz, skew rejected and counted,
    bounded journal overhead, schema-linted streams. ONE JSON line;
    ok=False exits nonzero."""
    tel_path = bench_env / "crash_recovery_telemetry.jsonl"
    monkeypatch.setenv("BENCH_TELEMETRY_FILE", str(tel_path))
    monkeypatch.setenv("BENCH_CR_SESSIONS", "2")
    monkeypatch.setenv("BENCH_CR_STEPS", "4")
    bench = _load_bench()
    out = io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    bench.main(["--bench=crash_recovery"])
    lines = [l for l in out.getvalue().splitlines() if l.strip()]
    assert len(lines) == 1, lines
    rec = json.loads(lines[0])
    assert rec["metric"] == "crash_recovery_latency_ms"
    assert rec["pipeline"] == "crash_recovery"
    assert rec["ok"] is True
    assert all(rec["checks"].values()), rec["checks"]
    assert rec["checks"]["bit_identity_greedy"] is True
    assert rec["checks"]["bit_identity_beam"] is True
    assert rec["checks"]["torn_fuzz_never_aborts"] is True
    assert rec["fuzz_failures"] == 0 and rec["fuzz_offsets"] > 1000
    assert rec["checks"]["skew_zero_recovered"] is True
    assert rec["recovered"] == rec["sessions"]
    # 2 greedy sids x 2 pre-crash chunks, journaled every chunk.
    assert rec["journal_appends_precrash"] == 4
    assert rec["schema_ok"] is True
    assert rec["source"] == "measured" and rec["backend"] == "cpu"
    # Journal counters + the crash_recovery postmortems landed as
    # JSONL and the lint is clean end to end.
    tel = [json.loads(l) for l in
           tel_path.read_text().splitlines() if l.strip()]
    snap = next(r for r in tel if r["event"] == "serving_telemetry")
    assert int(snap["counters"].get("journal_appends", 0)) > 0
    assert any(k.startswith("sessions_recovered{")
               for k in snap["counters"])
    pms = [r for r in tel if r.get("event") == "postmortem"
           and r.get("kind") == "crash_recovery"]
    assert pms and all(p["trigger"] == "boot" for p in pms)
    sys.path.insert(0, os.path.join(os.path.dirname(_BENCH), "tools"))
    try:
        import check_obs_schema
    finally:
        sys.path.pop(0)
    assert check_obs_schema.scan(
        [l for l in tel_path.read_text().splitlines() if l.strip()]) == []


def test_bench_xhost_migration_smoke(bench_env, monkeypatch):
    """--bench=xhost_migration: live sessions snapshot onto the wire,
    cross a real loopback socket mid-stream, and finish bit-identical
    on the receiving process-boundary — with handshake skew failing
    fast to the local ladder, every-offset frame fuzz never raising,
    flapping send/ack legs recovered by retry + idempotent transfer
    ids, and an exhausted peer degrading to the local re-pin. ONE
    JSON line; telemetry lints clean."""
    tel_path = bench_env / "xhost_telemetry.jsonl"
    monkeypatch.setenv("BENCH_TELEMETRY_FILE", str(tel_path))
    monkeypatch.setenv("BENCH_XH_SESSIONS", "2")
    monkeypatch.setenv("BENCH_XH_STEPS", "4")
    bench = _load_bench()
    out = io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    bench.main(["--bench=xhost_migration"])
    lines = [l for l in out.getvalue().splitlines() if l.strip()]
    assert len(lines) == 1, lines
    rec = json.loads(lines[0])
    assert rec["metric"] == "xhost_migration_latency_ms"
    assert rec["pipeline"] == "xhost_migration"
    assert rec["ok"] is True
    assert all(rec["checks"].values()), rec["checks"]
    assert rec["checks"]["bit_identity_socket_greedy"] is True
    assert rec["checks"]["bit_identity_socket_beam"] is True
    assert rec["checks"]["handshake_fail_fast_local"] is True
    assert rec["checks"]["torn_fuzz_never_raises"] is True
    assert rec["checks"]["flap_ack_duplicate_once"] is True
    assert rec["checks"]["crash_recovers_all"] is True
    # 2 greedy + 2 beam sids, each run over loopback AND socket.
    assert rec["transfers_remote"] == rec["sessions"] == 8
    assert rec["fuzz_failures"] == 0 and rec["fuzz_cases"] > 50
    assert rec["recovered_after_crash"] >= 1
    assert rec["p95_handoff_ms"] >= rec["p50_handoff_ms"] > 0
    assert rec["schema_ok"] is True
    assert rec["source"] == "measured" and rec["backend"] == "cpu"
    tel = [json.loads(l) for l in
           tel_path.read_text().splitlines() if l.strip()]
    snap = next(r for r in tel if r["event"] == "serving_telemetry")
    assert any(k.startswith("session_migrations{")
               and 'replica="peer:' in k for k in snap["counters"])
    assert any(k.startswith("session_migration_fallbacks{")
               for k in snap["counters"])
    pms = [r for r in tel if r.get("event") == "postmortem"
           and r.get("kind") == "migration"]
    assert any(p["outcome"] == "remote_handoff" for p in pms)
    assert any(p["outcome"] == "fallback_local" for p in pms)
    sys.path.insert(0, os.path.join(os.path.dirname(_BENCH), "tools"))
    try:
        import check_obs_schema
    finally:
        sys.path.pop(0)
    assert check_obs_schema.scan(
        [l for l in tel_path.read_text().splitlines() if l.strip()]) == []
