"""The ``falcon_*`` readers on a hand-made record: the program's
counters of two served calls of the hybrid recogniser (128 streams,
prefixes of 200 positions, 50 steps), its spans, and the device events
of its three named kernels."""

import importlib
import json

import pytest

import conftest  # noqa: F401  (puts the checkout on sys.path)
from benchmark.costs import falcon_h1
from test_kernel_metrics import event

STREAMS, FRAMES, STEPS = 128, 1600, 50
STATE = 2 * 4 * 4096 * 256          # a state read once and written once


def call(idle=0):
    """128 streams of 1,600 valid frames (200 prefix positions) and 50
    tokens, ``idle`` slot-steps of finished streams."""
    live = STREAMS * STEPS - idle
    rows = 6 * STREAMS * sum(200 + j + 1 for j in range(STEPS))
    return {"prefill": {"valid_positions": STREAMS * 200,
                        "padded_positions": STREAMS * 12},
            "decode": {"valid_positions": live, "padded_positions": idle},
            "decode_steps": STEPS, "idle_slot_steps": idle,
            "rows": STREAMS, "state_updates": 6 * live,
            "rows_attended_global": rows, "cache_rows_read": rows,
            "decode_bytes": {"weights": STEPS * 5161440384,
                             "head": STEPS * 2673868800,
                             "state": 6 * live * STATE,
                             "rows": rows * 2048},
            "dropped_pairs": 0, "valid_frames": [FRAMES] * STREAMS,
            "max_tokens": [STEPS] * STREAMS}


def record():
    from deepspeech_tpu.config import get_config

    spans = []
    for t0 in (10.0, 13.0):
        spans.append(("infer.transcribe", t0, t0 + 2.9))
        spans += [("infer.prefill", t0 + 0.3 * i, t0 + 0.3 * i + 0.28)
                  for i in range(4)]
        spans.append(("infer.decode", t0 + 1.3, t0 + 2.8))
    spans += [("pipeline.data_wait", 12.9, 12.95),
              ("pipeline.device_prefetch", 12.95, 13.0)]
    scan = event("ssd_chunk_scan.5", "(bf16[32,256,4096], f32[32,32,256,128])",
                 {"kernel": "ssd_chunk_scan", "b": 32, "s": 212})
    step = event("ssd_state_step.7", "(f32[128,32,128], f32[128,32,256,128])",
                 {"kernel": "ssd_state_step", "b": 128})
    attn = event("gqa_attn_decode.9", "bf16[128,4,5,128]",
                 {"kernel": "gqa_attn_decode", "b": 128})
    kernels = ([(scan, 0.003)] * (2 * 4 * 6)
               + [(step, 0.0016)] * (2 * STEPS * 6)
               + [(attn, 0.0002)] * (2 * STEPS * 6))
    return {
        "driver": "transcribe_hybrid",
        "model": get_config("falcon_h1_34b").model,
        "units": 2, "chips": 1,
        "t_window_start": 10.0, "t_window_end": 16.0,
        "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        "spans": spans,
        "counters": {
            "rows_per_call": STREAMS, "bucket_frames": 1696,
            "num_features": 161, "cache_rows": 288, "prefill_rows": 32,
            "cache_bytes": 3697803264, "cache_bytes_state": 3221225472,
            "calls": [call(), call(idle=640)]},
        "trace": {"op_seconds": {}, "kernels": kernels, "busy_s": 5.9},
    }


def read(name, rec):
    return importlib.import_module(
        f"benchmark.layer_metrics.{name}").read(rec)


def test_readers_on_the_record():
    rec = record()
    m = rec["model"]
    assert read("falcon_call_ms", rec) == pytest.approx(2900.0)
    assert read("falcon_prefill_ms", rec) == pytest.approx(280.0)
    assert read("falcon_decode_step_ms", rec) == pytest.approx(30.0)
    assert read("falcon_ssd_prefill_ms", rec) == pytest.approx(72.0)
    assert read("falcon_ssd_step_ms", rec) == pytest.approx(480.0)
    assert read("falcon_attn_decode_ms", rec) == pytest.approx(60.0)
    assert read("falcon_state_gb", rec) == pytest.approx(3.221225472)
    assert read("falcon_cache_gb", rec) == pytest.approx(3.697803264)
    assert read("falcon_input_wait_pct", rec) == pytest.approx(
        100 * 0.1 / 6.0)
    assert read("falcon_idle_slot_pct", rec) == pytest.approx(
        100 * 640 / (2 * STREAMS * STEPS))
    assert read("falcon_pad_position_pct", rec) == pytest.approx(
        100 * (2 * STREAMS * 12 + 640)
        / (2 * STREAMS * 212 + 2 * STREAMS * STEPS))
    assert read("falcon_unnamed_kernel_calls", rec) == 0
    flops = 2 * falcon_h1.call_flops_valid(m, [FRAMES] * STREAMS,
                                           [STEPS] * STREAMS)
    assert read("falcon_mfu_pct", rec) == pytest.approx(
        100 * flops / 6.0 / 197e12)
    assert 0 < read("falcon_mfu_pct", rec) < 100
    calls = rec["counters"]["calls"]
    needed = sum(sum(c["decode_bytes"].values()) for c in calls)
    assert read("falcon_decode_hbm_pct", rec) == pytest.approx(
        100 * needed / (3.0 * 819e9))
    assert 0 < read("falcon_decode_hbm_pct", rec) < 100
    state = sum(c["decode_bytes"]["state"] for c in calls)
    assert read("falcon_state_bytes_pct", rec) == pytest.approx(
        100 * state / needed)
    assert 35 < read("falcon_state_bytes_pct", rec) < 50
    # the step kernel against the states the live streams moved
    live = 6 * (2 * STREAMS * STEPS - 640)
    assert read("falcon_ssd_step_hbm_pct", rec) == pytest.approx(
        100 * live * STATE / (2 * STEPS * 6 * 0.0016 * 819e9))
    assert 0 < read("falcon_ssd_step_hbm_pct", rec) < 100
    # the scan kernel against its roofline on the valid positions
    f, b = falcon_h1.prefill_scan_cost(m, [FRAMES] * STREAMS)
    least = max(2 * f / 197e12, 2 * b / 819e9)
    assert read("falcon_ssd_prefill_roofline", rec) == pytest.approx(
        100 * least / (2 * 4 * 6 * 0.003))
    assert 0 < read("falcon_ssd_prefill_roofline", rec) < 100


def test_a_record_without_the_state_reads_nothing():
    """The parent's program, or another driver's record: no
    ``state_updates``, no kernel of these names."""
    from benchmark import harness

    rec = record()
    for c in rec["counters"]["calls"]:
        del c["state_updates"]
    for name in ("falcon_call_ms", "falcon_decode_hbm_pct",
                 "falcon_state_bytes_pct", "falcon_ssd_step_hbm_pct",
                 "falcon_ssd_prefill_roofline", "falcon_state_gb",
                 "falcon_idle_slot_pct", "falcon_mfu_pct"):
        assert read(name, rec) is None, name
    rec = record()
    rec["driver"] = "transcribe_long"
    for name in ("falcon_call_ms", "falcon_unnamed_kernel_calls",
                 "falcon_setup_trace_lower_s", "falcon_input_wait_pct"):
        assert harness.metric_value({"name": name}, rec,
                                    traced=True) is None
    rec = record()
    for name in ("trinity_call_ms", "axk1_call_ms", "unnamed_kernel_calls",
                 "setup_trace_lower_s", "host_turn_ms"):
        assert harness.metric_value({"name": name}, rec,
                                    traced=True) is None


def test_the_twins_of_the_generic_readers():
    rec = record()
    bare = event("custom-call.3", "bf16[128,6144]", {})
    rec["trace"]["kernels"] += [(bare, 0.001)] * 2
    rec["spans"] += [("jax.trace", 8.0, 8.5), ("jax.lower", 8.4, 9.0)]
    assert read("falcon_unnamed_kernel_calls", rec) == 2
    assert read("falcon_setup_trace_lower_s", rec) == pytest.approx(1.0)


def test_every_reader_has_its_entry_and_its_cell():
    import os

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]
               if m["name"].startswith("falcon_")}
    files = {f[:-3] for f in os.listdir(os.path.join(
        root, "benchmark", "layer_metrics")) if f.startswith("falcon_")}
    # BENCHMARK.json holds 128 per-layer metrics at most and had 120:
    # eight of the eighteen readers have an entry, the others are read
    # by ``--detail`` (``every_metric``)
    assert set(entries) < files and len(files) == 18
    assert sorted(entries) == [
        "falcon_call_ms", "falcon_decode_hbm_pct", "falcon_decode_step_ms",
        "falcon_mfu_pct", "falcon_ssd_prefill_roofline",
        "falcon_ssd_step_hbm_pct", "falcon_state_bytes_pct",
        "falcon_unnamed_kernel_calls"]
    assert len(bench["per_layer"]) == 128
    assert all(m["workloads"] == ["falcon_h1_34b.transcribe_16s"]
               for m in entries.values())
