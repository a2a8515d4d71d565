"""The shared readers and ``sala_*`` on a hand-made record of the
driver ``transcribe_sparse`` (``minicpm_sala``'s cell): the program's
counters of one served call (32 streams, prefixes of 12,000 positions,
100 steps), its spans, and the device events of its four named kernels;
each new reader a value where the facts are there, None where they are
not."""

import importlib

import numpy as np
import pytest

import conftest  # noqa: F401  (puts the checkout on sys.path)
from benchmark.costs import minicpm_sala as costs
from test_kernel_metrics import event

STREAMS, FRAMES, STEPS = 32, 96000, 100
NEW = ("sala_select_ms", "sala_sparse_decode_ms",
       "sala_sparse_decode_hbm_pct", "sala_sparse_prefill_ms",
       "sala_sparse_prefill_mfu_pct", "sala_rows_read_pct",
       "sala_linear_prefill_ms", "sala_linear_prefill_roofline",
       "sala_linear_step_ms", "sala_state_bytes_pct",
       "sala_mechanism_bytes_pct")


def model():
    from deepspeech_tpu.config import get_config

    return get_config("minicpm_sala").model


def call(idle=0):
    m = model()
    pos = 12000 + np.arange(STEPS)
    live = STREAMS * STEPS - idle
    read = STREAMS * int(costs.rows_selected(m, pos).sum())
    held = STREAMS * int((pos + 1).sum())
    ranked = STREAMS * int(costs.windows_ranked(m, pos).sum())
    return {"prefill": {"valid_positions": STREAMS * 12000,
                        "padded_positions": STREAMS * 3000},
            "decode": {"valid_positions": live, "padded_positions": idle},
            "decode_steps": STEPS, "idle_slot_steps": idle,
            "rows": STREAMS, "state_updates": 3 * live,
            "select_rows_read": read, "select_rows_held": held,
            "select_windows_read": ranked, "pooled_key_writes": 6 * STREAMS,
            "cache_rows_read": read,
            "decode_bytes": {"weights": STEPS * 2 * costs.position_params(m),
                             "head": STEPS * 2 * 4096 * 73448,
                             "state": 3 * live * 2 * 2_097_152,
                             "rows": read * 1024, "select": ranked * 512},
            "dropped_pairs": 0, "valid_frames": [FRAMES] * STREAMS,
            "max_tokens": [STEPS] * STREAMS}


def record():
    spans = [("infer.transcribe", 10.0, 15.9)]
    spans += [("infer.prefill", 10.0 + 0.3 * i, 10.0 + 0.3 * i + 0.28)
              for i in range(16)]
    spans.append(("infer.decode", 14.9, 15.8))
    spans += [("pipeline.data_wait", 9.9, 9.95)]
    fwd = event("gqa_attn_select_fwd.3", "bf16[2,2,16,15000,128]",
                {"kernel": "gqa_attn_select_fwd", "b": 2, "s": 15000})
    dec = event("gqa_attn_select_decode.4", "bf16[32,2,16,128]",
                {"kernel": "gqa_attn_select_decode", "b": 32})
    scan = event("ssd_chunk_scan.5",
                 "(bf16[2,15104,4096], f32[2,32,128,128])",
                 {"kernel": "ssd_chunk_scan", "b": 2, "s": 15000})
    step = event("ssd_state_step.7", "(f32[32,32,128], f32[32,32,128,128])",
                 {"kernel": "ssd_state_step", "b": 32, "group_block": 8})
    kernels = ([(fwd, 0.05)] * 16 + [(scan, 0.004)] * (16 * 3)
               + [(dec, 0.0008)] * STEPS + [(step, 0.0004)] * (STEPS * 3))
    return {
        "driver": "transcribe_sparse", "model": model(),
        "units": 1, "chips": 1,
        "t_window_start": 10.0, "t_window_end": 16.0,
        "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        "spans": spans,
        "counters": {
            "rows_per_call": STREAMS, "bucket_frames": 120000,
            "num_features": 161, "cache_rows": 19328, "prefill_rows": 2,
            "cache_bytes": 854458368, "cache_bytes_state": 201326592,
            "calls": [call(idle=320)]},
        "trace": {"op_seconds": {}, "kernels": kernels, "busy_s": 5.9},
    }


def read(name, rec):
    return importlib.import_module(
        f"benchmark.layer_metrics.{name}").read(rec)


def test_shared_readers_on_the_record():
    rec = record()
    m = rec["model"]
    assert read("call_ms", rec) == pytest.approx(5900.0)
    assert read("prefill_ms", rec) == pytest.approx(280.0)
    assert read("decode_step_ms", rec) == pytest.approx(9.0)
    assert read("cache_gb", rec) == pytest.approx(0.854458368)
    assert read("idle_slot_pct", rec) == pytest.approx(10.0)
    assert read("pad_position_pct", rec) == pytest.approx(
        100 * (STREAMS * 3000 + 320) / (STREAMS * 15000 + STREAMS * STEPS))
    assert read("unnamed_kernel_calls", rec) == 0
    flops = costs.call_flops_valid(m, [FRAMES] * STREAMS, [STEPS] * STREAMS)
    assert read("mfu_pct", rec) == pytest.approx(100 * flops / 6.0 / 197e12)
    assert 0 < read("mfu_pct", rec) < 100
    needed = sum(rec["counters"]["calls"][0]["decode_bytes"].values())
    assert read("decode_hbm_pct", rec) == pytest.approx(
        100 * needed / (0.9 * 819e9))
    assert 0 < read("decode_hbm_pct", rec) < 100


def test_the_new_readers_on_the_record():
    rec = record()
    m = rec["model"]
    c = rec["counters"]["calls"][0]
    assert read("sala_sparse_prefill_ms", rec) == pytest.approx(800.0)
    assert read("sala_sparse_decode_ms", rec) == pytest.approx(80.0)
    assert read("sala_linear_prefill_ms", rec) == pytest.approx(192.0)
    assert read("sala_linear_step_ms", rec) == pytest.approx(120.0)
    assert read("sala_sparse_decode_hbm_pct", rec) == pytest.approx(
        100 * c["select_rows_read"] * 1024 / (0.08 * 819e9))
    assert read("sala_sparse_prefill_mfu_pct", rec) == pytest.approx(
        100 * costs.prefill_select_flops(m, [FRAMES] * STREAMS)
        / (0.8 * 197e12))
    assert read("sala_rows_read_pct", rec) == pytest.approx(
        100 * c["select_rows_read"] / c["select_rows_held"])
    assert 45 < read("sala_rows_read_pct", rec) < 52     # 6.2 k of 12 k
    flops, moved = costs.prefill_scan_cost(m, [FRAMES] * STREAMS)
    least = max(flops / 197e12, moved / 819e9)
    assert read("sala_linear_prefill_roofline", rec) == pytest.approx(
        100 * least / 0.192)
    total = sum(c["decode_bytes"].values())
    assert read("sala_state_bytes_pct", rec) == pytest.approx(
        100 * c["decode_bytes"]["state"] / total)
    assert read("sala_mechanism_bytes_pct", rec) == pytest.approx(
        100 * (c["decode_bytes"]["state"] + c["decode_bytes"]["rows"]
               + c["decode_bytes"]["select"]) / total)
    # no share of a peak passes 100
    for name in NEW:
        if name.endswith(("_pct", "_roofline")):
            assert 0 < read(name, rec) < 100, name


def test_select_ms_reads_the_layer_table():
    """``sala_select_ms`` is the program's layer table's
    (``_layers.py``): a value where the record stores a table that
    holds the scope, None without a trace or a table."""
    assert read("sala_select_ms", record()) is None      # no table
    rec = record()
    rec["counters"]["layer_table"] = {"lm_decode": {
        "%fusion.1": ["jit(_decode)/while/body/layer0/sparse/"
                      "sparse_select/top_k", "f32[32,2,97]", "fusion"],
        "%fusion.2": ["jit(_decode)/while/body/layer0/ffn/w1/dot_general",
                      "bf16[32,16384]", "fusion"]}}
    rec["trace"]["op_seconds"] = {"%fusion.1 fusion f32[32,2,97]": 0.25,
                                  "%fusion.2 fusion bf16[32,16384]": 1.0}
    assert read("sala_select_ms", rec) == pytest.approx(250.0)


def test_select_ms_is_silent_on_a_program_without_the_layers_name(
        monkeypatch):
    """The parent's ``obs.layers.check`` raises for a name it lacks (the
    driver lays this PR's readers over the parent's checkout, and
    ``--detail`` asks every reader of every record): None, no error."""
    from deepspeech_tpu.obs import layers

    def parents(name):
        if name == "sparse_select":
            raise ValueError(f"{name!r} is not a layer")
        return name

    monkeypatch.setattr(layers, "check", parents)
    rec = record()
    rec["counters"]["layer_table"] = {"lm_decode": {
        "%fusion.2": ["jit(_decode)/while/body/layer0/ffn/w1/dot_general",
                      "bf16[32,16384]", "fusion"]}}
    rec["trace"]["op_seconds"] = {"%fusion.2 fusion bf16[32,16384]": 1.0}
    assert read("sala_select_ms", rec) is None


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_is_silent_where_the_facts_are_not(name):
    """Another driver's record, a program without a selection (no
    ``select_rows_read``), an untraced run: None, never 0 and never an
    exception. (The two kernels' own times are read by the kernel's
    name from any record, as ``falcon_ssd_step_ms`` is.)"""
    by_name = name in ("sala_sparse_decode_ms", "sala_sparse_prefill_ms")
    rec = record()
    rec["driver"] = "transcribe_hybrid"
    assert (read(name, rec) is None) != by_name
    rec = record()
    for c in rec["counters"]["calls"]:
        del c["select_rows_read"]
    assert (read(name, rec) is None) != by_name
    rec = record()
    rec["trace"]["kernels"] = []
    if name.endswith("_ms") or "hbm" in name or "mfu" in name \
            or "roofline" in name:
        assert read(name, rec) is None              # the kernel never ran
    rec = record()
    rec["trace"] = None
    if name in ("sala_rows_read_pct", "sala_state_bytes_pct",
                "sala_mechanism_bytes_pct"):
        assert read(name, rec) is not None          # counters alone
    else:
        assert read(name, rec) is None
    rec = record()
    rec["counters"]["calls"] = []
    assert (read(name, rec) is None) != by_name


def test_the_cells_entries_are_in_the_file():
    """All eleven have an entry (the accepted ``test_by_driver.py``
    holds the list to 80 and every entry's name to a reader file's, so
    no ``<reader>.sala`` join: the shared readers reach this cell
    through ``--detail``). ISSUE 54's twelfth, ``ssd_state_step``'s
    share of the HBM peak, is not a reader: XLA keeps two of the three
    layers' states in VMEM across the loop, so the kernel moves more
    than HBM could (it read 142%)."""
    import json
    import os

    from benchmark import harness

    with open(os.path.join(conftest.ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    cell = "minicpm_sala.transcribe_long_20min_b32"
    mine = [m["name"] for m in b["per_layer"]
            if m.get("workloads") == [cell]]
    assert sorted(mine) == sorted(NEW)
    assert len(b["per_layer"]) == 80
    assert set(NEW) <= set(harness.every_reader())
