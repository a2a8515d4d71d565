"""The ``trinity_*`` readers on a hand-made record: short names as
``reduce/xplane.short_name`` gives them for the cell's two compiled
programs (fusion results of my compile for a v5e, PR 41: prefill
sub-batches of 2 x 5,250 positions in query blocks of 512, decode steps
of 16 streams against rings of 4,096 rows and a full cache of 6,784;
10,752 and 128 static rows), the program's counters of two calls."""

import importlib

import pytest

import conftest  # noqa: F401  (puts the checkout on sys.path)
from benchmark.costs import trinity
from benchmark.layer_metrics import _trinity
from benchmark.layer_metrics._rnnt import parse

ATTN_PREFILL = [
    "%fusion.1 fusion f32[2,8,6,512,4607]",               # scores, a block
    "%fusion.2 fusion (f32[2,8,6,512], f32[2,8,6,512,4607])",
    "%fusion.3 fusion (f32[2,8,6,130], f32[2,8,6,130,4225])",
    "%fusion.4 fusion f32[2,8,6,512,3072]",               # global layer
    "%fusion.5 fusion bf16[2,5250,8,6,128]",              # blocks' outputs
    "%fusion.6 fusion bf16[2,512,8,6,128]",
    "%convolution.7 convolution bf16[2,8,128,6,512]",     # p . v
    "%fusion.8 fusion bf16[2,4607,8,128,1]",              # a key span
    "%slice.9 slice bf16[2,4096,8,128]",
]
ATTN_DECODE = [
    "%fusion.20 fusion bf16[16,4096,8,128]",              # a row's write
    "%fusion.21 fusion bf16[16,6784,8,128]",
    "%fusion.22 fusion f32[16,8,6,4096]",                 # scores
    "%fusion.23 fusion (f32[16,8,6], f32[16,8,6,6784])",  # softmax
    "%fusion.24 fusion f32[16,8,6,128]",                  # p . v
    "%copy.25 copy f32[16,8,6]",
]
ROUTE = [
    "%fusion.30 fusion (f32[10500,256], f32[10500,256])",  # scores + bias
    "%sort.31 sort (f32[10500,256], s32[10500,256])",      # top-4
    "%fusion.32 fusion f32[10500,4]",
    "%sort.33 sort (s32[42000], s32[42000])",              # pairs by expert
    "%fusion.34 fusion bf16[10752,3072]",                  # the gather
    "%fusion.35 fusion f32[10500,3072]",                   # scatter-add
    "%fusion.36 fusion bf16[128,3072]",
    "%iota.37 iota s32[16,256]",
    "%copy.38 copy f32[16,4]",
    "%reshape.39 reshape s32[64]",
]
OTHER = [
    "%fusion.40 fusion f32[16,25024]",                    # logits
    "%fusion.41 fusion bf16[2,5250,12288]",               # dense ffn
    "%fusion.42 fusion (f32[2,5250], bf16[2,5250,3072])",  # a norm
    "%fusion.43 fusion (f32[2,5250,48,64], f32[2,5250,48,64])",  # rotation
    "%fusion.44 fusion bf16[2,5250,8,128]",               # k's norm
    "%fusion.45 fusion bf16[16,3072]",     # decode scatter-add: left out
    "%fusion.46 fusion f32[2,5250,6144]",                 # the gate
    "%moe_gmm.210 custom-call [mosaic] bf16[128,6144]",
    "%fusion.47 fusion bf16[10752,6144]",                 # experts' silu
    "%fusion.48 fusion bf16[8192,8,128]",                 # ring's gather
    "%while.1110 while (s32[], s32[16], pred[16], bf16[16,4096,8,128])",
]
W, A = 4096, 5250


def part(pairs, valid, padded, capacity):
    return {"expert_pairs": pairs, "pairs_elsewhere": [
        7 * sum(p) for p in pairs], "valid_positions": valid,
        "padded_positions": padded,
        "rows_high_water": max(sum(p) for p in pairs),
        "rows_capacity": capacity, "dropped": 0}


def call(skew=False):
    """16 streams of 40,000 valid frames (5,000 prefix positions) and
    1,400 tokens: 80,000 prefill positions (10,000 pairs a layer on the
    32 held experts), 22,400 tokens in 1,400 steps (2 pairs a step and
    layer held)."""
    even = [[312] * 31 + [328]] * 4
    first = [[624, 0] + [312] * 29 + [328]] * 4 if skew else even
    decode = [[87] * 31 + [103]] * 4
    reach = [5001 + j for j in range(1400)]
    return {"prefill": part(first, 80000, 4000, 10752),
            "decode": part(decode, 22400, 0, 128),
            "decode_steps": 1400, "idle_slot_steps": 0, "rows": 16,
            "rows_attended_window": 4 * 16 * 1400 * W,
            "rows_attended_global": 16 * sum(reach),
            "cache_rows_read": 4 * 16 * 1400 * W + 16 * sum(reach),
            "ring_wraps": 16, "experts_hit": 4 * 1400 * 2,
            "experts_hit_by_layer": [2800] * 4,
            "empty_groups": {"decode": 4 * 1400 * 30,
                             "prefill": 8 if skew else 0,
                             "decode_calls": 4 * 1400,
                             "prefill_calls": 4 * 8, "groups": 32},
            "dropped_pairs": 0, "valid_frames": [40000] * 16,
            "max_tokens": [1400] * 16}


def record():
    from deepspeech_tpu.config import get_config

    ops = {k: 0.040 for k in ATTN_PREFILL}     # 180 ms a call
    ops.update({k: 1.500 for k in ATTN_DECODE})  # 4,500 ms
    ops.update({k: 0.010 for k in ROUTE})      # 50 ms
    ops.update({k: 1.000 for k in OTHER})
    spans = []
    for t0 in (10.0, 30.5):
        spans.append(("infer.transcribe", t0, t0 + 20.0))
        spans += [("infer.prefill", t0 + 0.7 * i, t0 + 0.7 * i + 0.6)
                  for i in range(8)]
        spans.append(("infer.decode", t0 + 6.0, t0 + 20.0))
    spans += [("pipeline.data_wait", 30.0, 30.1),
              ("pipeline.device_prefetch", 30.1, 30.4)]
    return {
        "driver": "transcribe_long",
        "model": get_config("trinity_large").model,
        "units": 2, "chips": 1,
        "t_window_start": 10.0, "t_window_end": 50.5,
        "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        "spans": spans,
        "counters": {
            "rows_per_call": 16, "bucket_frames": 42000,
            "num_features": 161, "cache_rows": 6784, "ring_rows": 4096,
            "prefill_rows": 2, "cache_bytes": 1518338048,
            "calls": [call(), call(skew=True)]},
        "trace": {"op_seconds": ops, "kernels": [], "busy_s": 40.0},
    }


def read(name, rec):
    return importlib.import_module(
        f"benchmark.layer_metrics.{name}").read(rec)


def kinds(key):
    shapes, rec = parse(key)[1], record()
    return (_trinity.is_attn(shapes, rec, "prefill"),
            _trinity.is_attn(shapes, rec, "decode"),
            _trinity.is_route(shapes, rec))


@pytest.mark.parametrize("key", ATTN_PREFILL)
def test_prefill_attention_is_found_by_shape(key):
    assert kinds(key) == (True, False, False)


@pytest.mark.parametrize("key", ATTN_DECODE)
def test_decode_attention_is_found_by_shape(key):
    assert kinds(key) == (False, True, False)


@pytest.mark.parametrize("key", ROUTE)
def test_routing_is_found_by_shape(key):
    assert kinds(key) == (False, False, True)


@pytest.mark.parametrize("key", OTHER[:-1])
def test_the_rest_of_a_call_is_none_of_them(key):
    assert kinds(key) == (False, False, False)


def test_readers_on_the_record():
    rec = record()
    m = rec["model"]
    assert read("trinity_call_ms", rec) == pytest.approx(20000.0)
    assert read("trinity_prefill_ms", rec) == pytest.approx(600.0)
    assert read("trinity_decode_step_ms", rec) == pytest.approx(10.0)
    # the loop's ``while`` spans its body's events and is skipped
    assert read("trinity_attn_prefill_ms", rec) == pytest.approx(180.0)
    assert read("trinity_attn_decode_ms", rec) == pytest.approx(4500.0)
    assert read("trinity_moe_route_ms", rec) == pytest.approx(50.0)
    assert read("trinity_input_wait_pct", rec) == pytest.approx(
        100 * 0.4 / 40.5)
    assert read("trinity_idle_slot_pct", rec) == 0.0
    assert read("trinity_pad_position_pct", rec) == pytest.approx(
        100 * 4000 / (80000 + 4000 + 22400))
    assert read("trinity_cache_gb", rec) == pytest.approx(1.518338048)
    assert read("trinity_held_pair_pct", rec) == pytest.approx(12.5)
    # 30 of 32 groups of a step's call have no row
    assert read("trinity_empty_group_pct", rec) == pytest.approx(
        100 * 30 / 32)
    # fullest / mean of the prefill's layers: 1.05 x 4, 2.0 x 4
    assert 1.04 < read("trinity_expert_load_ratio", rec) < 2.0
    reach = sum(5001 + j for j in range(1400))
    assert read("trinity_window_rows_pct", rec) == pytest.approx(
        100 * 1400 * W / reach)
    pairs = 4 * (10000 + 2800)
    assert _trinity.pairs_held(call()) == pairs
    flops = 2 * trinity.call_flops_valid(m, [40000] * 16, [1400] * 16,
                                         pairs)
    assert read("trinity_mfu_pct", rec) == pytest.approx(
        100 * flops / 40.5 / 197e12)
    assert 0 < read("trinity_mfu_pct", rec) < 100
    rows = 4 * 16 * 1400 * W + 16 * reach
    needed = 2 * 1400 * trinity.decode_step_bytes(m, 8, rows / 1400)
    assert read("trinity_decode_hbm_pct", rec) == pytest.approx(
        100 * needed / (28.0 * 819e9))
    assert 0 < read("trinity_decode_hbm_pct", rec) < 100
    attn = 2 * trinity.prefill_attention_flops(m, [40000] * 16)
    assert read("trinity_attn_prefill_mfu_pct", rec) == pytest.approx(
        100 * attn / (0.36 * 197e12))
    assert 0 < read("trinity_attn_prefill_mfu_pct", rec) < 100
    assert read("trinity_attn_decode_hbm_pct", rec) == pytest.approx(
        100 * 2 * rows * 4096 / (9.0 * 819e9))
    assert 0 < read("trinity_attn_decode_hbm_pct", rec) < 100
    # No named kernel in this record: the kernel readers say nothing.
    assert read("trinity_moe_gmm_ms", rec) is None
    assert read("trinity_moe_gmm_roofline", rec) is None
    assert read("trinity_unnamed_kernel_calls", rec) == 0


def test_named_grouped_products_are_read_by_name_and_by_program():
    from test_kernel_metrics import CALL

    def event(m, k, n):
        facts = {"kernel": "moe_gmm", "m": m, "k": k, "n": n,
                 "groups": 32, "transpose_rhs": 0}
        inner = ",\n".join(f'"{a}":"{b}"' for a, b in sorted(facts.items()))
        return f"%moe_gmm.3 = bf16[{m},{n}]{{1,0}} {CALL}{{\n{inner}\n}}}}"

    prefill = [(event(10752, 3072, 6144), 0.0030),
               (event(10752, 3072, 3072), 0.0016)]
    decode = [(event(128, 3072, 6144), 0.00012),
              (event(128, 3072, 3072), 0.00007)]
    rec = record()
    # two calls x 4 expert layers x (8 sub-batches, 1,400 steps)
    rec["trace"]["kernels"] = prefill * (2 * 4 * 8) \
        + decode * (2 * 4 * 1400)
    spent = 2 * 4 * (8 * 0.0046 + 1400 * 0.00019)
    assert read("trinity_moe_gmm_ms", rec) == pytest.approx(
        1e3 * spent / 2)
    least = 0.0
    for k, n in ((3072, 6144), (3072, 3072)):
        for hit in (32, 32 - 8 / 32):          # the second call's empties
            least += 4 * 8 * trinity.roofline_seconds(
                trinity.gmm_call_cost(k, n, hit, 10000 / 8),
                197e12, 819e9)[0]
        least += 2 * 4 * 1400 * trinity.roofline_seconds(
            trinity.gmm_call_cost(k, n, 2, 2), 197e12, 819e9)[0]
    share = read("trinity_moe_gmm_roofline", rec)
    assert share == pytest.approx(100 * least / spent, rel=1e-6)
    assert 0 < share < 100
    assert rec["counters"]["trinity_moe_gmm_bound_by"] == {
        "prefill memory": 16, "decode memory": 16}


def test_the_generic_kernel_and_set_up_readers_have_twins_for_this_driver():
    from benchmark import harness
    from test_kernel_metrics import event

    rec = record()
    named = event("moe_gmm.18", "bf16[128,6144]", {"kernel": "moe_gmm"})
    bare = event("custom-call.3", "bf16[128,6144]", {})
    rec["trace"]["kernels"] = [(named, 0.001)] * 3 + [(bare, 0.001)] * 2
    rec["spans"] += [("jax.trace", 8.0, 8.5), ("jax.lower", 8.4, 9.0)]
    assert read("trinity_unnamed_kernel_calls", rec) == 2
    assert read("trinity_setup_trace_lower_s", rec) == pytest.approx(1.0)
    for name in ("unnamed_kernel_calls", "setup_trace_lower_s",
                 "axk1_unnamed_kernel_calls", "axk1_call_ms",
                 "xing4_mfu_pct", "host_turn_ms"):
        assert harness.metric_value({"name": name}, rec,
                                    traced=True) is None
    for name in ("unnamed_kernel_calls", "setup_trace_lower_s"):
        assert harness.metric_value({"name": "trinity_" + name}, rec,
                                    traced=True) is not None
    # the readers every cell reports take this record as it is
    for name in ("compiles_in_window", "setup_compile_s", "gen_self_pct",
                 "peak_hbm_gb"):
        rec["counters"].update(window={"compiles": 0},
                               setup={"compile_s": 2.0})
        rec.update(gen_s=0.0, memory_peak_bytes=12.8e9)
        assert harness.metric_value({"name": name}, rec,
                                    traced=True) is not None


READERS = ("trinity_call_ms", "trinity_prefill_ms",
           "trinity_decode_step_ms", "trinity_mfu_pct",
           "trinity_decode_hbm_pct", "trinity_window_rows_pct",
           "trinity_cache_gb", "trinity_expert_load_ratio",
           "trinity_held_pair_pct", "trinity_empty_group_pct",
           "trinity_idle_slot_pct", "trinity_pad_position_pct",
           "trinity_attn_prefill_ms", "trinity_attn_prefill_mfu_pct",
           "trinity_attn_decode_ms", "trinity_attn_decode_hbm_pct",
           "trinity_moe_gmm_ms", "trinity_moe_gmm_roofline",
           "trinity_moe_route_ms")


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_counters_reads_nothing(name):
    """A program without a cache per layer kind (no
    ``rows_attended_window`` in a call's counters, or no counters at
    all: the parent of the PR that added them): the reader returns
    None, it does not raise."""
    rec = record()
    for c in rec["counters"]["calls"]:
        del c["rows_attended_window"]
    assert read(name, rec) is None
    del rec["counters"]["calls"]
    rec["spans"] = []
    assert read(name, rec) is None


@pytest.mark.parametrize("name", READERS)
def test_other_drivers_records_are_skipped(name):
    from benchmark import harness

    rec = record()
    rec["driver"] = "transcribe_lm"
    assert harness.metric_value({"name": name}, rec, traced=True) is None


def test_every_reader_of_the_cell_is_listed():
    import json
    import os

    with open(os.path.join(conftest.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = "trinity_large.transcribe_long_7min_b16"
    listed = {m["name"] for m in bench["per_layer"]
              if m["name"].startswith("trinity_")}
    assert listed == set(READERS) | {
        "trinity_input_wait_pct", "trinity_setup_trace_lower_s",
        "trinity_unnamed_kernel_calls"}
    assert all(m["workloads"] == [cell] for m in bench["per_layer"]
               if m["name"].startswith("trinity_"))
