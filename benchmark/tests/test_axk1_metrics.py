"""The ``axk1_*`` readers on a hand-made record: short names as
``reduce/xplane.short_name`` gives them for the cell's two compiled
programs (taken from a chip trace, PR 32: prefill sub-batches of 32 x
212 positions, decode steps of 256 streams, 13,824 and 512 static
rows), the program's counters of two calls."""

import importlib

import pytest

import conftest  # noqa: F401  (puts the checkout on sys.path)
from benchmark.costs import axk1
from benchmark.layer_metrics import _axk1
from benchmark.layer_metrics._rnnt import parse

MLA = [
    "%fusion.911 fusion (f32[32,64,212], f32[32,64,212,212])",   # scores
    "%fusion.534 fusion bf16[32,212,12288]",                     # q_b
    "%fusion.9 fusion bf16[32,212,1536]",
    "%fusion.10 fusion bf16[32,212,64,256]",                     # expand
    "%fusion.11 fusion bf16[256,64,576]",                        # absorbed
    "%fusion.12 fusion f32[256,64,288]",                  # decode scores
    "%fusion.13 fusion bf16[256,288,576]",                # cache update
]
ROUTE = [
    "%fusion.104 fusion f32[6784,7168]",                  # scatter-add
    "%fusion.20 fusion f32[6784,192]",
    "%sort.3 sort (f32[6784,192], s32[6784,192])",
    "%fusion.21 fusion s32[54272,13]",
    "%fusion.22 fusion bf16[13824,7168]",                 # the gather
    "%fusion.23 fusion f32[6784,8,24]",                   # group maxima
    "%fusion.24 fusion bf16[512,7168]",
    "%fusion.25 fusion s32[256,8]",
    "%fusion.26 fusion f32[256,192]",
]
OTHER = [
    "%fusion.181 fusion bf16[32,212,18432]",              # dense ffn
    "%fusion.279 fusion (f32[32,212], bf16[32,212,7168])",   # o / w2
    "%fusion.2227 fusion f32[256,20480]",                 # logits
    "%fusion.2061 fusion f32[256,7168]",     # decode scatter-add: left out
    "%fusion.1936 fusion bf16[256,18432]",
    "%moe_gmm.204 custom-call [mosaic] bf16[512,4096]",
    "%fusion.30 fusion bf16[13824,4096]",                 # experts' silu
    "%while.371 while (s32[], s32[256], pred[256], s32[256,65], bf16[256,288,576])",
]


def part(pairs, elsewhere, valid, padded, capacity):
    return {"expert_pairs": pairs, "pairs_elsewhere": elsewhere,
            "valid_positions": valid, "padded_positions": padded,
            "rows_high_water": max(sum(p) for p in pairs) // 8,
            "rows_capacity": capacity, "dropped": 0}


def call(skew=False):
    even = [[2400] * 12] * 7          # 28,800 prefill pairs a layer
    first = [[4800, 0] + [2400] * 10] * 7 if skew else even
    return {"prefill": part(first, [371200] * 7, 50000, 4272, 13824),
            "decode": part([[550] * 12] * 7, [100400] * 7, 13400, 1960,
                           512),
            "decode_steps": 60, "idle_slot_steps": 1960,
            "cache_rows_read": 2770000, "rows": 256, "experts_hit": 4950,
            "dropped_pairs": 0, "valid_frames": [1650] * 256,
            "max_tokens": [52] * 256}


def record():
    from deepspeech_tpu.config import get_config

    ops = {k: 0.004 for k in MLA}              # 28 ms over two calls
    ops.update({k: 0.002 for k in ROUTE})      # 18 ms
    ops.update({k: 0.100 for k in OTHER})
    spans = []
    for t0 in (10.0, 13.5):
        spans.append(("infer.transcribe", t0, t0 + 3.4))
        spans += [("infer.prefill", t0 + 0.25 * i, t0 + 0.25 * i + 0.24)
                  for i in range(8)]
        spans.append(("infer.decode", t0 + 2.0, t0 + 3.38))
    spans += [("pipeline.data_wait", 13.40, 13.41),
              ("pipeline.device_prefetch", 13.41, 13.47)]
    return {
        "driver": "transcribe_lm", "model": get_config("ax_k1").model,
        "units": 2, "chips": 1,
        "t_window_start": 10.0, "t_window_end": 17.0,
        "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        "spans": spans,
        "counters": {
            "rows_per_call": 256, "bucket_frames": 1696,
            "num_features": 161, "cache_rows": 288, "prefill_rows": 32,
            "cache_bytes": 679477248,
            "calls": [call(), call(skew=True)]},
        "trace": {"op_seconds": ops, "kernels": [], "busy_s": 6.9},
    }


def read(name, rec):
    return importlib.import_module(
        f"benchmark.layer_metrics.{name}").read(rec)


@pytest.mark.parametrize("key", MLA)
def test_latent_attention_is_found_by_shape(key):
    shapes = parse(key)[1]
    assert _axk1.is_mla(shapes, record())
    assert not _axk1.is_route(shapes, record())


@pytest.mark.parametrize("key", ROUTE)
def test_routing_is_found_by_shape(key):
    shapes = parse(key)[1]
    assert _axk1.is_route(shapes, record())
    assert not _axk1.is_mla(shapes, record())


@pytest.mark.parametrize("key", OTHER[:-1])
def test_the_rest_of_a_call_is_neither(key):
    shapes = parse(key)[1]
    assert not _axk1.is_route(shapes, record())
    assert not _axk1.is_mla(shapes, record())


def test_readers_on_the_record():
    rec = record()
    assert read("axk1_call_ms", rec) == pytest.approx(3400.0)
    assert read("axk1_prefill_ms", rec) == pytest.approx(240.0)
    assert read("axk1_decode_step_ms", rec) == pytest.approx(23.0)
    # the loop's ``while`` spans its body's events and is skipped
    assert read("axk1_mla_ms", rec) == pytest.approx(14.0)
    assert read("axk1_moe_route_ms", rec) == pytest.approx(9.0)
    assert read("axk1_input_wait_pct", rec) == pytest.approx(1.0)
    assert read("axk1_idle_slot_pct", rec) == pytest.approx(
        100 * 1960 / (60 * 256))
    assert read("axk1_pad_position_pct", rec) == pytest.approx(
        100 * (4272 + 1960) / (50000 + 4272 + 13400 + 1960))
    held = 7 * 12 * (2400 + 550)
    assert read("axk1_held_pair_pct", rec) == pytest.approx(
        100 * held / (held + 7 * (371200 + 100400)))
    # fullest / mean over (call, program, layer): 1.0 x 21, 2.0 x 7
    assert read("axk1_expert_load_ratio", rec) == pytest.approx(1.0)
    assert read("axk1_cache_gb", rec) == pytest.approx(0.679477248)
    flops = 2 * axk1.call_flops_valid(rec["model"], [1650] * 256,
                                      [52] * 256, held)
    assert read("axk1_mfu_pct", rec) == pytest.approx(
        100 * flops / 7.0 / 197e12)
    needed = 2 * 60 * axk1.decode_step_bytes(rec["model"], 4950 / 60,
                                             2770000 / 60)
    assert read("axk1_decode_hbm_pct", rec) == pytest.approx(
        100 * needed / (2 * 1.38 * 819e9))
    assert 0 < read("axk1_decode_hbm_pct", rec) < 100
    # No named kernel in this record: the kernel readers say nothing.
    assert read("axk1_moe_gmm_ms", rec) is None
    assert read("axk1_moe_gmm_roofline", rec) is None
    assert read("axk1_unnamed_kernel_calls", rec) == 0


def test_named_grouped_products_are_read_by_name_and_by_program():
    from test_kernel_metrics import CALL

    def event(m, k, n):
        facts = {"kernel": "moe_gmm", "m": m, "k": k, "n": n,
                 "groups": 12, "transpose_rhs": 0}
        inner = ",\n".join(f'"{a}":"{b}"' for a, b in sorted(facts.items()))
        return f"%moe_gmm.3 = bf16[{m},{n}]{{1,0}} {CALL}{{\n{inner}\n}}}}"

    prefill = [(event(13824, 7168, 4096), 0.0026),
               (event(13824, 2048, 7168), 0.0013)]
    decode = [(event(512, 7168, 4096), 0.00093),
              (event(512, 2048, 7168), 0.00047)]
    rec = record()
    # two calls x 7 layers x (8 sub-batches, 60 steps)
    rec["trace"]["kernels"] = prefill * (2 * 7 * 8) + decode * (2 * 7 * 60)
    spent = 2 * 7 * (8 * 0.0039 + 60 * 0.0014)
    assert read("axk1_moe_gmm_ms", rec) == pytest.approx(1e3 * spent / 2)
    least = 0.0
    for rows, hit, times in ((28800 / 8, 12, 8),
                             (6600 / 60, 4950 / 60 / 7, 60)):
        for k, n in ((7168, 4096), (2048, 7168)):
            least += 2 * 7 * times * axk1.roofline_seconds(
                axk1.gmm_call_cost(k, n, hit, rows), 197e12, 819e9)[0]
    share = read("axk1_moe_gmm_roofline", rec)
    assert share == pytest.approx(100 * least / spent, rel=1e-6)
    assert 0 < share < 100
    assert rec["counters"]["axk1_moe_gmm_bound_by"] == {
        "prefill compute": 28, "decode memory": 28}


def test_the_generic_kernel_and_set_up_readers_have_twins_for_this_driver():
    from benchmark import harness
    from test_kernel_metrics import event

    rec = record()
    named = event("moe_gmm.18", "bf16[512,4096]", {"kernel": "moe_gmm"})
    bare = event("custom-call.3", "bf16[512,4096]", {})
    rec["trace"]["kernels"] = [(named, 0.001)] * 3 + [(bare, 0.001)] * 2
    rec["spans"] += [("jax.trace", 8.0, 8.5), ("jax.lower", 8.4, 9.0)]
    assert read("axk1_unnamed_kernel_calls", rec) == 2
    assert read("axk1_setup_trace_lower_s", rec) == pytest.approx(1.0)
    for name in ("unnamed_kernel_calls", "setup_trace_lower_s"):
        assert harness.metric_value({"name": name}, rec,
                                    traced=True) is None
        assert harness.metric_value({"name": "axk1_" + name}, rec,
                                    traced=True) is not None


def test_a_program_without_the_counters_reads_nothing():
    """The parent's record (no served call's counters, no spans of the
    engine): every ``axk1_*`` reader that needs them returns None, none
    raises."""
    rec = record()
    del rec["counters"]["calls"]
    rec["spans"] = []
    for name in ("axk1_call_ms", "axk1_prefill_ms", "axk1_decode_step_ms",
                 "axk1_mfu_pct", "axk1_decode_hbm_pct", "axk1_mla_ms",
                 "axk1_moe_route_ms", "axk1_expert_load_ratio",
                 "axk1_held_pair_pct", "axk1_idle_slot_pct",
                 "axk1_pad_position_pct", "axk1_cache_gb",
                 "axk1_moe_gmm_ms", "axk1_moe_gmm_roofline"):
        assert read(name, rec) is None, name


def test_other_drivers_records_are_skipped():
    from benchmark import harness

    rec = record()
    rec["driver"] = "train_lfm2"
    for name in ("axk1_call_ms", "axk1_mfu_pct", "axk1_moe_route_ms",
                 "axk1_held_pair_pct", "axk1_moe_gmm_ms", "axk1_cache_gb"):
        assert harness.metric_value({"name": name}, rec, traced=True) is None
    rec["driver"] = "transcribe_lm"
    for name in ("lfm2_mfu_pct", "rnnt_mfu_pct", "train_step_ms",
                 "lfm2_moe_route_ms"):
        assert harness.metric_value({"name": name}, rec, traced=True) is None
