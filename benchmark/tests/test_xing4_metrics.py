"""The ``xing4_*`` readers on a hand-made record: short names as
``reduce/xplane.short_name`` gives them for the cell's two compiled
programs (fusion results of my compile for a v5e and of my chip trace,
PR 39: prefill sub-batches of 32 x 212 positions, drafting steps of 2 x
256 positions, 27,136 and 2,048 static rows), the program's counters of
two calls with seeded drafts (none accepted)."""

import importlib

import pytest

import conftest  # noqa: F401  (puts the checkout on sys.path)
from benchmark.costs import xing4
from benchmark.layer_metrics import _xing4
from benchmark.layer_metrics._rnnt import parse

MHC = [
    "%fusion.1 fusion f32[1,4,6784]",                     # a Sinkhorn turn
    "%fusion.2 fusion (f32[1,6784], f32[1,6784], f32[1,6784])",
    "%fusion.3 fusion f32[4,4,6784]",
    "%fusion.4 fusion f32[24,6784]",
    "%fusion.5 fusion f32[16,512]",
    "%fusion.6 fusion f32[14336,24]",                     # gain * phi
    "%fusion.7 fusion bf16[6784,14336]",
    "%fusion.8 fusion (f32[6784,4], f32[6784,4])",        # H_pre, H_post
    "%fusion.9 fusion (f32[32,212,4,3584], bf16[32,212,4,3584])",
    "%fusion.10 fusion (f32[32,212,4,1], f32[32,212,4,1])",
    "%fusion.11 fusion (f32[256,2], bf16[256,2,4,3584])",  # write-back
    "%fusion.12 fusion f32[512,4,4]",
    "%fusion.13 fusion (f32[256,2,1,3584], f32[256,2,1,3584], "
    "f32[256,2,1,3584], f32[256,2,1,3584])",
    "%fusion.14 fusion (f32[1,512], f32[1,512], f32[1,512], f32[1,512], "
    "f32[1,512])",
]
MLA = [
    "%fusion.20 fusion f32[32,32,212,212]",               # scores
    "%fusion.21 fusion bf16[32,212,6144]",                # q_b
    "%fusion.22 fusion bf16[32,212,768]",
    "%fusion.23 fusion bf16[32,212,32,256]",              # expand
    "%fusion.24 fusion bf16[256,2,32,576]",               # absorbed
    "%fusion.25 fusion f32[256,32,2,288]",         # a step's scores
    "%fusion.26 fusion bf16[256,288,576]",         # cache update
]
ROUTE = [
    "%fusion.30 fusion f32[6784,3584]",                   # scatter-add
    "%fusion.31 fusion f32[6784,64]",
    "%fusion.32 fusion f32[6784,4]",                      # top-4 weights
    "%fusion.33 fusion s32[27136,65]",
    "%fusion.34 fusion bf16[27136,3584]",                 # the gather
    "%fusion.35 fusion bf16[2048,3584]",
    "%fusion.36 fusion f32[512,64]",
    "%fusion.37 fusion s32[512,4]",
]
OTHER = [
    "%fusion.40 fusion f32[256,2,131072]",                # logits
    "%fusion.41 fusion bf16[32,212,9216]",                # dense ffn
    "%fusion.42 fusion (f32[32,212,3584], f32[32,212,3584])",  # read mix
    "%fusion.43 fusion f32[512,3584]",     # decode scatter-add: left out
    "%fusion.44 fusion bf16[256,2,1024]",                 # shared expert
    "%moe_gmm.210 custom-call [mosaic] bf16[2048,2048]",
    "%fusion.45 fusion bf16[27136,2048]",                 # experts' silu
    "%while.1110 while (s32[], s32[256], s32[256], bf16[256,288,576])",
]


def part(pairs, valid, padded, capacity):
    return {"expert_pairs": pairs, "pairs_elsewhere": [0] * len(pairs),
            "valid_positions": valid, "padded_positions": padded,
            "rows_high_water": max(sum(p) for p in pairs),
            "rows_capacity": capacity, "dropped": 0}


def call(skew=False):
    """50,000 valid prefix positions (200,000 pairs a layer, the module
    alike), 13,400 tokens in 60 steps of 256 streams; the module drafts
    at every token but each stream's last."""
    even = [[3125] * 64] * 7
    first = [[6250, 0] + [3125] * 62] * 7 if skew else even
    decode = [[837] * 64] * 6 + [[821] * 64]
    return {"prefill": part(first, 50000, 4272, 27136),
            "decode": part(decode, 13400, 2 * 60 * 256 - 13400, 2048),
            "decode_steps": 60, "idle_slot_steps": 1960,
            "cache_rows_read": 2770000, "rows": 256, "experts_hit": 26800,
            "verify_positions": 26544, "draft_positions": 13144,
            "draft_accepted": 0, "rejected_rows_overwritten": 13144,
            "drafts": 13400, "dropped_pairs": 0,
            "valid_frames": [1650] * 256, "max_tokens": [52] * 256}


def record():
    from deepspeech_tpu.config import get_config

    ops = {k: 0.003 for k in MHC}              # 21 ms a call
    ops.update({k: 0.004 for k in MLA})        # 14 ms
    ops.update({k: 0.002 for k in ROUTE})      # 8 ms
    ops.update({k: 0.100 for k in OTHER})
    spans = []
    for t0 in (10.0, 14.5):
        spans.append(("infer.transcribe", t0, t0 + 4.4))
        spans += [("infer.prefill", t0 + 0.26 * i, t0 + 0.26 * i + 0.25)
                  for i in range(8)]
        spans.append(("infer.decode", t0 + 2.0, t0 + 4.4))
    spans += [("pipeline.data_wait", 14.40, 14.41),
              ("pipeline.device_prefetch", 14.41, 14.49)]
    return {
        "driver": "transcribe_mtp",
        "model": get_config("xing4_29b_a4b").model,
        "units": 2, "chips": 1,
        "t_window_start": 10.0, "t_window_end": 19.0,
        "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        "spans": spans,
        "counters": {
            "rows_per_call": 256, "bucket_frames": 1696,
            "num_features": 161, "cache_rows": 288, "prefill_rows": 32,
            "cache_bytes": 679477248,
            "calls": [call(), call(skew=True)]},
        "trace": {"op_seconds": ops, "kernels": [], "busy_s": 8.9},
    }


def read(name, rec):
    return importlib.import_module(
        f"benchmark.layer_metrics.{name}").read(rec)


def kinds(key):
    shapes, rec = parse(key)[1], record()
    return (_xing4.is_mhc(shapes, rec), _xing4.is_mla(shapes, rec),
            _xing4.is_route(shapes, rec))


@pytest.mark.parametrize("key", MHC)
def test_hyper_connections_are_found_by_shape(key):
    assert kinds(key) == (True, False, False)


@pytest.mark.parametrize("key", MLA)
def test_latent_attention_is_found_by_shape(key):
    assert kinds(key) == (False, True, False)


@pytest.mark.parametrize("key", ROUTE)
def test_routing_is_found_by_shape(key):
    assert kinds(key) == (False, False, True)


@pytest.mark.parametrize("key", OTHER[:-1])
def test_the_rest_of_a_call_is_none_of_them(key):
    assert kinds(key) == (False, False, False)


def test_readers_on_the_record():
    rec = record()
    assert read("xing4_call_ms", rec) == pytest.approx(4400.0)
    assert read("xing4_prefill_ms", rec) == pytest.approx(250.0)
    assert read("xing4_decode_step_ms", rec) == pytest.approx(40.0)
    # the loop's ``while`` spans its body's events and is skipped
    assert read("xing4_mhc_ms", rec) == pytest.approx(21.0)
    assert read("xing4_mla_ms", rec) == pytest.approx(14.0)
    assert read("xing4_moe_route_ms", rec) == pytest.approx(8.0)
    assert read("xing4_input_wait_pct", rec) == pytest.approx(1.0)
    assert read("xing4_draft_accept_pct", rec) == 0.0
    assert read("xing4_tokens_per_step", rec) == pytest.approx(1.0)
    assert read("xing4_idle_slot_pct", rec) == pytest.approx(
        100 * 1960 / (60 * 256))
    assert read("xing4_pad_position_pct", rec) == pytest.approx(
        100 * (4272 + 30720 - 13400) / (50000 + 4272 + 30720))
    # fullest / mean over (call, program, layer): 1.0 x 21, 2.0 x 7
    assert read("xing4_expert_load_ratio", rec) == pytest.approx(1.0)
    assert read("xing4_cache_gb", rec) == pytest.approx(0.679477248)
    # needed operations: the model's pairs, not the module's (last row)
    pairs = 6 * 64 * (3125 + 837)
    assert _xing4.model_pairs(call()) == pairs
    flops = 2 * xing4.call_flops_valid(rec["model"], [1650] * 256,
                                       [52] * 256, pairs)
    assert read("xing4_mfu_pct", rec) == pytest.approx(
        100 * flops / 9.0 / 197e12)
    assert 0 < read("xing4_mfu_pct", rec) < 100
    needed = 2 * 60 * xing4.decode_step_bytes(rec["model"], 26800 / 60,
                                              2770000 / 60)
    assert read("xing4_decode_hbm_pct", rec) == pytest.approx(
        100 * needed / (2 * 2.4 * 819e9))
    assert 0 < read("xing4_decode_hbm_pct", rec) < 100
    mhc = xing4.mhc_bytes(rec["model"], 2 * (50000 + 13400))
    assert read("xing4_mhc_hbm_pct", rec) == pytest.approx(
        100 * mhc / (0.042 * 819e9))
    # No named kernel in this record: the kernel readers say nothing.
    assert read("xing4_moe_gmm_ms", rec) is None
    assert read("xing4_moe_gmm_roofline", rec) is None
    assert read("xing4_unnamed_kernel_calls", rec) == 0


def test_an_accepted_draft_shows_in_both_counters():
    rec = record()
    for c in rec["counters"]["calls"]:
        c["draft_accepted"] = c["draft_positions"] // 2
        c["idle_slot_steps"] += 256 * 20
    assert read("xing4_draft_accept_pct", rec) == pytest.approx(50.0)
    assert read("xing4_tokens_per_step", rec) == pytest.approx(
        13400 / (60 * 256 - 1960 - 5120))


def test_named_grouped_products_are_read_by_name_and_by_program():
    from test_kernel_metrics import CALL

    def event(m, k, n):
        facts = {"kernel": "moe_gmm", "m": m, "k": k, "n": n,
                 "groups": 64, "transpose_rhs": 0}
        inner = ",\n".join(f'"{a}":"{b}"' for a, b in sorted(facts.items()))
        return f"%moe_gmm.3 = bf16[{m},{n}]{{1,0}} {CALL}{{\n{inner}\n}}}}"

    prefill = [(event(27136, 3584, 2048), 0.0042),
               (event(27136, 1024, 3584), 0.0022)]
    decode = [(event(2048, 3584, 2048), 0.00155),
              (event(2048, 1024, 3584), 0.00074)]
    rec = record()
    # two calls x 7 expert layers x (8 sub-batches, 60 steps)
    rec["trace"]["kernels"] = prefill * (2 * 7 * 8) + decode * (2 * 7 * 60)
    spent = 2 * 7 * (8 * 0.0064 + 60 * 0.00229)
    assert read("xing4_moe_gmm_ms", rec) == pytest.approx(1e3 * spent / 2)
    least = 0.0
    for k, n in ((3584, 2048), (1024, 3584)):
        least += 2 * 7 * 8 * xing4.roofline_seconds(
            xing4.gmm_call_cost(k, n, 64, 200000 / 8), 197e12, 819e9)[0]
        for rows in [837 * 64] * 6 + [821 * 64]:
            least += 2 * 60 * xing4.roofline_seconds(
                xing4.gmm_call_cost(k, n, 26800 / 60 / 7, rows / 60),
                197e12, 819e9)[0]
    share = read("xing4_moe_gmm_roofline", rec)
    assert share == pytest.approx(100 * least / spent, rel=1e-6)
    assert 0 < share < 100
    assert rec["counters"]["xing4_moe_gmm_bound_by"] == {
        "prefill compute": 28, "decode memory": 28}


def test_the_generic_kernel_and_set_up_readers_have_twins_for_this_driver():
    from benchmark import harness
    from test_kernel_metrics import event

    rec = record()
    named = event("moe_gmm.18", "bf16[2048,2048]", {"kernel": "moe_gmm"})
    bare = event("custom-call.3", "bf16[2048,2048]", {})
    rec["trace"]["kernels"] = [(named, 0.001)] * 3 + [(bare, 0.001)] * 2
    rec["spans"] += [("jax.trace", 8.0, 8.5), ("jax.lower", 8.4, 9.0)]
    assert read("xing4_unnamed_kernel_calls", rec) == 2
    assert read("xing4_setup_trace_lower_s", rec) == pytest.approx(1.0)
    for name in ("unnamed_kernel_calls", "setup_trace_lower_s",
                 "axk1_unnamed_kernel_calls", "axk1_call_ms",
                 "axk1_mfu_pct"):
        assert harness.metric_value({"name": name}, rec,
                                    traced=True) is None
    for name in ("unnamed_kernel_calls", "setup_trace_lower_s"):
        assert harness.metric_value({"name": "xing4_" + name}, rec,
                                    traced=True) is not None
    # the six readers every cell reports take this record as it is
    for name in ("compiles_in_window", "setup_compile_s", "gen_self_pct",
                 "peak_hbm_gb"):
        rec["counters"].update(window={"compiles": 0},
                               setup={"compile_s": 2.0})
        rec.update(gen_s=0.0, memory_peak_bytes=15.8e9)
        assert harness.metric_value({"name": name}, rec,
                                    traced=True) is not None


READERS = ("xing4_call_ms", "xing4_prefill_ms", "xing4_decode_step_ms",
           "xing4_mfu_pct", "xing4_decode_hbm_pct", "xing4_mla_ms",
           "xing4_moe_route_ms", "xing4_mhc_ms", "xing4_mhc_hbm_pct",
           "xing4_expert_load_ratio", "xing4_draft_accept_pct",
           "xing4_tokens_per_step", "xing4_idle_slot_pct",
           "xing4_pad_position_pct", "xing4_cache_gb", "xing4_moe_gmm_ms",
           "xing4_moe_gmm_roofline")


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_counters_reads_nothing(name):
    """A program that does not draft (no ``draft_positions`` in a
    call's counters, or no counters at all): the reader returns None,
    it does not raise."""
    rec = record()
    for c in rec["counters"]["calls"]:
        del c["draft_positions"]
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
