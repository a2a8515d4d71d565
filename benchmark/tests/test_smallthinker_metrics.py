"""The ``smallthinker_*`` readers on a hand-made record: short names
as ``reduce/xplane.short_name`` gives them for the cell's compiled step
and the kernels' events with their facts (taken from a chip trace, PR
45: 4 recordings of 6,784 positions, 61,440 static rows), the
program's routing counters, two steps."""

import importlib

import pytest

import conftest  # noqa: F401  (puts the checkout on sys.path)
from benchmark.costs import lfm2, smallthinker
from benchmark.layer_metrics import _lfm2
from test_kernel_metrics import CALL

CELL = "smallthinker_21b_a3b.train_long_7min"
F = {"n": 27136, "k": 6, "e": 64, "g": 16, "d": 2560, "m": 61440}

ROUTE = [
    "%fusion.139 fusion bf16[27136,2560]",          # rows back to positions
    "%fusion.89 fusion f32[27136,2560]",            # scatter-add
    "%fusion.203 fusion f32[61440,2560]",           # weighted rows
    "%fusion.310 fusion bf16[61440,2560]",          # the gather
    "%sort.5 sort (f32[27136,64], s32[27136,64])",  # top-6
    "%fusion.12 fusion s32[27136,6]",
    "%fusion.13 fusion s32[162816,17]",             # count per expert
    "%sort.9 sort (s32[162816], s32[162816])",      # pairs by expert
    "%fusion.144 fusion s32[61440]",
]
OTHER = [
    "%fusion.138 fusion bf16[37984,2560]",                  # the head
    "%fusion.580 fusion bf16[6084,2560]",                   # text positions
    "%fusion.1414 fusion (f32[6084], f32[6084,37984])",     # log-softmax
    "%convolution_convert_fusion.7 fusion f32[4,6784,3584]",  # q
    "%fusion.291 fusion (f32[37984,2560], f32[37984,2560], f32[37984,2560])",
    "%fusion.472 fusion (bf16[61440,768], bf16[61440,768])",  # relu * up
    "%gqa_attn_fwd.4 custom-call [mosaic] (bf16[4,4,7,6784,128], f32[4,4,7,6784])",
    "%gqa_attn_bwd_dq.4 custom-call [mosaic] bf16[4,6784,3584]",
    "%moe_gmm.18 custom-call [mosaic] bf16[61440,1536]",
    "%while.7 while (s32[], f32[27136,64])",
]


@pytest.mark.parametrize("key", ROUTE)
def test_routing_is_found_by_shape(key):
    assert _lfm2.classify(key, F) == "route"


@pytest.mark.parametrize("key", OTHER)
def test_the_rest_of_the_step_is_not_routing(key):
    assert _lfm2.classify(key, F) is None


def step(pairs, elsewhere):
    return {"expert_pairs": pairs, "pairs_elsewhere": elsewhere,
            "valid_positions": 24158, "padded_positions": 2978,
            "rows_high_water": max(sum(p) for p in pairs),
            "rows_capacity": 61440, "dropped_pairs": 0,
            "reach_pairs_window": 65_000_000,
            "reach_pairs_global": 73_000_000,
            "experts_hit_by_layer": [16] * 4}


def attn(kernel, window, result):
    facts = {"kernel": kernel, "b": 4, "s": 6784, "kv": 4, "rep": 7,
             "head": 128, "window": window, "q_tile": 256, "k_tile": 512,
             "key_tiles": 171 if window else 196}
    inner = ",\n".join(f'"{a}":"{b}"' for a, b in sorted(facts.items()))
    return f"%{kernel}.4 = {result} {CALL}{{\n{inner}\n}}}}"


def gmm(kernel, k, n, **more):
    facts = {"kernel": kernel, "m": 61440, "k": k, "n": n, "groups": 16,
             **more}
    inner = ",\n".join(f'"{a}":"{b}"' for a, b in sorted(facts.items()))
    return f"%{kernel}.3 = bf16[61440,{n}]{{1,0}} {CALL}{{\n{inner}\n}}}}"


FWD = "(bf16[4,4,7,6784,128], f32[4,4,7,6784])"
DQ = "bf16[4,6784,3584]"
DKV = "(bf16[4,6784,512], bf16[4,6784,512])"


def kernels_of_a_step():
    """One step's Mosaic events: a global layer and three sliding ones
    (times of the chip trace, PR 45), six grouped products a layer."""
    out = []
    for window, n in ((0, 1), (4096, 3)):
        out += [(attn("gqa_attn_fwd", window, FWD), 0.0090),
                (attn("gqa_attn_bwd_dq", window, DQ), 0.0150),
                (attn("gqa_attn_bwd_dkv", window, DKV), 0.0155)] * n
    out += [(gmm("moe_gmm", 2560, 1536, transpose_rhs=0), 0.0016),
            (gmm("moe_gmm", 768, 2560, transpose_rhs=0), 0.0009),
            (gmm("moe_gmm", 1536, 2560, transpose_rhs=1), 0.0016),
            (gmm("moe_gmm", 2560, 768, transpose_rhs=1), 0.0009),
            (gmm("moe_tgmm", 2560, 1536), 0.0030),
            (gmm("moe_tgmm", 768, 2560), 0.0016)] * 4
    return out


def record():
    from deepspeech_tpu.config import get_config

    ops = {k: 0.002 for k in ROUTE}            # 18 ms over two steps
    ops.update({k: 0.100 for k in OTHER})
    even = [[2250] * 16] * 4                   # 36,000 pairs a layer
    skew = [[4500, 0] + [2250] * 14] * 4
    return {
        "driver": "train_long",
        "model": get_config("smallthinker_21b_a3b").model,
        "units": 2, "chips": 1, "warmup_steps": 2,
        "t_window_start": 10.0, "t_window_end": 11.3,
        "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        "spans": [("train.step", 10.0, 10.64), ("train.step", 10.65, 11.29),
                  ("pipeline.data_wait", 10.64, 10.645),
                  ("pipeline.device_prefetch", 10.645, 10.653)],
        "counters": {
            "rows_per_step": 4, "seq_positions": 6784,
            "bucket_frames": 42000, "num_features": 161,
            "max_label_len": 1520,
            "valid_frames": [[34126, 36376, 38626, 40876]] * 3,
            "label_lens": [[1229, 1310, 1391, 1472]] * 3,
            "routing": [step(even, [108948] * 4),
                        step(skew, [108948] * 4)]},
        "trace": {"op_seconds": ops, "kernels": kernels_of_a_step() * 2,
                  "busy_s": 1.29},
    }


def read(name, rec):
    return importlib.import_module(
        f"benchmark.layer_metrics.{name}").read(rec)


def test_readers_on_the_record():
    rec = record()
    assert read("smallthinker_moe_route_ms", rec) == pytest.approx(9.0)
    assert read("smallthinker_step_ms", rec) == pytest.approx(640.0)
    assert read("smallthinker_input_wait_pct", rec) == pytest.approx(1.0)
    assert read("smallthinker_pad_position_pct", rec) == pytest.approx(
        100 * 2978 / 27136)
    assert read("smallthinker_held_pair_pct", rec) == pytest.approx(
        100 * 36000 / 144948)
    # Median over (step, layer) of fullest / mean: 1.0 and 2.0.
    assert read("smallthinker_expert_load_ratio", rec) == pytest.approx(1.5)
    assert read("smallthinker_window_pairs_pct", rec) == pytest.approx(
        100 * 65 / 73)
    assert read("smallthinker_unnamed_kernel_calls", rec) == 0
    assert read("smallthinker_attn_fwd_ms", rec) == pytest.approx(36.0)
    assert read("smallthinker_attn_bwd_ms", rec) == pytest.approx(122.0)
    assert read("smallthinker_moe_gmm_ms", rec) == pytest.approx(4 * 9.6)
    flops = smallthinker.train_flops_valid(
        rec["model"], [34126, 36376, 38626, 40876],
        [1229, 1310, 1391, 1472], 4 * 36000)
    assert read("smallthinker_mfu_pct", rec) == pytest.approx(
        100 * 2 * flops / 1.3 / 197e12)


@pytest.mark.parametrize("name, kernels, products, seconds", [
    ("smallthinker_attn_fwd_mfu_pct", ["gqa_attn_fwd"], 2, 0.036),
    ("smallthinker_attn_bwd_mfu_pct",
     ["gqa_attn_bwd_dq", "gqa_attn_bwd_dkv"], 4, 0.122)])
def test_an_attention_kernels_share_of_the_peak(name, kernels, products,
                                                seconds):
    """Each call's pairs IN REACH by its own facts (one global layer,
    three sliding ones), 2 x head operations a product and query head,
    over the kernels' device time and the bf16 peak."""
    rec = record()
    pairs = (smallthinker.pairs_in_reach(6784, 0)
             + 3 * smallthinker.pairs_in_reach(6784, 4096))
    flops = products * 2 * 128 * 4 * 28 * pairs
    share = read(name, rec)
    assert share == pytest.approx(100 * flops / seconds / 197e12, rel=1e-9)
    assert 0 < share < 100
    # another record's kernels are not this reader's
    rec["trace"]["kernels"] = [
        (t, s) for t, s in rec["trace"]["kernels"]
        if not any(f'"{k}"' in t for k in kernels)]
    assert read(name, rec) is None


def test_the_grouped_products_share_of_their_roofline():
    rec = record()
    kinds = [("moe_gmm", 2560, 1536), ("moe_gmm", 768, 2560),
             ("moe_gmm", 1536, 2560), ("moe_gmm", 2560, 768),
             ("moe_tgmm", 2560, 1536), ("moe_tgmm", 768, 2560)]
    least = sum(lfm2.roofline_seconds(
        lfm2.gmm_call_cost(k, a, b, 16, 36000), 197e12, 819e9)[0]
        for k, a, b in kinds)
    assert read("smallthinker_moe_gmm_roofline", rec) == pytest.approx(
        100 * least / 0.0096, rel=1e-6)
    # A call made twice (a rematerialised forward) is needed once.
    rec["trace"]["kernels"] += kernels_of_a_step()[-6:-4] * 8
    assert read("smallthinker_moe_gmm_roofline", rec) == pytest.approx(
        100 * least / 0.0121, rel=1e-6)


def test_the_generic_readers_have_twins_for_this_driver():
    from benchmark import harness

    rec = record()
    bare = f"%custom-call.3 = bf16[61440,1536] {CALL}{{}}}}"
    rec["trace"]["kernels"] += [(bare, 0.001)] * 2
    rec["spans"] += [("jax.trace", 8.0, 8.5), ("jax.lower", 8.4, 9.0),
                     ("jax.compile", 10.2, 10.3)]
    assert read("smallthinker_unnamed_kernel_calls", rec) == 2
    assert read("smallthinker_setup_trace_lower_s", rec) == \
        pytest.approx(1.0)
    for name in ("unnamed_kernel_calls", "setup_trace_lower_s",
                 "train_step_ms", "input_wait_pct", "lfm2_mfu_pct",
                 "lfm2_moe_gmm_ms", "trinity_mfu_pct"):
        assert harness.metric_value({"name": name}, rec,
                                    traced=True) is None, name


def test_a_program_without_the_counters_or_kernels_reads_nothing():
    """The parent's record (no such preset, so no such run; and any
    record without routing counters or named attention kernels): every
    reader that needs them returns None, none raises."""
    rec = record()
    del rec["counters"]["routing"]
    rec["trace"]["kernels"] = []
    for name in ("smallthinker_mfu_pct", "smallthinker_moe_route_ms",
                 "smallthinker_expert_load_ratio",
                 "smallthinker_held_pair_pct",
                 "smallthinker_pad_position_pct",
                 "smallthinker_window_pairs_pct",
                 "smallthinker_moe_gmm_ms",
                 "smallthinker_moe_gmm_roofline",
                 "smallthinker_attn_fwd_ms", "smallthinker_attn_bwd_ms",
                 "smallthinker_attn_fwd_mfu_pct",
                 "smallthinker_attn_bwd_mfu_pct"):
        assert read(name, rec) is None, name


def test_other_drivers_records_are_skipped():
    from benchmark import harness

    rec = record()
    rec["driver"] = "train_lfm2"
    for name in ("smallthinker_step_ms", "smallthinker_mfu_pct",
                 "smallthinker_attn_bwd_ms", "smallthinker_moe_route_ms",
                 "smallthinker_held_pair_pct"):
        assert harness.metric_value({"name": name}, rec,
                                    traced=True) is None


def test_every_reader_of_the_cell_is_listed():
    import glob
    import json
    import os

    with open(os.path.join(conftest.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"]
              if m["name"].startswith("smallthinker_")}
    files = {os.path.basename(p)[:-3] for p in glob.glob(os.path.join(
        conftest.ROOT, "benchmark", "layer_metrics", "smallthinker_*.py"))}
    assert set(listed) == files and len(files) == 16
    assert all(m["workloads"] == [CELL] for m in listed.values())
    for name, m in listed.items():
        if "mfu" in name or "roofline" in name:
            assert m["unit"] == "%" and m["better"] == "higher"
