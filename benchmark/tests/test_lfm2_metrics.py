"""The ``lfm2_*`` readers on a hand-made record: short names as
``reduce/xplane.short_name`` gives them for the cell's compiled step
(taken from a chip trace, PR 30, with the dispatch's rows at the cell's
32,256), the program's routing counters, two steps."""

import importlib
import types

import pytest

import conftest  # noqa: F401  (puts the checkout on sys.path)
from benchmark.layer_metrics import _lfm2

F = {"n": 36864, "k": 4, "e": 64, "g": 8, "d": 2048, "m": 32256}

ROUTE = [
    "%fusion.136 fusion bf16[32256,2048]",                  # the gather
    "%fusion.89 fusion f32[36864,2048]",                    # scatter-add
    "%fusion.139 fusion bf16[36864,2048]",
    "%fusion.157 fusion f32[147456]",
    "%convert_multiply_fusion.95 fusion (f32[32256,2048], bf16[32256,2048])",
    "%multiply_convert_fusion.6 fusion (bf16[32256,2048], f32[32256])",
    "%fusion.144 fusion s32[32256]",
    "%add_divide_fusion.5 fusion (f32[36864,64], f32[36864,64], f32[36864,6",
    "%sort.14 sort (f32[36864,64], s32[36864,64])",
    "%fusion.12 fusion s32[36864,4]",
    "%fusion.13 fusion s32[147456,9]",
]
OTHER = [
    "%fusion.774 fusion (f32[], bf16[2048,11776])",
    "%fusion.357 fusion (f32[128,288], bf16[128,288,2048])",
    "%convolution_reduce-precision_fusion.1 fusion bf16[128,288,11776]",
    "%fusion.1604 fusion (f32[128,8,4,288], f32[128,8,4,288,288])",
    "%fusion.472 fusion (bf16[32256,1536], bf16[32256,1536], bf16[32256,15",
    "%pad_add_fusion.1 fusion bf16[32256,3072]",
    "%moe_gmm.18 custom-call [mosaic] bf16[32256,3072]",
    "%fusion.138 fusion bf16[8192,2048]",
    "%while.7 while (s32[], f32[36864,64])",
]


@pytest.mark.parametrize("key", ROUTE)
def test_routing_is_found_by_shape(key):
    assert _lfm2.classify(key, F) == "route"


@pytest.mark.parametrize("key", OTHER)
def test_the_rest_of_the_step_is_not_routing(key):
    assert _lfm2.classify(key, F) is None


def step(pairs, elsewhere):
    return {"expert_pairs": pairs, "pairs_elsewhere": elsewhere,
            "valid_positions": 29000, "padded_positions": 7864,
            "rows_high_water": max(sum(p) for p in pairs),
            "rows_capacity": 32256, "dropped_pairs": 0}


def record():
    from deepspeech_tpu.config import get_config

    ops = {k: 0.002 for k in ROUTE}            # 22 ms over two steps
    ops.update({k: 0.100 for k in OTHER})
    even = [[1800] * 8] * 4                    # 14,400 pairs a layer
    skew = [[3600, 0, 1800, 1800, 1800, 1800, 1800, 1800]] * 4
    return {
        "driver": "train_lfm2", "model": get_config("lfm2_24b_a2b").model,
        "units": 2, "chips": 1, "warmup_steps": 2,
        "t_window_start": 10.0, "t_window_end": 11.0,
        "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        "spans": [("train.step", 10.0, 10.4), ("train.step", 10.5, 10.98),
                  ("pipeline.data_wait", 10.40, 10.41),
                  ("pipeline.device_prefetch", 10.41, 10.42)],
        "counters": {
            "rows_per_step": 128, "seq_positions": 288,
            "bucket_frames": 1696, "num_features": 161,
            "max_label_len": 64,
            "valid_frames": [[1650] * 128, [1201] * 128],
            "label_lens": [[59] * 128, [43] * 128],
            "routing": [step(even, [101600] * 4),
                        step(skew, [101600] * 4)]},
        "trace": {"op_seconds": ops, "kernels": [], "busy_s": 0.9},
    }


def read(name, rec):
    return importlib.import_module(
        f"benchmark.layer_metrics.{name}").read(rec)


def test_readers_on_the_record():
    rec = record()
    assert read("lfm2_moe_route_ms", rec) == pytest.approx(11.0)
    assert read("lfm2_step_ms", rec) == pytest.approx(440.0)
    assert read("lfm2_input_wait_pct", rec) == pytest.approx(2.0)
    assert read("lfm2_pad_position_pct", rec) == pytest.approx(
        100 * 7864 / 36864)
    assert read("lfm2_held_pair_pct", rec) == pytest.approx(
        100 * 14400 / 116000)
    # Median over (step, layer) of fullest / mean: 1.0 and 2.0.
    assert read("lfm2_expert_load_ratio", rec) == pytest.approx(1.5)
    assert 0 < read("lfm2_mfu_pct", rec) < 100
    # No named kernel in this record: the kernel readers say nothing.
    assert read("lfm2_moe_gmm_ms", rec) is None
    assert read("lfm2_moe_gmm_roofline", rec) is None
    assert read("lfm2_unnamed_kernel_calls", rec) == 0


def test_the_generic_kernel_and_set_up_readers_have_twins_for_this_driver():
    """``unnamed_kernel_calls`` and ``setup_trace_lower_s`` skip this
    driver's records (``DRIVERS``); their ``lfm2_`` twins read them."""
    from benchmark import harness
    from test_kernel_metrics import event

    rec = record()
    named = event("moe_gmm.18", "bf16[32256,3072]", {"kernel": "moe_gmm"})
    bare = event("custom-call.3", "bf16[32256,3072]", {})
    rec["trace"]["kernels"] = [(named, 0.001)] * 3 + [(bare, 0.001)] * 2
    rec["spans"] += [("jax.trace", 8.0, 8.5), ("jax.lower", 8.4, 9.0),
                     ("jax.compile", 10.2, 10.3)]
    assert read("lfm2_unnamed_kernel_calls", rec) == 2
    assert read("lfm2_setup_trace_lower_s", rec) == pytest.approx(1.0)
    assert rec["counters"]["compiled_in_window"]["jax.compile"] == 1
    for name in ("unnamed_kernel_calls", "setup_trace_lower_s"):
        assert harness.metric_value({"name": name}, rec,
                                    traced=True) is None
        assert harness.metric_value({"name": "lfm2_" + name}, rec,
                                    traced=True) is not None


def test_named_grouped_products_are_read_by_name():
    from benchmark.costs import lfm2
    from test_kernel_metrics import CALL

    def event(kernel, k, n, **more):
        facts = {"kernel": kernel, "m": 32256, "k": k, "n": n,
                 "groups": 8, **more}
        inner = ",\n".join(f'"{a}":"{b}"' for a, b in sorted(facts.items()))
        return f"%{kernel}.3 = bf16[32256,{n}]{{1,0}} {CALL}{{\n{inner}\n}}}}"

    calls = [(event("moe_gmm", 2048, 3072, transpose_rhs=0), 0.0012),
             (event("moe_gmm", 1536, 2048, transpose_rhs=0), 0.0006),
             (event("moe_gmm", 3072, 2048, transpose_rhs=1), 0.0012),
             (event("moe_gmm", 2048, 1536, transpose_rhs=1), 0.0006),
             (event("moe_tgmm", 2048, 3072), 0.0020),
             (event("moe_tgmm", 1536, 2048), 0.0010)]
    rec = record()
    rec["trace"]["kernels"] = calls * 8        # 4 layers x 2 steps
    assert read("lfm2_moe_gmm_ms", rec) == pytest.approx(4 * 6.6)
    # Needed: each of the six kinds once per layer and step, over the
    # 14,400 rows routed there: 3 x 2 x 14400 x 9.44 M operations.
    flops = 3 * 2 * 14400 * (2048 * 3072 + 1536 * 2048)
    assert sum(lfm2.gmm_call_cost(k, a, b, 8, 14400)["flops"]
               for k, a, b in [("moe_gmm", 2048, 3072),
                               ("moe_gmm", 1536, 2048),
                               ("moe_gmm", 3072, 2048),
                               ("moe_gmm", 2048, 1536),
                               ("moe_tgmm", 2048, 3072),
                               ("moe_tgmm", 1536, 2048)]) == flops
    share = read("lfm2_moe_gmm_roofline", rec)
    assert share == pytest.approx(100 * flops / 197e12 / 0.0066, rel=1e-6)
    assert rec["counters"]["lfm2_moe_gmm_bound_by"] == {"compute": 48}
    # A call made twice (a rematerialised forward) is needed once.
    rec["trace"]["kernels"] = (calls + calls[:2]) * 8
    assert read("lfm2_moe_gmm_roofline", rec) == pytest.approx(
        100 * flops / 197e12 / 0.0084, rel=1e-6)


def test_a_program_without_the_counters_reads_nothing():
    """The parent's record (no routing counters, no named moe kernel):
    every ``lfm2_*`` reader that needs them returns None, none raises."""
    rec = record()
    del rec["counters"]["routing"]
    for name in ("lfm2_mfu_pct", "lfm2_moe_route_ms",
                 "lfm2_expert_load_ratio", "lfm2_held_pair_pct",
                 "lfm2_pad_position_pct", "lfm2_moe_gmm_ms",
                 "lfm2_moe_gmm_roofline"):
        assert read(name, rec) is None, name


def test_other_drivers_records_are_skipped():
    from benchmark import harness

    rec = record()
    rec["driver"] = "train_rnnt"
    for name in ("lfm2_moe_route_ms", "lfm2_step_ms", "lfm2_mfu_pct",
                 "lfm2_held_pair_pct", "lfm2_moe_gmm_ms"):
        assert harness.metric_value({"name": name}, rec, traced=True) is None
    rec["driver"] = "train_lfm2"
    for name in ("train_mfu_pct", "rnnt_mfu_pct", "train_step_ms"):
        assert harness.metric_value({"name": name}, rec, traced=True) is None
