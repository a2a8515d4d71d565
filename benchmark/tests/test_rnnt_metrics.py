"""The ``rnnt_*`` readers on a hand-made record: short names as
``reduce/xplane.short_name`` gives them for the cell's compiled step
(taken from a chip trace, PR 26), two steps."""

import importlib
import types

import pytest

import conftest  # noqa: F401  (puts the checkout on sys.path)
from benchmark.layer_metrics import _rnnt

F = {"b": 64, "t": 284, "u1": 65, "tile": 3, "j": 640, "v": 4096}

JOINT = [
    "%fusion.2333 fusion (f32[64,3,65], f32[64,3,65,4096])",
    "%exponential_reduce_fusion.3 fusion f32[64,3,65]",
    "%multiply_reduce_fusion.95 fusion f32[64,3,65]",
    "%fusion.2345 fusion (f32[4096], bf16[64,3,65,4096])",
    "%fusion.2346 fusion f32[640,4096]",
    "%fusion.2347 fusion (bf16[64,3,640], f32[64,65,640])",
]
LATTICE = [
    "%fusion.1931 fusion f32[64,65]",
    "%select_fusion.12 fusion (f32[64,33], f32[64,32])",
    "%fusion.77 fusion f32[284,64,65]",
    "%fusion.78 fusion (f32[64,284,65], f32[64,284,64])",
]
OTHER = [
    "%while.2087 while (s32[], f32[640,4096], f32[4096], f32[64,65,640], bf",
    "%fusion.2081 fusion bf16[640,8192]",
    "%fusion.2051 fusion (f32[64,2048], f32[64,2048], f32[64,2048])",
    "%lstmp_scan_fwd.3 custom-call [mosaic] (f32[284,64,640], f32[284,64,2",
    "%fusion.9 fusion f32[64,1]",
    "%convolution.5 convolution bf16[64,567,8192]",
]


@pytest.mark.parametrize("key", JOINT)
def test_joint_tiles_are_found_by_shape(key):
    assert _rnnt.classify(key, F) == "joint"


@pytest.mark.parametrize("key", LATTICE)
def test_lattice_rows_are_found_by_shape(key):
    assert _rnnt.classify(key, F) == "lattice"


@pytest.mark.parametrize("key", OTHER)
def test_the_rest_of_the_step_is_neither(key):
    assert _rnnt.classify(key, F) is None


def record():
    ops = {k: 0.010 for k in JOINT}            # 60 ms over two steps
    ops.update({k: 0.004 for k in LATTICE})    # 16 ms
    ops.update({k: 0.100 for k in OTHER})
    model = types.SimpleNamespace(
        rnn_layers=8, rnn_hidden=2048, rnn_proj=640, frame_stack=3,
        time_reduction_layer=2, time_reduction=2, rnnt_pred_layers=2,
        rnnt_pred_hidden=2048, rnnt_pred_embed=128, rnnt_joint_dim=640,
        vocab_size=4096)
    return {
        "driver": "train_rnnt", "model": model, "units": 2, "chips": 1,
        "warmup_steps": 2, "t_window_start": 10.0, "t_window_end": 11.0,
        "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        "spans": [("train.step", 10.0, 10.4), ("train.step", 10.5, 10.98),
                  ("pipeline.data_wait", 10.40, 10.41),
                  ("pipeline.device_prefetch", 10.41, 10.42)],
        "counters": {
            "rows_per_step": 64, "enc_frames": 284, "max_label_len": 64,
            "joint_tile_frames": 3, "bucket_frames": 1700,
            "num_features": 161,
            "valid_frames": [[1700] * 64, [1201] * 64],
            "label_lens": [[64] * 64, [43] * 64]},
        "trace": {"op_seconds": ops, "kernels": [], "busy_s": 0.9},
    }


def read(name, rec):
    return importlib.import_module(
        f"benchmark.layer_metrics.{name}").read(rec)


def test_readers_on_the_record():
    rec = record()
    assert read("rnnt_joint_ms", rec) == pytest.approx(30.0)
    assert read("rnnt_lattice_ms", rec) == pytest.approx(8.0)
    # 94.3 ms is the least a step's joint can take on a v5e.
    assert read("rnnt_joint_roofline", rec) == pytest.approx(
        100 * 94.3 / 30.0, rel=0.01)
    assert rec["counters"]["rnnt_joint_bound_by"] == "compute"
    assert read("rnnt_step_ms", rec) == pytest.approx(440.0)
    assert read("rnnt_input_wait_pct", rec) == pytest.approx(2.0)
    # Step 1 serves pool batch 0 (all full: no padding), step 2 batch 1.
    full, short = 284 * 65, 201 * 44
    assert read("rnnt_pad_node_pct", rec) == pytest.approx(
        100 * (1 - (full + short) / (2 * full)))
    assert 0 < read("rnnt_mfu_pct", rec) < 100
    # No named kernel in this record: the kernel readers say nothing.
    assert read("rnnt_enc_scan_ms", rec) is None
    assert read("rnnt_lstmp_roofline", rec) is None


def test_named_lstmp_kernels_are_read_by_name():
    from test_kernel_metrics import CALL

    def event(kernel, shape):
        facts = {"kernel": kernel, "variant": "resident", "reverse": 0,
                 "t": 284, "b": 64, "h": 2048, "gates": 4, "p": 640}
        inner = ",\n".join(f'"{k}":"{v}"' for k, v in sorted(facts.items()))
        return f"%{kernel}.3 = {shape} {CALL}{{\n{inner}\n}}}}"

    rec = record()
    rec["trace"]["kernels"] = [
        (event("lstmp_scan_fwd", "f32[284,64,640]{2,1,0}"), 0.004),
        (event("lstmp_scan_bwd", "bf16[284,64,8192]{2,1,0}"), 0.008)] * 2
    assert read("rnnt_enc_scan_ms", rec) == pytest.approx(12.0)
    share = read("rnnt_lstmp_roofline", rec)
    assert 0 < share < 100
    assert rec["counters"]["rnnt_lstmp_bound_by"] == {"compute": 4}


def test_other_drivers_records_are_skipped():
    from benchmark import harness

    rec = record()
    rec["driver"] = "train"
    for name in ("rnnt_joint_ms", "rnnt_step_ms", "rnnt_mfu_pct"):
        assert harness.metric_value({"name": name}, rec, traced=True) is None
    rec["driver"] = "train_rnnt"
    for name in ("train_mfu_pct", "rnn_scan_roofline", "train_step_ms"):
        assert harness.metric_value({"name": name}, rec, traced=True) is None
