"""Controls of the limits in ``drivers/transcribe_mtp.REF_TOL``: the
plain reference with one fault put in, against the plain reference, has
to come out NOT correct under the limits the cell runs with. Both sides
are float32 on the CPU, so a reading here is the fault's own size; the
float8 control at the published widths on the chip is
``tools/xing4_ref_seeds.py``'s (readings in PERF.md section 6, PR 39).
As in the cell, the faulty side plays the system.

The faults (``xing4_ref.FAULTS``): every matrix rounded to float8
(e4m3), the nearest precision below the configuration's bfloat16; ONE
Sinkhorn round for 20; ``H_post`` without its factor 2; the clamp left
out (at ``b_res`` of std 40, where it binds); the selection bias used
as a weight; routed scaling 1 instead of 2; ``W_eh``'s halves swapped;
the second position of a verified pair seeing no first.
"""

import dataclasses
import json

import jax
import numpy as np
import pytest

import conftest  # noqa: F401  (puts the checkout on sys.path)
from benchmark.drivers import transcribe_lm, transcribe_mtp
from benchmark.reference import xing4_ref

# Wider than the configuration file's ``rehearsal`` group (rounding
# errors average out over a sum's terms, so the control needs sums of
# more than 64 to say anything about 3584), with the published 4
# streams, 20 rounds, top-4 of 64 with a selection bias, YaRN's factor
# and one draft module.
SIZES = {"lfm_hidden": 128, "lfm_heads": 4, "lfm_ffn_dim": 256,
         "lfm_expert_dim": 64, "lfm_experts": 64, "lfm_top_k": 4,
         "experts_held": 64, "vocab_size": 256,
         "lfm_layer_types": ("latent_attention",) * 3,
         "mla_q_rank": 48, "mla_kv_rank": 32, "mla_nope_dim": 16,
         "mla_rope_dim": 8, "mla_v_dim": 16, "lfm_seq_positions": 40,
         "moe_impl": "xla", "dtype": "float32"}
FRAMES, LABELS = 96, 16


@pytest.fixture(scope="module")
def case():
    from deepspeech_tpu.config import get_config
    from deepspeech_tpu.models.lfm2 import seeded_variables

    cfg = get_config("xing4_29b_a4b")
    cfg = dataclasses.replace(
        cfg, model=dataclasses.replace(cfg.model, **SIZES),
        data=dataclasses.replace(cfg.data, max_label_len=LABELS))
    ctx = type("Ctx", (), {"seed": 7, "param": staticmethod(
        lambda k, d=None: {"ref_rows": 4, "bucket_frames": FRAMES,
                           "valid_frames": [48, 96],
                           "labels_per_frame": 0.15}.get(k, d))})
    sample = transcribe_lm._sample(cfg, ctx)
    params, buffers = seeded_variables(cfg, 0)
    # Weights of size 1 after every product (at std 0.02 and a width of
    # 128 softmax and sigmoid sit near their middles, and a fault in
    # either reads as rounding); the hyper-connections' phi to logits
    # of the published width's size (0.02 * sqrt(14336) = 2.4); a
    # selection bias large enough to weigh.
    n = cfg.model.hc_streams
    params = jax.tree.map(
        lambda x: x if x.ndim < 2 else x * (
            2.4 / 0.02 / (n * 128) ** 0.5 if x.shape[-1] == n * (n + 2)
            else x.shape[-2] ** -0.5 / 0.02), params)
    buffers = jax.tree.map(lambda x: 20.0 * x, buffers)

    def read(faults=(), res_bias_std=1.0):
        """The readings of the reference under ``faults`` as the
        system, against the sound reference."""
        held = params
        if res_bias_std != 1.0:
            held = jax.tree_util.tree_map_with_path(
                lambda path, x: x.at[2 * n:].multiply(res_bias_std)
                if path[-1].key == "bias" else x, params)
        args = (cfg.model, held, buffers, sample["features"],
                sample["feat_lens"], sample["labels"],
                sample["label_lens"], cfg.model.lfm_seq_positions)
        got, want = jax.device_get(
            (xing4_ref.forward(*args, faults), xing4_ref.forward(*args)))
        return transcribe_mtp.errors(got, want)

    return read


def test_unfaulted_reference_reads_zero(case):
    errs = case()
    assert set(transcribe_mtp.REF_TOL) - {"forms"} <= set(errs)
    sums = ("h_res_columns", "h_res_rows")
    assert all(v == 0.0 for k, v in errs.items() if k not in sums)
    assert transcribe_mtp.sound(errs, transcribe_mtp.REF_TOL,
                                transcribe_mtp.REF_CHOSEN_DIFFER)


def test_the_clamp_binds_nothing_at_the_seeded_bias(case):
    """At ``b_res`` of std 1 the clamp at +-30 is never reached: the
    control needs std 40."""
    assert all(v == 0.0 for k, v in case(("no_clamp",)).items()
               if not k.startswith("h_res_"))


@pytest.mark.parametrize("fault", xing4_ref.FAULTS)
def test_fault_is_not_correct_under_the_cells_limits(case, fault):
    errs = case((fault,), 40.0 if fault == "no_clamp" else 1.0)
    limits = {**transcribe_mtp.REF_TOL,
              "chosen_differ": transcribe_mtp.REF_CHOSEN_DIFFER,
              "h_res_columns": transcribe_mtp.H_RES_COLUMNS,
              "h_res_rows": transcribe_mtp.H_RES_ROWS}
    over = {k: round(float(v / limits[k]), 2) for k, v in errs.items()
            if not v <= limits[k]}
    print(json.dumps({"fault": fault, "errors": errs, "over": over},
                     default=float))
    assert over, errs
    assert not transcribe_mtp.sound(errs, transcribe_mtp.REF_TOL,
                                    transcribe_mtp.REF_CHOSEN_DIFFER)
    if fault != "no_clamp":
        assert all(np.isfinite(v) for v in errs.values())
