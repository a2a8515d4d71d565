"""Controls of the limits in ``drivers/transcribe_lm.REF_TOL``: the
plain reference with one fault put in, against the plain reference, has
to come out NOT correct under the limits the cell runs with. Both sides
are float32 on the CPU, so a reading here is the fault's own size; the
float8 control at the published widths on the chip is
``tools/axk1_ref_seeds.py``'s (readings in PERF.md section 6, PR 32).
As in the cell, the faulty side plays the system.

The faults (``axk1_ref.FAULTS``): every matrix rounded to float8
(e4m3), the nearest precision below the configuration's bfloat16; plain
top-8 without groups (the other reading of ``topk_method: "none"``);
the softmax scale without YaRN's ``m^2``; ``c_kv`` used before its
norm; the key's rotation left out; the shared expert left out; routed
scaling 1 instead of 2.5.
"""

import dataclasses
import json

import jax
import pytest

import conftest  # noqa: F401  (puts the checkout on sys.path)
from benchmark.drivers import transcribe_lm
from benchmark.reference import axk1_ref

# Wider than the configuration file's ``rehearsal`` group (rounding
# errors average out over a sum's terms, so the control needs sums of
# more than 64 to say anything about 7168), with the published rule's 8
# groups, 4 kept, top-8, and YaRN's factor.
SIZES = {"lfm_hidden": 128, "lfm_heads": 4, "lfm_ffn_dim": 256,
         "lfm_expert_dim": 64, "lfm_experts": 64, "lfm_top_k": 8,
         "moe_groups": 8, "moe_groups_kept": 4, "experts_held": 16,
         "expert_offset": 8, "vocab_size": 256,
         "lfm_layer_types": ("latent_attention",) * 4,
         "mla_q_rank": 48, "mla_kv_rank": 32, "mla_nope_dim": 16,
         "mla_rope_dim": 8, "mla_v_dim": 16, "lfm_seq_positions": 40,
         "moe_rows_bound": 0.0, "moe_impl": "xla", "dtype": "float32"}
FRAMES, LABELS = 96, 16


@pytest.fixture(scope="module")
def case():
    from deepspeech_tpu.config import get_config
    from deepspeech_tpu.models.lfm2 import seeded_variables

    cfg = get_config("ax_k1")
    cfg = dataclasses.replace(
        cfg, model=dataclasses.replace(cfg.model, **SIZES),
        data=dataclasses.replace(cfg.data, max_label_len=LABELS))
    ctx = type("Ctx", (), {"seed": 7, "param": staticmethod(
        lambda k, d=None: {"ref_rows": 4, "bucket_frames": FRAMES,
                           "valid_frames": [48, 96],
                           "labels_per_frame": 0.15}.get(k, d))})
    sample = transcribe_lm._sample(cfg, ctx)
    params, _ = seeded_variables(cfg, 0)
    # Weights of size 1 after every product: at std 0.02 and a width of
    # 128 softmax and sigmoid sit near their middles, and a fault in
    # either reads as rounding; at the published width they do not.
    params = jax.tree.map(
        lambda x: x * (x.shape[-2] ** -0.5 / 0.02) if x.ndim >= 2 else x,
        params)

    def read(faults=()):
        """The readings of the reference under ``faults`` as the
        system, against the sound reference."""
        args = (cfg.model, params, sample["features"],
                sample["feat_lens"], sample["labels"],
                sample["label_lens"], cfg.model.lfm_seq_positions)
        got, want = jax.device_get(
            (axk1_ref.forward(*args, faults), axk1_ref.forward(*args)))
        return transcribe_lm.errors(got, want)

    return read


def test_unfaulted_reference_reads_zero(case):
    errs = case()
    assert set(errs) | {"forms"} == \
        set(transcribe_lm.REF_TOL) | {"chosen_differ"}
    assert all(v == 0.0 for v in errs.values())
    assert transcribe_lm.within(errs, transcribe_lm.REF_TOL,
                                transcribe_lm.REF_CHOSEN_DIFFER)


@pytest.mark.parametrize("fault", axk1_ref.FAULTS)
def test_fault_is_not_correct_under_the_cells_limits(case, fault):
    errs = case((fault,))
    limits = {**transcribe_lm.REF_TOL,
              "chosen_differ": transcribe_lm.REF_CHOSEN_DIFFER}
    over = {k: round(v / limits[k], 2) for k, v in errs.items()
            if v > limits[k]}
    print(json.dumps({"fault": fault, "errors": errs, "over": over}))
    assert over, errs
    assert not transcribe_lm.within(errs, transcribe_lm.REF_TOL,
                                    transcribe_lm.REF_CHOSEN_DIFFER)
