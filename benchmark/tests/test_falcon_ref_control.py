"""Controls of the limits in ``drivers/transcribe_hybrid.REF_TOL``: the
plain reference with one fault put in, against the plain reference, has
to come out NOT correct under the limits the cell runs with. Both sides
are float32 on the CPU, so a reading here is the fault's own size; the
float8 control and the bfloat16-state control at the published widths
on the chip are ``tools/falcon_ref_seeds.py``'s (readings in PERF.md
section 6, PR 49). As in the cell, the faulty side plays the system.

The faults (``falcon_h1_ref.FAULTS``): each of the thirteen multipliers
that is not 1 left at 1, one at a time; every matrix rounded to float8
(e4m3); the state reset at a chunk's edge; the state taken at the padded
end of the prefix and not at ``a - 1``; the convolution's inputs taken
from padded positions; ``dt`` without ``dt_bias``; ``A`` with the wrong
sign; ``D x`` left out; the convolution's bias left out; its taps
reversed; no silu after it; norm before gate; one norm over all
channels in place of one a group; head -> group ``h mod 2``; mixer and
attention in series; attention fed the un-normed stream; theta 1e4;
key/value head ``h mod 4`` for ``h // 5``; a tied head.
"""

import dataclasses
import json

import jax
import numpy as np
import pytest

import conftest  # noqa: F401  (puts the checkout on sys.path)
from benchmark.drivers import transcribe_hybrid, transcribe_lm
from benchmark.reference import falcon_h1_ref

# Wider than the configuration file's ``rehearsal`` group (rounding
# errors average out over a sum's terms, so the control needs sums of
# more than 32 to say anything about 5120), with the published ratios:
# 5 query heads a key/value head, 16 mixer heads a group... 4 here, the
# mixer's width 0.8 D, chunks of 8 under prefixes of 9-11 positions in
# a bucket of 12 (every state is taken at a ragged end past one chunk).
SIZES = {"lfm_hidden": 160, "lfm_heads": 10, "lfm_kv_heads": 2,
         "lfm_head_dim": 16, "lfm_ffn_dim": 320, "ssm_d_ssm": 128,
         "ssm_heads": 8, "ssm_state": 32, "ssm_groups": 2, "ssm_chunk": 8,
         "vocab_size": 256, "lfm_seq_positions": 32,
         "lfm_layer_types": ("ssm_attention",) * 2, "lfm_dense_layers": 2,
         "dtype": "float32"}
FRAMES, LABELS = 96, 16


@pytest.fixture(scope="module")
def case():
    from deepspeech_tpu.config import get_config
    from deepspeech_tpu.models.lfm2 import seeded_variables

    cfg = get_config("falcon_h1_34b")
    cfg = dataclasses.replace(
        cfg, model=dataclasses.replace(cfg.model, **SIZES),
        data=dataclasses.replace(cfg.data, max_label_len=LABELS))
    m = cfg.model
    ctx = type("Ctx", (), {"seed": 7, "param": staticmethod(
        lambda k, d=None: {"ref_rows": 4, "bucket_frames": FRAMES,
                           "valid_frames": [65, 88],
                           "labels_per_frame": 0.15}.get(k, d))})
    sample = transcribe_lm._sample(cfg, ctx)
    params, _ = seeded_variables(cfg, 0)
    # Weights of size 1 after every product BEFORE the multipliers (at
    # std 0.02 and a width of 160 every nonlinearity sits near its
    # middle, and a fault in one reads as rounding); the multipliers
    # then scale as they do at the published widths.
    params = jax.tree.map(
        lambda x: x if x.ndim < 2 else x * (x.shape[-2] ** -0.5 / 0.02),
        params)
    # ... and the convolution's bias at the size it has beside its
    # inputs at the published widths (a fifth of them)
    for i in range(2):
        params[f"layer{i}"]["mixer"]["conv_bias"] *= 10.0
    a_lens = -(-sample["feat_lens"] // m.frame_stack)
    last = a_lens + sample["label_lens"]
    assert 8 < int(a_lens.min()) and int(a_lens.max()) < 12

    def read(faults=()):
        """The readings of the reference under ``faults`` as the
        system, against the sound reference."""
        args = (m, params, sample["features"], sample["feat_lens"],
                sample["labels"], sample["label_lens"],
                m.lfm_seq_positions)
        got, want = jax.device_get(
            (falcon_h1_ref.forward(*args, faults),
             falcon_h1_ref.forward(*args)))
        return transcribe_hybrid.errors(
            transcribe_hybrid.reference_as_system(got), want, last)

    return read


def limits() -> dict:
    return {k: v for k, v in transcribe_hybrid.REF_TOL.items()
            if not k.startswith("forms_")}


def test_unfaulted_reference_reads_zero(case):
    errs = case()
    assert set(limits()) == set(errs)
    assert all(v == 0.0 for v in errs.values())
    assert transcribe_hybrid.within(errs, transcribe_hybrid.REF_TOL)


def test_the_faults_are_the_issues():
    assert len(falcon_h1_ref.MULTIPLIERS) == 13
    assert len(falcon_h1_ref.FAULTS) == 13 + 18


@pytest.mark.parametrize("fault", falcon_h1_ref.FAULTS)
def test_fault_is_not_correct_under_the_cells_limits(case, fault):
    errs = case((fault,))
    over = {k: round(float(v / limits()[k]), 2) for k, v in errs.items()
            if not v <= limits()[k]}
    print(json.dumps({"fault": fault, "errors": errs, "over": over},
                     default=float))
    assert over, errs
    assert not transcribe_hybrid.within(errs, transcribe_hybrid.REF_TOL)
    assert all(np.isfinite(v) for v in errs.values())
