"""Controls of the limits in ``drivers/transcribe_long.REF_TOL``: the
plain reference with one fault put in, against the plain reference, has
to come out NOT correct under the limits the cell runs with. Both sides
are float32 on the CPU, so a reading here is the fault's own size; the
float8 control at the published widths on the chip is
``tools/trinity_ref_seeds.py``'s (readings in PERF.md section 6, PR
41). As in the cell, the faulty side plays the system.

The faults (``trinity_ref.FAULTS``): every matrix rounded to float8
(e4m3), the nearest precision below the configuration's bfloat16; no
window in a sliding layer; W + 1 keys; rotation on the global layer;
none on a sliding one; the output gate left out; the post-norms left
out; the input's sqrt(D) left out; ``route_scale`` 1; the selection
bias used as a weight; a ring laid out at ``p mod (W + 1)``.
"""

import dataclasses
import json

import jax
import numpy as np
import pytest

import conftest  # noqa: F401  (puts the checkout on sys.path)
from benchmark.drivers import transcribe_lm, transcribe_long
from benchmark.reference import trinity_ref

# Wider than the configuration file's ``rehearsal`` group (rounding
# errors average out over a sum's terms, so the control needs sums of
# more than 32 to say anything about 3072), with the published ratio of
# heads (6 query heads a key/value head, heads x head = 2 D), a window
# of 8 under sequences of 20-27 positions, top-4 of 64 with a selection
# bias.
SIZES = {"lfm_hidden": 128, "lfm_heads": 12, "lfm_kv_heads": 2,
         "lfm_head_dim": 16, "lfm_window": 8, "lfm_ffn_dim": 256,
         "lfm_expert_dim": 64, "lfm_experts": 64, "lfm_top_k": 4,
         "experts_held": 16, "expert_offset": 8, "vocab_size": 256,
         "lfm_seq_positions": 40, "moe_impl": "xla", "dtype": "float32"}
FRAMES, LABELS = 96, 16


@pytest.fixture(scope="module")
def case():
    from deepspeech_tpu.config import get_config
    from deepspeech_tpu.models.lfm2 import seeded_variables

    cfg = get_config("trinity_large")
    cfg = dataclasses.replace(
        cfg, model=dataclasses.replace(cfg.model, **SIZES),
        data=dataclasses.replace(cfg.data, max_label_len=LABELS))
    m = cfg.model
    ctx = type("Ctx", (), {"seed": 7, "param": staticmethod(
        lambda k, d=None: {"ref_rows": 4, "bucket_frames": FRAMES,
                           "valid_frames": [48, 96],
                           "labels_per_frame": 0.15}.get(k, d))})
    sample = transcribe_lm._sample(cfg, ctx)
    params, buffers = seeded_variables(cfg, 0)
    # Weights of size 1 after every product (at std 0.02 and a width of
    # 128 softmax and sigmoid sit near their middles, and a fault in
    # either reads as rounding); a selection bias large enough to weigh.
    params = jax.tree.map(
        lambda x: x if x.ndim < 2 else x * (x.shape[-2] ** -0.5 / 0.02),
        params)
    buffers = jax.tree.map(lambda x: 20.0 * x, buffers)
    last = -(-sample["feat_lens"] // m.frame_stack) + sample["label_lens"]

    def read(faults=()):
        """The readings of the reference under ``faults`` as the
        system, against the sound reference."""
        args = (m, params, buffers, sample["features"],
                sample["feat_lens"], sample["labels"],
                sample["label_lens"], m.lfm_seq_positions)
        got, want = jax.device_get(
            (trinity_ref.forward(*args, faults),
             trinity_ref.forward(*args)))
        return transcribe_long.errors(
            transcribe_long.reference_as_system(
                got, last, m.lfm_window, faults), want, last)

    assert int(last.max()) > 2 * m.lfm_window     # rings wrap twice
    return read


def test_unfaulted_reference_reads_zero(case):
    errs = case()
    assert set(transcribe_long.REF_TOL) - {"forms"} <= set(errs)
    assert all(v == 0.0 for v in errs.values())
    assert transcribe_lm.within(errs, transcribe_long.REF_TOL,
                                transcribe_long.REF_CHOSEN_DIFFER)


@pytest.mark.parametrize("fault", trinity_ref.FAULTS)
def test_fault_is_not_correct_under_the_cells_limits(case, fault):
    errs = case((fault,))
    limits = {**transcribe_long.REF_TOL,
              "chosen_differ": transcribe_long.REF_CHOSEN_DIFFER}
    over = {k: round(float(v / limits[k]), 2) for k, v in errs.items()
            if not v <= limits[k]}
    print(json.dumps({"fault": fault, "errors": errs, "over": over},
                     default=float))
    assert over, errs
    assert not transcribe_lm.within(errs, transcribe_long.REF_TOL,
                                    transcribe_long.REF_CHOSEN_DIFFER)
    assert all(np.isfinite(v) for v in errs.values())
