"""Controls of the limits in ``drivers/train_long.REF_TOL``: the plain
reference with one fault put in, against the plain reference, has to
come out NOT correct under the limits the cell runs with. Both sides
are float32 on the CPU, so a reading here is the fault's own size; the
float8 control at the published widths on the chip is
``tools/smallthinker_ref_seeds.py``'s (readings in PERF.md section 6,
PR 45). As in the cell, the faulty side plays the system: the sound
reference's gradients are routed by ITS chosen sets.

The faults (``smallthinker_ref.FAULTS``): every matrix rounded to
float8 (e4m3), the nearest precision below the configuration's
bfloat16; the router fed the normed input, or the stream after
attention; sigmoid scores for the softmax; a softmax over all logits
whose chosen ones are not renormalised; SiLU for ReLU; rotation on the
global layer, none on a sliding one; the window off by one, or
dropped; a q/k norm added; key/value head ``h mod kv`` for ``h // rep``;
a tied head.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import conftest  # noqa: F401  (puts the checkout on sys.path)
from benchmark.drivers import train_lfm2, train_long
from benchmark.reference import smallthinker_ref as ref

# Wider than the configuration file's ``rehearsal`` group: rounding
# errors average out over a sum's terms, so the control needs sums of
# more than 64 to say anything about 2560. 14 / 2 heads keep the 7
# query heads a key/value head; 40 positions pass the window of 16.
SIZES = {"lfm_hidden": 128, "lfm_heads": 14, "lfm_kv_heads": 2,
         "lfm_head_dim": 16, "lfm_window": 16, "lfm_expert_dim": 64,
         "lfm_experts": 16, "lfm_top_k": 3, "experts_held": 8,
         "expert_offset": 4, "vocab_size": 256, "moe_rows_bound": 0.0,
         "moe_impl": "xla", "dtype": "float32", "lfm_seq_positions": 0}
FRAMES, LABELS = 240, 16


@pytest.fixture(scope="module")
def case():
    from deepspeech_tpu.config import get_config
    from deepspeech_tpu.models.lfm2 import (create_lfm2_model,
                                            seq_positions)

    cfg = get_config("smallthinker_21b_a3b")
    cfg = dataclasses.replace(
        cfg, model=dataclasses.replace(cfg.model, **SIZES),
        data=dataclasses.replace(cfg.data, max_label_len=LABELS))
    ctx = type("Ctx", (), {"seed": 7, "param": staticmethod(
        lambda k, d=None: {"ref_rows": 3, "bucket_frames": FRAMES,
                           "valid_frames": [160, FRAMES],
                           "labels_per_frame": 0.05}.get(k, d))})
    batch = tuple(jnp.asarray(x) for x in train_long.sample(cfg, ctx))
    v = jax.jit(lambda: create_lfm2_model(cfg.model, LABELS).init(
        jax.random.PRNGKey(0), *batch, method="loss"))()
    s = seq_positions(cfg.model, FRAMES, LABELS)
    clip = cfg.train.grad_clip_norm
    sums = jax.jit(train_lfm2.leaf_sums)

    def read(faults=()):
        """The readings of the reference under ``faults`` as the
        system, against the sound reference."""
        got = train_long.plain_outputs(cfg.model, v["params"], batch, s,
                                       clip, None, faults, q_block=16)
        want = train_long.plain_outputs(cfg.model, v["params"], batch, s,
                                        clip, got["chosen"], (),
                                        q_block=16)
        both = sums(got.pop("grads"), want.pop("grads"))
        both = {"grads": both, "step": {"grads": both}}
        return train_long.errors(cfg.model, *jax.device_get(
            (got, want, both)))

    return read


def test_unfaulted_reference_reads_zero(case):
    errs = case()
    assert set(errs) == set(train_long.REF_TOL) | {"chosen_differ"}
    assert all(v == 0.0 for v in errs.values())
    assert train_lfm2.within(errs, train_long.REF_TOL,
                             train_long.REF_CHOSEN_DIFFER)


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_fault_is_not_correct_under_the_cells_limits(case, fault):
    errs = case((fault,))
    limits = {**train_long.REF_TOL,
              "chosen_differ": train_long.REF_CHOSEN_DIFFER}
    over = {k: round(v / limits[k], 2) for k, v in errs.items()
            if v > limits[k]}
    print(json.dumps({"fault": fault, "errors": errs, "over": over}))
    assert np.all(np.isfinite(list(errs.values())))
    assert over, errs
    assert not train_lfm2.within(errs, train_long.REF_TOL,
                                 train_long.REF_CHOSEN_DIFFER)


def test_the_issues_twelve_controls_are_all_there():
    assert set(ref.FAULTS) == {
        "float8_weights", "router_normed_input", "router_post_attn",
        "sigmoid_scores", "softmax_all_no_renorm", "silu_experts",
        "rope_on_global", "no_rope_on_sliding", "window_plus_1",
        "no_window", "qk_norm", "kv_head_mod", "tied_head"}
