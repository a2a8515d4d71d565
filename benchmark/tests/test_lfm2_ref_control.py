"""Controls of the limits in ``drivers/train_lfm2.REF_TOL``: the plain
reference with one fault put in, against the plain reference, has to
come out NOT correct under the limits the cell runs with. Both sides
are float32 on the CPU, so a reading here is the fault's own size; the
float8 and the bias control at the published widths on the chip are
``tools/lfm2_ref_seeds.py``'s (readings in PERF.md section 6, PR 30).
As in the cell, the faulty side plays the system: the sound
reference's gradients are routed by ITS chosen sets.

The faults: every matrix rounded to float8 (e4m3), the nearest
precision below the configuration's bfloat16; and one line of the
published block each (``lfm2_ref.FAULTS``): the chosen scores not
normalised, the bias left out of the selection, q and k not normed, a
2-tap or a non-causal filter, padded positions routed or counted in
the loss.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

import conftest  # noqa: F401  (puts the checkout on sys.path)
from benchmark.drivers import train_lfm2
from benchmark.reference import lfm2_ref

# Wider than the configuration file's ``rehearsal`` group: rounding
# errors average out over a sum's terms, so the control needs sums of
# more than 64 to say anything about 2048.
SIZES = {"lfm_hidden": 128, "lfm_heads": 4, "lfm_kv_heads": 2,
         "lfm_ffn_dim": 256, "lfm_expert_dim": 128, "lfm_experts": 16,
         "experts_held": 8, "expert_offset": 4, "vocab_size": 256,
         "moe_rows_bound": 0.0, "moe_impl": "xla",
         "dtype": "float32"}
FRAMES, LABELS = 96, 16


def round_to_float8(params):
    return jax.tree.map(
        lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype)
        if x.ndim >= 2 else x, params)


@pytest.fixture(scope="module")
def case():
    from deepspeech_tpu.config import get_config
    from deepspeech_tpu.models.lfm2 import (create_lfm2_model,
                                            seq_positions)

    cfg = get_config("lfm2_24b_a2b")
    cfg = dataclasses.replace(
        cfg, model=dataclasses.replace(cfg.model, **SIZES),
        data=dataclasses.replace(cfg.data, max_label_len=LABELS))
    ctx = type("Ctx", (), {"seed": 7, "param": staticmethod(
        lambda k, d=None: {"ref_rows": 4, "bucket_frames": FRAMES}.get(
            k, d))})
    batch = tuple(jnp.asarray(x) for x in train_lfm2._sample(cfg, ctx))
    v = jax.jit(lambda: create_lfm2_model(cfg.model, LABELS).init(
        jax.random.PRNGKey(0), *batch, method="loss"))()
    s = seq_positions(cfg.model, FRAMES, LABELS)

    clip = cfg.train.grad_clip_norm
    sums = jax.jit(train_lfm2.leaf_sums)

    def read(params, faults=()):
        """The readings of ``params`` under ``faults`` as the system,
        against the sound reference of the fixture's parameters."""
        got = train_lfm2.plain_program(cfg.model, s, clip, faults,
                                       pin=False)(params, v["buffers"],
                                                  batch)
        want = train_lfm2.plain_program(cfg.model, s, clip)(
            v["params"], v["buffers"], batch, got["chosen"])
        both = sums(got.pop("grads"), want.pop("grads"))
        both = {"grads": both, "step": {"grads": both}}
        return train_lfm2.errors(cfg.model, *jax.device_get(
            (got, want, both)))

    return v["params"], read


def test_unfaulted_reference_reads_zero(case):
    params, read = case
    errs = read(params)
    assert set(errs) == set(train_lfm2.REF_TOL) | {"chosen_differ"}
    assert all(v == 0.0 for v in errs.values())
    assert train_lfm2.within(errs, train_lfm2.REF_TOL,
                             train_lfm2.REF_CHOSEN_DIFFER)


@pytest.mark.parametrize("fault", ("float8",) + lfm2_ref.FAULTS)
def test_fault_is_not_correct_under_the_cells_limits(case, fault):
    params, read = case
    errs = read(round_to_float8(params)) if fault == "float8" \
        else read(params, (fault,))
    limits = {**train_lfm2.REF_TOL,
              "chosen_differ": train_lfm2.REF_CHOSEN_DIFFER}
    over = {k: round(v / limits[k], 2) for k, v in errs.items()
            if v > limits[k]}
    print(json.dumps({"fault": fault, "errors": errs, "over": over}))
    assert over, errs
    assert not train_lfm2.within(errs, train_lfm2.REF_TOL,
                                 train_lfm2.REF_CHOSEN_DIFFER)
