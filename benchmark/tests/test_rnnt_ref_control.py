"""Controls of the limits in ``drivers/train_rnnt.REF_TOL``: the plain
reference with one fault put in, against the plain reference, has to
come out NOT correct under the limits the cell runs with. Both sides
are float32 on the CPU, so a reading here is the fault's own size; the
same controls at the published widths on the chip are in PERF.md
(section 6, PR 26).

The faults: every matrix rounded to float8 (e4m3), the nearest
precision below the configuration's bfloat16; the joint without its
``tanh``; padded lattice nodes counted (every utterance taken at the
full T' and U).
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

import conftest  # noqa: F401  (puts the checkout on sys.path)
from benchmark.drivers import train_rnnt
from benchmark.reference import rnnt_ref

# Wider than the configuration file's ``rehearsal`` group: rounding
# errors average out over a sum's terms, so the control needs sums of
# more than 16 to say anything about 640.
SIZES = {"rnn_layers": 3, "rnnt_pred_layers": 1, "rnn_hidden": 64,
         "rnnt_pred_hidden": 64, "rnn_proj": 32, "rnnt_joint_dim": 32,
         "rnnt_pred_embed": 16, "vocab_size": 128, "dtype": "float32"}


def round_to_float8(params):
    return jax.tree.map(
        lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype)
        if x.ndim >= 2 else x, params)


def faulty_outputs(mcfg, params, batch, *, tanh=True, masked=True):
    """``train_rnnt.plain_outputs`` with the joint's ``tanh`` or the
    masking of padded nodes left out."""
    feats, lens, labels, label_lens = batch
    last = f"lstmp{mcfg.rnn_layers - 1}"

    def mean_nll(p):
        enc, enc_lens = rnnt_ref.encode(mcfg, p, feats, lens)
        pred = rnnt_ref.predict(mcfg, p, labels)
        j = p["joint"]
        h = ((enc @ j["enc_proj"]["kernel"] + j["enc_proj"]["bias"]
              )[:, :, None, :]
             + (pred @ j["pred_proj"]["kernel"])[:, None, :, :])
        if tanh:
            h = jnp.tanh(h)
        blank, emit = rnnt_ref.picks(jax.nn.log_softmax(
            h @ j["out"]["kernel"] + j["out"]["bias"], axis=-1), labels)
        t_lens, u_lens = enc_lens, label_lens
        if not masked:
            t_lens = jnp.full_like(enc_lens, enc.shape[1])
            u_lens = jnp.full_like(label_lens, labels.shape[1])
        nll = rnnt_ref.lattice_nll(blank, emit, t_lens, u_lens)
        return jnp.mean(nll), {"enc": enc, "lens": enc_lens,
                               "blank": blank, "emit": emit, "nll": nll}

    with jax.default_matmul_precision("highest"):
        (_, out), grads = jax.value_and_grad(mean_nll, has_aux=True)(params)
    return {**out, "grad_w_o": grads["joint"]["out"]["kernel"],
            "grad_w_p": grads["enc"][last]["wp"]}


FAULTS = {
    "float8": lambda plain, m, p, b: plain(round_to_float8(p), b),
    "no_tanh": lambda plain, m, p, b: jax.jit(
        lambda p, b: faulty_outputs(m, p, b, tanh=False))(p, b),
    "pad_counted": lambda plain, m, p, b: jax.jit(
        lambda p, b: faulty_outputs(m, p, b, masked=False))(p, b),
}


@pytest.fixture(scope="module")
def case():
    from deepspeech_tpu.config import get_config
    from deepspeech_tpu.models.transducer import create_rnnt_model

    cfg = get_config("rnnt_he2019")
    cfg = dataclasses.replace(
        cfg, model=dataclasses.replace(cfg.model, **SIZES))
    ctx = type("Ctx", (), {"seed": 7, "param": staticmethod(
        lambda k, d=None: {"ref_rows": 4, "ref_frames": 96}.get(k, d))})
    batch = tuple(jnp.asarray(x) for x in train_rnnt._sample(cfg, ctx))
    model = create_rnnt_model(cfg.model)
    params = jax.jit(lambda: model.init(
        jax.random.PRNGKey(0), *batch, method=type(model).loss))()["params"]
    plain = jax.jit(
        lambda p, b: train_rnnt.plain_outputs(cfg.model, p, b))
    return cfg.model, params, batch, plain


def test_unfaulted_reference_reads_zero(case):
    _, params, batch, plain = case
    want = jax.device_get(plain(params, batch))
    errs = train_rnnt.errors(want, want, batch[2], batch[3])
    assert set(errs) == set(train_rnnt.REF_TOL)
    assert all(v == 0.0 for v in errs.values())


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct_under_the_cells_limits(case, fault):
    mcfg, params, batch, plain = case
    want = jax.device_get(plain(params, batch))
    got = jax.device_get(FAULTS[fault](plain, mcfg, params, batch))
    errs = train_rnnt.errors(got, want, batch[2], batch[3])
    over = {k: round(v / train_rnnt.REF_TOL[k], 2)
            for k, v in errs.items() if v > train_rnnt.REF_TOL[k]}
    print(json.dumps({"fault": fault, "errors": errs, "over": over}))
    assert over, errs
