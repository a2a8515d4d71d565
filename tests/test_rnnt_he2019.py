"""The streaming RNN-T of He et al. 2019 (preset ``rnnt_he2019``): the
tiled joint + loss against the materialised oracle, the system against
the benchmark's plain reference at rehearsal size, the
LSTM-with-projection recurrence, and the normal train -> decode path."""

import dataclasses
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeech_tpu.config import get_config
from deepspeech_tpu.ops.transducer import (LOG_ZERO, joint_tile_frames,
                                           rnnt_joint_loss,
                                           rnnt_joint_scores,
                                           transducer_loss)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def rehearsal_sizes() -> dict:
    """The configuration file's ``rehearsal`` group."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "rnnt_he2019.json")) as f:
        return json.load(f)["rehearsal"]


def rehearsal_model(**kw):
    """The preset at those sizes."""
    return dataclasses.replace(get_config("rnnt_he2019").model,
                               **{**rehearsal_sizes(), **kw})


# -- the tiled joint + loss ---------------------------------------------

def _joint_case(seed=0, b=5, t=7, u=5, j=6, v=11):
    rng = np.random.default_rng(seed)
    f32 = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa
    e, p, w, bias = f32(b, t, j), f32(b, u + 1, j), f32(j, v), f32(v)
    labels = jnp.asarray(rng.integers(1, v, size=(b, u)), jnp.int32)
    # Full, padded in T', padded in both, one frame and no label,
    # zero frames (no lattice at all).
    t_lens = jnp.asarray([t, t - 2, 3, 1, 0], jnp.int32)
    u_lens = jnp.asarray([u, 2, u, 0, 3], jnp.int32)
    return e, p, w, bias, labels, t_lens, u_lens


def _materialised(e, p, w, bias, labels, t_lens, u_lens):
    h = jnp.tanh(e[:, :, None, :] + p[:, None, :, :])
    lp = jax.nn.log_softmax(h @ w + bias, axis=-1)
    return transducer_loss(lp, labels, t_lens, u_lens), lp


# 3 and 4 do not divide T' = 7: the last tile is padded.
@pytest.mark.parametrize("tile_t", [None, 1, 3, 4, 7])
def test_tiled_loss_and_gradients_match_materialised(tile_t):
    e, p, w, bias, labels, t_lens, u_lens = _joint_case()
    weights = jnp.asarray([1.0, 0.5, 2.0, 1.5, 1.0])
    real = t_lens > 0

    def total(fn):
        def f(e, p, w, bias):
            nll = fn(e, p, w, bias)
            return jnp.sum(jnp.where(real, nll, 0.0) * weights), nll
        return jax.value_and_grad(f, argnums=(0, 1, 2, 3), has_aux=True)

    (_, want), g_want = total(
        lambda *a: _materialised(*a, labels, t_lens, u_lens)[0])(
            e, p, w, bias)
    (_, got), g_got = jax.jit(total(
        lambda *a: rnnt_joint_loss(*a, labels, t_lens, u_lens,
                                   tile_t=tile_t)))(e, p, w, bias)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert got[4] == np.float32(-LOG_ZERO)  # the zero-frame sentinel
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)
    # Padded nodes contribute EXACTLY zero: frames past T'_b to de,
    # prefix rows past U_b to dp, and a zero-frame row to both.
    de, dp = np.asarray(g_got[0]), np.asarray(g_got[1])
    for i, (tl, ul) in enumerate(zip(np.asarray(t_lens),
                                     np.asarray(u_lens))):
        if tl == 0:
            assert not de[i].any() and not dp[i].any()
            continue
        assert not de[i, tl:].any()
        assert not dp[i, ul + 1:].any()
        assert de[i, :tl].any()


def test_tiled_scores_are_the_lattice_log_probabilities():
    e, p, w, bias, labels, t_lens, u_lens = _joint_case(seed=1)
    _, lp = _materialised(e, p, w, bias, labels, t_lens, u_lens)
    blank, emit = rnnt_joint_scores(e, p, w, bias, labels, u_lens,
                                    tile_t=3)
    np.testing.assert_allclose(blank, lp[..., 0], rtol=1e-5, atol=1e-5)
    want = jnp.take_along_axis(
        lp[:, :, :-1], labels[:, None, :, None], axis=-1)[..., 0]
    for i, ul in enumerate(np.asarray(u_lens)):
        np.testing.assert_allclose(emit[i, :, :ul], want[i, :, :ul],
                                   rtol=1e-5, atol=1e-5)
        assert (np.asarray(emit[i, :, ul:]) == LOG_ZERO).all()


def test_tile_is_a_function_of_shapes():
    assert joint_tile_frames(64, 65, 284) == 3       # the cell's
    assert joint_tile_frames(2, 5, 7) == 7           # whole T' fits
    assert joint_tile_frames(512, 65, 284) == 1      # never below one


def test_compiled_loss_holds_no_lattice():
    """At a size where the [B,T',U+1,V] lattice would dominate, the
    compiled value-and-gradient of the training loss holds no tensor of
    that many elements and takes a fraction of its bytes."""
    b, t, u1, j, v = 4, 64, 9, 16, 512
    lattice = b * t * u1 * v                          # 1.18 M elements
    tile_t = 4
    args = (jnp.zeros((b, t, j)), jnp.zeros((b, u1, j)),
            jnp.zeros((j, v)), jnp.zeros((v,)))
    labels = jnp.ones((b, u1 - 1), jnp.int32)
    lens = (jnp.full((b,), t, jnp.int32), jnp.full((b,), u1 - 1,
                                                   jnp.int32))

    def tiled(e, p, w, bias):
        return jnp.mean(rnnt_joint_loss(e, p, w, bias, labels, *lens,
                                        tile_t=tile_t))

    def materialised(e, p, w, bias):
        return jnp.mean(_materialised(e, p, w, bias, labels, *lens)[0])

    def compiled(fn):
        return jax.jit(jax.value_and_grad(fn, argnums=(0, 1, 2, 3))
                       ).lower(*args).compile()

    def largest(hlo):
        return max(int(np.prod([int(d) for d in dims.split(",")]))
                   for dims in re.findall(r"f32\[([\d,]+)\]", hlo))

    got, ref = compiled(tiled), compiled(materialised)
    assert largest(ref.as_text()) >= lattice      # the oracle holds it
    assert largest(got.as_text()) <= lattice * tile_t // t
    assert (got.memory_analysis().temp_size_in_bytes
            < lattice * 4 // 4)
    assert ref.memory_analysis().temp_size_in_bytes > lattice * 4


# -- the system against the benchmark's plain reference -------------------

def _system_case(seed=3, b=3, frames=42, u=4):
    from deepspeech_tpu.models.transducer import create_rnnt_model

    mcfg = rehearsal_model()
    model = create_rnnt_model(mcfg)
    rng = np.random.default_rng(seed)
    lens = np.asarray([frames, frames - 13, 16], np.int32)
    feats = rng.standard_normal((b, frames, 161)).astype(np.float32)
    feats *= np.arange(frames)[None, :, None] < lens[:, None, None]
    labels = rng.integers(1, mcfg.vocab_size, size=(b, u)).astype(np.int32)
    u_lens = np.asarray([u, 3, 0], np.int32)
    batch = tuple(jnp.asarray(x) for x in (feats, lens, labels, u_lens))
    params = model.init(jax.random.PRNGKey(seed), *batch,
                        method=type(model).loss)["params"]
    # Random gains and biases, so that layer norm's parameters count.
    params = jax.tree.map(
        lambda x: x + 0.1 * jax.random.normal(
            jax.random.PRNGKey(x.size), x.shape), params)
    return mcfg, model, params, batch


def test_system_matches_plain_reference_at_rehearsal_size():
    """Encoder output, the lattice's log-probabilities, per-utterance
    loss and EVERY gradient against benchmark/reference/rnnt_ref.py.
    Both sides are float32 on the CPU and differ only in the order of
    sums (tiles, associative scans, the custom gradient), so the
    tolerance is float32 round-off over a few hundred steps: 1e-4 of
    each tensor's rms; a wrong gate order, a missing +1 or tanh, or an
    unmasked padded node is off by more than 1e-2."""
    from benchmark.reference import rnnt_ref
    from deepspeech_tpu.models.transducer import RNNTModel

    mcfg, model, params, batch = _system_case()
    feats, lens, labels, u_lens = batch
    want, want_grads = jax.jit(lambda p, *b: (
        rnnt_ref.forward(mcfg, p, *b),
        rnnt_ref.loss_and_grads(mcfg, p, *b)[1]))(params, *batch)

    enc, enc_lens = model.apply({"params": params}, feats, lens,
                                method=RNNTModel.encode)
    assert np.array_equal(enc_lens, want["lens"])
    assert int(enc_lens[0]) == 42 // 6
    assert rnnt_ref.rms_rel(enc, want["enc"]) < 1e-4
    lp, _ = model.apply({"params": params}, *batch)   # materialised
    mask = rnnt_ref.lattice_mask(enc_lens, u_lens, lp.shape[1],
                                 lp.shape[2])
    got_blank, got_emit = rnnt_ref.picks(lp, labels)
    assert rnnt_ref.rms_rel(got_blank, want["blank"], mask) < 1e-4
    emask = mask[:, :, :-1] & (np.arange(labels.shape[1])[None, None, :]
                               < np.asarray(u_lens)[:, None, None])
    assert rnnt_ref.rms_rel(got_emit, want["emit"], emask) < 1e-4

    def mean_loss(p):
        nll, _ = model.apply({"params": p}, *batch, True,
                             method=RNNTModel.loss)
        return jnp.mean(nll), nll

    (_, nll), grads = jax.jit(jax.value_and_grad(
        mean_loss, has_aux=True))(params)
    np.testing.assert_allclose(nll, want["nll"], rtol=1e-5)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    flat_got = jax.tree_util.tree_leaves_with_path(grads)
    assert len(flat_got) == len(flat_want) == 24
    for path, g in flat_got:
        assert rnnt_ref.rms_rel(g, flat_want[path]) < 1e-4, \
            jax.tree_util.keystr(path)


def test_preset_has_the_published_sizes():
    cfg = get_config("rnnt_he2019")
    m = cfg.model
    assert (m.rnn_layers, m.rnn_hidden, m.rnn_proj) == (8, 2048, 640)
    assert (m.rnnt_pred_layers, m.rnnt_pred_hidden) == (2, 2048)
    assert (m.rnnt_joint_dim, m.vocab_size, m.frame_stack) == (640, 4096, 3)
    assert m.time_stride == 6 and cfg.data.max_label_len == 64
    assert cfg.train.objective == "rnnt"
    # 1700 frames -> 567 stacked -> T' = 284.
    assert -(-(-(-1700 // 3)) // 2) == 284
    from deepspeech_tpu.models.transducer import create_rnnt_model

    model = create_rnnt_model(m)
    shapes = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 12, 161)),
            jnp.full((1,), 12, jnp.int32), jnp.zeros((1, 2), jnp.int32),
            jnp.full((1,), 2, jnp.int32), method=type(model).loss))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert 120e6 < n < 125e6, n


# -- the LSTM-with-projection recurrence ----------------------------------

def test_lstmp_prediction_step_matches_full_scan():
    """The decode path's carried one-step (c, r) of every layer == the
    training path's full prefix scan, row for row."""
    from deepspeech_tpu.models.transducer import LSTMPPredictionNet

    mcfg = rehearsal_model(rnnt_pred_layers=2)
    net = LSTMPPredictionNet(mcfg, mcfg.rnnt_pred_hidden)
    rng = np.random.default_rng(4)
    labels = jnp.asarray(rng.integers(1, mcfg.vocab_size, size=(2, 5)),
                         jnp.int32)
    variables = net.init(jax.random.PRNGKey(0), labels)
    rows = net.apply(variables, labels)  # [2, 6, P]
    assert rows.shape == (2, 6, mcfg.rnn_proj)
    state = jnp.zeros(
        (2, 2 * (mcfg.rnnt_pred_hidden + mcfg.rnn_proj)), jnp.float32)
    seq = jnp.concatenate([jnp.zeros((2, 1), jnp.int32), labels], axis=1)
    for u in range(6):
        out, state = net.apply(variables, seq[:, u], state,
                               method=LSTMPPredictionNet.step)
        np.testing.assert_allclose(out, rows[:, u], rtol=1e-5, atol=1e-5)


def test_lstmp_masked_frames_carry_state_and_remat_is_exact():
    from deepspeech_tpu.models.rnn import lstmp_scan

    rng = np.random.default_rng(5)
    b, t, h, p = 3, 11, 8, 4
    f32 = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa
    xp, w_r, w_p = f32(b, t, 4 * h), f32(p, 4 * h), f32(h, p)
    scale, bias = 1.0 + 0.1 * f32(4 * h), 0.1 * f32(4 * h)
    lens = np.asarray([t, 6, 1])
    mask = jnp.asarray(np.arange(t)[None, :] < lens[:, None], jnp.float32)
    ys, (c, r) = lstmp_scan(xp, mask, w_r, w_p, scale, bias,
                            return_final=True)
    for i, n in enumerate(lens):
        # Past its length a row repeats its last valid state.
        np.testing.assert_array_equal(ys[i, n:], jnp.broadcast_to(
            ys[i, n - 1], (t - n, p)))
        np.testing.assert_array_equal(r[i], ys[i, n - 1])
    # The final carry is what a second call continues from.
    ys2 = lstmp_scan(xp[:, 6:], mask[:, 6:], w_r, w_p, scale, bias,
                     cr0=lstmp_scan(xp[:, :6], mask[:, :6], w_r, w_p,
                                    scale, bias, return_final=True)[1])
    np.testing.assert_allclose(ys2, ys[:, 6:], rtol=1e-6, atol=1e-6)

    def loss(remat):
        return lambda xp, w_r, w_p: jnp.sum(lstmp_scan(
            xp, mask, w_r, w_p, scale, bias, remat_chunk=remat) ** 2)

    want = jax.grad(loss(0), argnums=(0, 1, 2))(xp, w_r, w_p)
    got = jax.grad(loss(4), argnums=(0, 1, 2))(xp, w_r, w_p)  # 4 ∤ 11
    for a, b_ in zip(got, want):
        np.testing.assert_allclose(a, b_, rtol=1e-5, atol=1e-6)


def test_stack_frames_pads_and_rounds_lengths_up():
    from deepspeech_tpu.models.rnn import stack_frames

    x = jnp.arange(2 * 7 * 2, dtype=jnp.float32).reshape(2, 7, 2)
    y, lens = stack_frames(x, jnp.asarray([7, 4]), 3)
    assert y.shape == (2, 3, 6) and lens.tolist() == [3, 2]
    np.testing.assert_array_equal(y[0, 0], x[0, :3].reshape(-1))
    assert not np.asarray(y[0, 2, 2:]).any()  # the padded frames


# -- the normal path: train CLI -> checkpoint -> infer CLI -----------------

def _cli(ckpt_dir):
    return (["--config=rnnt_he2019", "--synthetic=8",
             f"--train.checkpoint_dir={ckpt_dir}", "--data.batch_size=8",
             "--data.bucket_frames=48", "--data.max_label_len=4",
             "--train.optimizer=adamw"]
            + [f"--model.{k}={v}" for k, v in rehearsal_sizes().items()
               if k != "vocab_size"])


def test_preset_trains_through_trainer_and_decodes(tmp_path):
    """``python -m deepspeech_tpu.train --config=rnnt_he2019`` (no
    ``*_impl``, no environment switch) trains through ``Trainer.fit``;
    ``infer --decode.mode=rnnt_greedy`` decodes its checkpoint (the beam
    shares the prediction step and rescores through the same tiled
    loss: tests/test_transducer.py)."""
    from deepspeech_tpu import infer as infer_mod
    from deepspeech_tpu import train as train_mod

    ckpt = str(tmp_path / "ckpt")
    log = str(tmp_path / "train.jsonl")
    train_mod.main(_cli(ckpt) + ["--train.epochs=3", "--train.log_every=1",
                                 f"--log-file={log}"])
    events = [json.loads(line) for line in open(log)]
    losses = [e["loss"] for e in events if e["event"] == "train_step"]
    assert len(losses) == 3 and all(np.isfinite(losses))
    assert losses[-1] < losses[0]
    out = str(tmp_path / "greedy.jsonl")
    infer_mod.main(_cli(ckpt) + [f"--checkpoint-dir={ckpt}",
                                 "--decode.mode=rnnt_greedy",
                                 f"--log-file={out}"])
    summary = [json.loads(line) for line in open(out)]
    summary = [e for e in summary if e["event"] == "infer_summary"]
    assert summary and summary[0]["n_utts"] == 8
