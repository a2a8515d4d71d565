"""Plain reference of the streaming RNN-T of He et al. 2019
(arXiv:1811.06621): forward pass, transducer loss and gradients.

Straightforward float32 ``jax.numpy``: no kernels, no mixed precision,
no tiling, no custom gradient, nothing imported from the program. The
joint's logits are MATERIALISED ([B,T',U+1,V]), normalised with
``log_softmax``, the lattice loss is the plain dynamic programme of
Graves (arXiv:1211.3711) with one scan over frames and one over label
positions, and gradients are autodiff of that. So it holds only what
fits: a few utterances of a few hundred frames at the published widths.

  features [B,T,F], zero past each length
    -> ``frame_stack`` adjacent frames concatenated      [B,T/3,3F]
    -> L LSTM-with-projection layers (Sak et al., arXiv:1402.1128):
         a = x W_x + b_x + r_{t-1} W_r                    [B,4H] i,f,g,o
         i,f,g,o = LN_k(a_k) * gain_k + bias_k   per gate, over H units
         c = sig(f + 1) c + sig(i) tanh(g)
         m = sig(o) tanh(c);  r = m W_p                   [B,P]
       frames past an utterance's length carry (c, r) through and give
       zero output; after layer ``time_reduction_layer`` every
       ``time_reduction`` adjacent outputs are concatenated
    -> enc [B,T',P]
  labels [B,U] -> [start = blank, y_1..y_U] -> embedding -> the same
  LSTM layers -> pred [B,U+1,P]
  joint: tanh(enc W_e + b + pred W_p) W_o + b_o -> logits [B,T',U+1,V]

Departures from the paper (the ``assumed`` list of
``benchmark/configs/rnnt_he2019.json``): (a) this repo's 161-bin
log-spectrogram at 10 ms, three frames stacked, in place of log-mel;
(b) layer normalisation on the four gate pre-activations, each on its
own, none on the cell state, epsilon 1e-5; (c) time reduction by
concatenating two adjacent frames; (d) the blank is class 0 of the V
classes; (e) no peepholes, +1 on the forget gate, one bias in the
joint's hidden layer.

Every function takes the flax parameter tree the program's model
produces and a duck-typed model configuration. On a TPU a float32
matmul runs in reduced precision unless told otherwise, so everything
runs under ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LN_EPS = 1e-5
NEG = -1e30


def _mask(lens, t):
    return (jnp.arange(t)[None, :] < lens[:, None]).astype(jnp.float32)


def _stack(x, lens, k):
    """Every k adjacent frames into one; lens -> ceil(lens / k)."""
    if k == 1:
        return x, lens
    b, t, d = x.shape
    n = -(-t // k)
    x = jnp.pad(x, [(0, 0), (0, n * k - t), (0, 0)])
    return x.reshape(b, n, k * d), -(-lens // k)


def _lstmp(model, p, x, mask):
    """One LSTM-with-projection layer: x [B,T,D], mask [B,T] ->
    r [B,T,P] (the carried projection; NOT zeroed past the length)."""
    xp = x @ p["wx"]["kernel"] + p["wx"]["bias"]
    w_r, w_p = p["wr"], p["wp"]
    b, _, h4 = xp.shape
    h = h4 // 4

    def step(carry, xt):
        c, r = carry
        a, m = xt
        a = a + r @ w_r
        if model.rnn_layer_norm:
            g = a.reshape(b, 4, h)
            mu = g.mean(-1, keepdims=True)
            var = ((g - mu) ** 2).mean(-1, keepdims=True)
            a = ((g - mu) / jnp.sqrt(var + LN_EPS)).reshape(b, h4) \
                * p["ln_scale"] + p["ln_bias"]
        i, f, g, o = (a[:, :h], a[:, h:2 * h], a[:, 2 * h:3 * h],
                      a[:, 3 * h:])
        cn = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        rn = (jax.nn.sigmoid(o) * jnp.tanh(cn)) @ w_p
        m = m[:, None]
        cn = m * cn + (1.0 - m) * c
        rn = m * rn + (1.0 - m) * r
        return (cn, rn), rn

    init = (jnp.zeros((b, h), jnp.float32),
            jnp.zeros((b, w_p.shape[1]), jnp.float32))
    _, ys = jax.lax.scan(step, init, (jnp.moveaxis(xp, 1, 0),
                                      jnp.moveaxis(mask, 1, 0)))
    return jnp.moveaxis(ys, 0, 1)


def encode(model, params, features, feat_lens):
    """(enc [B,T',P] zero past each length, lens [B])."""
    x, lens = _stack(features.astype(jnp.float32), feat_lens,
                     model.frame_stack)
    for i in range(model.rnn_layers):
        mask = _mask(lens, x.shape[1])
        x = _lstmp(model, params["enc"][f"lstmp{i}"], x, mask) \
            * mask[:, :, None]
        if i + 1 == model.time_reduction_layer:
            x, lens = _stack(x, lens, model.time_reduction)
    return x, lens


def predict(model, params, labels):
    """pred [B,U+1,P]: row u is the state after u labels."""
    p = params["pred"]
    ids = jnp.concatenate(
        [jnp.zeros((labels.shape[0], 1), labels.dtype), labels], axis=1)
    x = p["embed"]["embedding"][ids]
    mask = jnp.ones(ids.shape, jnp.float32)
    for i in range(model.rnnt_pred_layers):
        x = _lstmp(model, p[f"lstmp{i}"], x, mask)
    return x


def joint_log_probs(params, enc, pred):
    """log_softmax of the materialised joint: [B,T',U+1,V]."""
    j = params["joint"]
    e = enc @ j["enc_proj"]["kernel"] + j["enc_proj"]["bias"]
    p = pred @ j["pred_proj"]["kernel"]
    h = jnp.tanh(e[:, :, None, :] + p[:, None, :, :])
    return jax.nn.log_softmax(
        h @ j["out"]["kernel"] + j["out"]["bias"], axis=-1)


def picks(log_probs, labels):
    """(blank [B,T',U+1], emit [B,T',U]): the two log-probabilities
    each lattice node uses; emit[..., u] is that of labels[:, u]."""
    u = labels.shape[1]
    emit = jnp.take_along_axis(
        log_probs[:, :, :u, :], labels[:, None, :, None], axis=-1)[..., 0]
    return log_probs[..., 0], emit


def lattice_nll(blank, emit, enc_lens, label_lens):
    """Per-utterance negative log-likelihood [B] by the plain dynamic
    programme: alpha[t,u] = logaddexp(alpha[t-1,u] + blank[t-1,u],
    alpha[t,u-1] + emit[t,u-1]); loss = -(alpha[T-1,U] + blank[T-1,U])
    at each utterance's own T and U."""
    b, t_max, u1 = blank.shape
    start = jnp.full((b, u1), NEG).at[:, 0].set(0.0)
    emit_left = jnp.concatenate(            # emit[t, u-1], NEG at u=0
        [jnp.full((b, t_max, 1), NEG), emit], axis=2)

    def row(prev, t):
        from_blank = jnp.where(
            t == 0, start, prev + blank[:, jnp.maximum(t - 1, 0)])

        def cell(left, u):
            val = jnp.logaddexp(from_blank[:, u],
                                left + emit_left[:, t, u])
            return val, val

        _, cols = jax.lax.scan(cell, jnp.full((b,), NEG), jnp.arange(u1))
        alpha_t = cols.T
        return alpha_t, alpha_t

    _, alpha = jax.lax.scan(row, start, jnp.arange(t_max))  # [T,B,U+1]
    rows = jnp.arange(b)
    t_last = enc_lens - 1
    return -(alpha[t_last, rows, label_lens]
             + blank[rows, t_last, label_lens])


def forward(model, params, features, feat_lens, labels, label_lens):
    """Everything the comparison reads: enc, lens, blank, emit, nll."""
    with jax.default_matmul_precision("highest"):
        enc, lens = encode(model, params, features, feat_lens)
        pred = predict(model, params, labels)
        blank, emit = picks(joint_log_probs(params, enc, pred), labels)
        nll = lattice_nll(blank, emit, lens, label_lens)
    return {"enc": enc, "lens": lens, "blank": blank, "emit": emit,
            "nll": nll}


def loss_and_grads(model, params, features, feat_lens, labels,
                   label_lens):
    """(mean NLL over the batch, its gradient for every parameter)."""
    def mean_nll(p):
        return jnp.mean(forward(model, p, features, feat_lens, labels,
                                label_lens)["nll"])

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(mean_nll)(params)


def lattice_mask(enc_lens, label_lens, t_max: int, u1: int):
    """[B,T',U+1] bool: the nodes of each utterance's own lattice."""
    import numpy as np

    t = np.arange(t_max)[None, :, None] < np.asarray(enc_lens)[:, None, None]
    u = np.arange(u1)[None, None, :] <= np.asarray(label_lens)[:, None, None]
    return t & u


def rms_rel(got, want, mask=None) -> float:
    """Root-mean-square difference over the reference's root mean
    square, over the entries ``mask`` keeps (all, if None)."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if mask is not None:
        mask = np.broadcast_to(mask, want.shape)
        got, want = got[mask], want[mask]
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / max(np.sqrt(np.mean(want ** 2)), 1e-30))
