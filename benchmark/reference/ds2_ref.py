"""Plain reference forward pass of the DS2 family (inference mode).

Straightforward float32 ``jax.numpy``: no kernels, no mixed precision,
no streaming state, nothing imported from the program. It follows the
DS2 paper's layer equations as this repo's model states them:

  features [B,T,F] -> conv stack (2-D conv, masked batch norm with the
  running statistics, ReLU clipped at ``relu_clip``, frames past each
  utterance's length zeroed) -> L recurrent layers (batch norm,
  x W_x + b_x hoisted, GRU scan with cuDNN gate order r,z,n; forward
  and backward directions summed) -> optional lookahead (row)
  convolution over the next ``context`` frames + clipped ReLU -> batch
  norm -> dense head -> logits [B,T',V].

Departures from the paper, as in the program: time padding of the conv
layers is explicit ((k-s)//2 on the left) rather than SAME, so the
sampling grid does not depend on the padded length; batch norm is
weighted by the length mask; bidirectional outputs are summed, not
concatenated.

``forward`` takes the flax variable trees the program's model produces
(``params``, ``batch_stats``) and a duck-typed model configuration.
On a TPU a float32 matmul runs in reduced precision unless told
otherwise, so everything runs under
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

BN_EPS = 1e-5


def _bn(x, p, s):
    """Inference-mode batch norm over the last axis."""
    return ((x - s["mean"]) * jax.lax.rsqrt(s["var"] + BN_EPS)
            * p["scale"] + p["bias"])


def _mask(lens, t):
    return (jnp.arange(t)[None, :] < lens[:, None]).astype(jnp.float32)


def _gru(xp, mask, w_h, b_h, reverse):
    """xp [B,T,3H] (bias of the input side included), mask [B,T]."""
    b, _, h3 = xp.shape
    h = h3 // 3

    def step(hprev, xt):
        x, m = xt
        g = hprev @ w_h + b_h
        r = jax.nn.sigmoid(x[:, :h] + g[:, :h])
        z = jax.nn.sigmoid(x[:, h:2 * h] + g[:, h:2 * h])
        n = jnp.tanh(x[:, 2 * h:] + r * g[:, 2 * h:])
        hnew = (1.0 - z) * n + z * hprev
        hnew = m[:, None] * hnew + (1.0 - m[:, None]) * hprev
        return hnew, hnew

    xs = (jnp.moveaxis(xp, 1, 0), jnp.moveaxis(mask, 1, 0))
    _, ys = jax.lax.scan(step, jnp.zeros((b, h), jnp.float32), xs,
                         reverse=reverse)
    return jnp.moveaxis(ys, 0, 1)


def forward(model, params, batch_stats, features, feat_lens):
    """logits [B,T',V] float32 and output lengths [B]."""
    if model.rnn_type != "gru":
        raise ValueError("the reference covers GRU stacks")
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(features, jnp.float32)[..., None]  # [B,T,F,1]
        lens = jnp.asarray(feat_lens, jnp.int32)
        for i, (kt, kf, st, sf) in enumerate(model.conv_layers):
            pt = (kt - st) // 2
            f = x.shape[2]
            pf_total = (-(-f // sf) - 1) * sf + kf - f
            x = jax.lax.conv_general_dilated(
                x, jnp.asarray(params["conv"][f"conv{i}"]["kernel"],
                               jnp.float32),
                window_strides=(st, sf),
                padding=((pt, kt - 1 - pt),
                         (pf_total // 2, pf_total - pf_total // 2)),
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            lens = -(-lens // st)
            m = _mask(lens, x.shape[1])
            x = _bn(x, params["conv"][f"bn{i}"],
                    batch_stats["conv"][f"bn{i}"])
            x = jnp.clip(x, 0.0, model.relu_clip)
            x = x * m[:, :, None, None]
        b, t, f, c = x.shape
        x = x.reshape(b, t, f * c)
        m = _mask(lens, t)
        for i in range(model.rnn_layers):
            p = params["rnn"][f"rnn{i}"]
            if model.rnn_batch_norm:
                x = _bn(x, p["bn"], batch_stats["rnn"][f"rnn{i}"]["bn"])
            xp = x @ p["wx"]["kernel"] + p["wx"]["bias"]
            y = _gru(xp, m, p["wh_fw"], p["bh_fw"], reverse=False)
            if model.bidirectional:
                y = y + _gru(xp, m, p["wh_bw"], p["bh_bw"], reverse=True)
            x = y * m[:, :, None]
        ctx = model.lookahead_context
        if ctx > 0:
            w = params["lookahead"]["w"]  # [context, H]
            xpad = jnp.pad(x, ((0, 0), (0, ctx - 1), (0, 0)))
            x = sum(w[k] * xpad[:, k:k + t] for k in range(ctx))
            x = jnp.clip(x, 0.0, model.relu_clip)
        x = _bn(x, params["bn_out"], batch_stats["bn_out"])
        logits = x @ params["head"]["kernel"] + params["head"]["bias"]
        return logits, lens


def relative_error(got, want, lens) -> dict:
    """How far ``got`` is from the reference on the valid rows: the
    root-mean-square difference over the reference's root mean square,
    and the largest difference over the reference's largest value."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    m = (np.arange(want.shape[1])[None, :]
         < np.asarray(lens)[:, None])[..., None]
    d = (got - want) * m
    n = max(float(m.sum()) * want.shape[-1], 1.0)
    rms = float(np.sqrt((d ** 2).sum() / n)
                / max(np.sqrt(((want * m) ** 2).sum() / n), 1e-30))
    mx = float(np.abs(d).max() / max(np.abs(want * m).max(), 1e-30))
    return {"rms_rel": rms, "max_rel": mx}
