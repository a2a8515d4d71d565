"""Plain reference of the Falcon-H1 decoder-only recogniser
(``model_type: falcon_h1``): the full forward pass over each packed
sequence (prefix + start + labels) in straightforward ``jax.numpy``,
float32, matrix products at ``highest`` precision; the state-space
recurrence a ``lax.scan`` over the positions, never chunked; attention
with a dense causal mask; no cache, no kernels, no loop of steps, no
batching tricks. Independent of ``deepspeech_tpu``: it shares with the
program only the names of the parameters it is handed. It upcasts ONE
layer's matrices and ONE block of vocabulary rows at a time, so that at
the published widths no float32 copy of the 10.5 GB of weights exists.

The layer (ISSUE 49 writes it out; the family's public modelling code
is ``modeling_falcon_h1.py``), ``x [S, D]`` one sequence's residual
stream, d = 4,096 = H x P = 32 x 128, G = 2 groups, N = 256:

  u       RMSNorm(x; eps 1e-5): both branches read it
  mixer   p = ((ssm_in u) W_in) * m, m the ``ssm_multipliers`` over
          [z d | x d | B GN | C GN | dt H]; xBC = silu(conv(p[x|B|C])
          + b_c), depthwise, causal, 4 taps, zeros before position 0,
          tap 3 on the current position; dt = softplus(p[dt] +
          dt_bias); A = -exp(A_log); head h reads group h // (H / G);
          h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T  ([P, N], zero
          before position 0); y_t = h_t C_t + D x_t; g = y * silu(z);
          RMSNorm over each group's d / G channels, one gain [d];
          ssm_out * (g W_out)
  attn    a = attn_in * u; q = a W_q (20 x 128), k = key_mult * (a W_k),
          v = a W_v (4 x 128); q, k rotated over the whole head
          (rotate-half pairing, theta 1e11); query i attends j <= i;
          scores / sqrt(128); query head h reads key/value head
          h // 5; attn_out * (heads W_o)
  y       x + mixer + attn
  mlp     f = RMSNorm(y); y + mlp_down * ((f W_up) * silu(mlp_gate *
          (f W_gate))) W_down
  ends    h_0 = embedding_multiplier * (Emb(t) | frames W_prefix);
          logits = lm_head_multiplier * (Norm(h_L) W_head^T), untied

Departures, all shared with the program and listed under ``assumed``
in ``configs/falcon_h1_34b.json``: the audio prefix (8 stacked frames
projected by one matrix, left-packed before the transcript, id 0
starts it) entering at the embeddings' scale, positions from 0 at the
first prefix frame, the seeded norm gains and mixer constants.

``faults`` names departures put in on purpose, for the controls of
``benchmark/tests/test_falcon_ref_control.py``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

MULTIPLIERS = ("mup_embedding", "mup_lm_head", "mup_key", "mup_attn_out",
               "mup_ssm_in", "mup_ssm0", "mup_ssm1", "mup_ssm2",
               "mup_ssm3", "mup_ssm4", "mup_ssm_out", "mup_mlp0",
               "mup_mlp1")
FAULTS = tuple(k + "_is_1" for k in MULTIPLIERS) + (
    "float8_weights", "state_reset_at_chunk", "state_at_padded_end",
    "conv_state_from_padding", "no_dt_bias", "a_positive", "no_skip",
    "no_conv_bias", "taps_reversed", "no_conv_silu", "norm_before_gate",
    "one_norm", "group_h_mod", "series", "attn_unnormed", "theta_1e4",
    "kv_h_mod", "tied_head")
HI = jax.lax.Precision.HIGHEST


def _w(x, faults=()):
    """A weight as float32; under ``float8_weights`` every matrix is
    first rounded to float8 (e4m3), the nearest precision below the
    configuration's bfloat16, where it is used."""
    if "float8_weights" in faults and np.ndim(x) >= 2:
        x = jnp.asarray(x).astype(jnp.float8_e4m3fn)
    return jnp.asarray(x, jnp.float32)


def _mm(a, b, faults=()):
    return jnp.matmul(a, _w(b, faults), precision=HI)


def mult(m, name: str, faults=()):
    """The multiplier ``name`` of :data:`MULTIPLIERS` as the
    configuration states it, or 1 under its fault."""
    if name + "_is_1" in faults:
        return 1.0
    if name[-1].isdigit():
        return float(getattr(m, name[:-1])[int(name[-1])])
    return float(getattr(m, name))


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _w(gain)


def rope(theta: float, x):
    """``x [B, S, H, hd]`` at positions 0..S-1: the halves ``(x1, x2)``
    of a head become ``(x1 cos - x2 sin, x2 cos + x1 sin)``, pair i
    turning by ``position * theta^(-2i/hd)`` (tables in float64)."""
    s, hd = x.shape[1], x.shape[-1]
    freq = theta ** (-np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(s, dtype=np.float64)[:, None] * freq[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def recurrence(x, dt, a, bm, cm, at, chunk: int, faults=()):
    """``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``, ``y_t = h_t C_t``
    over the positions of ``x [B, S, H, P]`` (``dt [B, S, H]``, ``bm,
    cm [B, S, H, N]``: each head's own). Returns ``y`` and the state
    ``[B, H, P, N]`` after each of the positions ``at [B, n]``."""
    b, s, h, p = x.shape
    n = bm.shape[-1]

    def step(carry, t):
        state, kept = carry
        if "state_reset_at_chunk" in faults:
            state = jnp.where(t % chunk == 0, 0.0, state)
        decay = jnp.exp(dt[:, t] * a)                         # [B, H]
        state = decay[..., None, None] * state + (
            dt[:, t, :, None] * x[:, t])[..., None] * bm[:, t, :, None, :]
        y = jnp.sum(state * cm[:, t, :, None, :], axis=-1)    # [B, H, P]
        hit = (at == t)[:, :, None, None, None]               # [B, n, ...]
        kept = jnp.where(hit, state[:, None], kept)
        return (state, kept), y

    zero = jnp.zeros((b, h, p, n), jnp.float32)
    (_, kept), y = jax.lax.scan(
        step, (zero, jnp.zeros((b, at.shape[1], h, p, n), jnp.float32)),
        jnp.arange(s))
    return jnp.moveaxis(y, 0, 1), kept


def mixer(m, p, u, at, chunk, faults):
    """The mixer's branch output ``[B, S, D]``, the state after each
    position of ``at [B, n]`` ``[B, n, H, P, N]`` and the convolution's
    inputs at the three positions up to each ``[B, n, 3, channels]``."""
    b, s, _ = u.shape
    d, nh, n, g = m.ssm_d_ssm, m.ssm_heads, m.ssm_state, m.ssm_groups
    hp, taps, gn = d // nh, m.ssm_conv, g * n
    proj = _mm(mult(m, "mup_ssm_in", faults) * u, p["in_proj"]["kernel"],
               faults)
    by = np.repeat([mult(m, f"mup_ssm{i}", faults) for i in range(5)],
                   [d, d, gn, gn, nh]).astype(np.float32)
    proj = proj * by
    z, taken, dt = (proj[..., :d], proj[..., d:2 * d + 2 * gn],
                    proj[..., 2 * d + 2 * gn:])
    filt = _w(p["filter"], faults)
    if "taps_reversed" in faults:
        filt = filt[::-1]
    ahead = jnp.pad(taken, [(0, 0), (taps - 1, 0), (0, 0)])
    conv = sum(filt[j] * ahead[:, j:j + s] for j in range(taps))
    if "no_conv_bias" not in faults:
        conv = conv + _w(p["conv_bias"])
    if "no_conv_silu" not in faults:
        conv = jax.nn.silu(conv)
    x = conv[..., :d].reshape(b, s, nh, hp)
    of = np.arange(nh) % g if "group_h_mod" in faults \
        else np.arange(nh) // (nh // g)
    bm = conv[..., d:d + gn].reshape(b, s, g, n)[:, :, of]
    cm = conv[..., d + gn:].reshape(b, s, g, n)[:, :, of]
    if "no_dt_bias" not in faults:
        dt = dt + _w(p["dt_bias"])
    dt = jax.nn.softplus(dt)
    a = jnp.exp(_w(p["A_log"]))
    if "a_positive" not in faults:
        a = -a
    y, states = recurrence(x, dt, a, bm, cm, at, chunk, faults)
    if "no_skip" not in faults:
        y = y + _w(p["D"])[:, None] * x
    y, gate = y.reshape(b, s, d), jax.nn.silu(z)
    eps, gain = m.lfm_norm_eps, p["norm"]
    parts = 1 if "one_norm" in faults else g

    def normed(v):
        v = v.reshape(b, s, parts, d // parts)
        v = v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps)
        return v.reshape(b, s, d) * _w(gain)

    y = normed(y) * gate if "norm_before_gate" in faults \
        else normed(y * gate)
    out = mult(m, "mup_ssm_out", faults) * _mm(
        y, p["out_proj"]["kernel"], faults)
    # the inputs at t - 2 .. t of each kept position t
    idx = at[:, :, None] + np.arange(taps - 1)[None, None, :] + 1
    inputs = jnp.take_along_axis(ahead[:, None], idx[..., None], axis=2)
    return out, states, inputs


def attention(m, p, a_in, faults):
    """Attention's branch output ``[B, S, D]`` and its keys (rotated)
    and values ``[B, S, kv, hd]``."""
    b, s, _ = a_in.shape
    nh, nkv = m.lfm_heads, m.lfm_kv_heads
    hd = m.lfm_head_dim or m.lfm_hidden // nh
    a_in = m.mup_attn_in * a_in
    q = _mm(a_in, p["q"]["kernel"], faults).reshape(b, s, nh, hd)
    k = mult(m, "mup_key", faults) * _mm(
        a_in, p["k"]["kernel"], faults).reshape(b, s, nkv, hd)
    v = _mm(a_in, p["v"]["kernel"], faults).reshape(b, s, nkv, hd)
    theta = 1e4 if "theta_1e4" in faults else m.lfm_rope_theta
    q, k = rope(theta, q), rope(theta, k)
    of = np.arange(nh) % nkv if "kv_h_mod" in faults \
        else np.arange(nh) // (nh // nkv)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k[:, :, of], precision=HI) \
        / np.sqrt(hd)
    seen = np.arange(s)[None, :] <= np.arange(s)[:, None]
    scores = jnp.where(seen, scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1),
                     v[:, :, of], precision=HI).reshape(b, s, nh * hd)
    return mult(m, "mup_attn_out", faults) * _mm(
        out, p["o"]["kernel"], faults), k, v


@partial(jax.jit, static_argnums=(0, 4, 5))
def layer(m, p, x, at, chunk, faults):
    """One layer: the new residual stream, the three branch outputs
    (mixer, attention, MLP), the keys and values, and the mixer's state
    and convolution inputs at the positions ``at``."""
    eps = m.lfm_norm_eps
    u = rms_norm(x, p["op_norm"]["scale"], eps)
    mixed, states, inputs = mixer(m, p["mixer"], u, at, chunk, faults)
    if "series" in faults:           # attention AFTER the mixer, not beside
        u = rms_norm(x + mixed, p["op_norm"]["scale"], eps)
    attended, k, v = attention(
        m, p["attn"], x if "attn_unnormed" in faults else u, faults)
    y = x + mixed + attended
    f = rms_norm(y, p["ffn_norm"]["scale"], eps)
    w = p["ffn"]
    up = _mm(f, w["w3"]["kernel"], faults)
    gate = jax.nn.silu(mult(m, "mup_mlp0", faults) * _mm(
        f, w["w1"]["kernel"], faults))
    fed = mult(m, "mup_mlp1", faults) * _mm(
        up * gate, w["w2"]["kernel"], faults)
    return y + fed, mixed, attended, fed, k, v, states, inputs


@partial(jax.jit, static_argnums=(2,))
def _head_block(h, rows, faults):
    return jnp.einsum("nd,vd->nv", h, _w(rows, faults), precision=HI)


def layout(a_lens, labels, label_lens, s):
    """Which of the ``s`` positions hold audio, which text, and the
    ids embedded at the text positions (id 0 starts a transcript)."""
    u_max = labels.shape[1]
    t = np.arange(s)[None, :] - np.asarray(a_lens)[:, None]
    audio = t < 0
    text = (t >= 0) & (t <= np.asarray(label_lens)[:, None])
    padded = np.pad(np.asarray(labels), [(0, 0), (1, 0)])
    ids = np.take_along_axis(padded, np.clip(t, 0, u_max), 1)
    return audio, text, np.where(text, ids, 0)


def forward(m, params, feats, lens, labels, label_lens, seq_positions,
            faults=(), head_rows: int = 32768):
    """Everything the comparison reads, as a dict: ``logits`` [B, U+1,
    V] at each stream's text positions (what decode step j emits is at
    [:, j]) and ``steps`` [B, U+1] marking those a stream has; of the
    LAST layer ``k`` and ``v`` [B, S, kv, hd], the three branch outputs
    ``mixer``, ``attn`` and ``mlp`` [B, S, D], the recurrent state ``[B, H, P,
    N]`` and the convolution's last three inputs ``[B, 3, channels]``
    after the prefix (``state_prefill``, ``conv_prefill``: position
    ``a - 1``) and after the stream's last step (``state_last``,
    ``conv_last``: position ``a + u``); ``valid`` [B, S]."""
    s = seq_positions
    feats = np.asarray(feats, np.float32)
    b, t, nf = feats.shape
    fs = m.frame_stack
    frames = -(-t // fs)
    x = np.pad(feats, [(0, 0), (0, frames * fs - t), (0, 0)]).reshape(
        b, frames, fs * nf)[:, :s]
    a_lens = -(-np.asarray(lens) // fs)
    audio, text, ids = layout(a_lens, labels, label_lens, s)
    valid = audio | text
    pre = _mm(jnp.asarray(x), params["prefix"]["kernel"], faults)
    pre = jnp.pad(pre, [(0, 0), (0, s - pre.shape[1]), (0, 0)])
    emb = _w(jnp.take(params["embed"], jnp.asarray(ids), axis=0), faults)
    h = mult(m, "mup_embedding", faults) * jnp.where(
        audio[..., None], pre, jnp.where(text[..., None], emb, 0.0))
    # the positions whose state is kept: the prefix's last and the
    # stream's last step (a fault: the padded end of the prefix)
    end = a_lens - 1
    ends = np.stack([end, a_lens + np.asarray(label_lens)], axis=1)
    state_at, conv_at = ends.copy(), ends.copy()
    if "state_at_padded_end" in faults:
        state_at[:, 0] = min(frames, s) - 1
    if "conv_state_from_padding" in faults:
        conv_at[:, 0] = min(frames, s) - 1
    at = jnp.asarray(np.concatenate([state_at, conv_at], axis=1))
    for i in range(len(m.lfm_layer_types)):
        h, mixed, attended, fed, k, v, states, inputs = layer(
            m, params[f"layer{i}"], h, at, m.ssm_chunk, tuple(faults))
    hidden = rms_norm(h, params["out_norm"]["scale"], m.lfm_norm_eps)
    u1 = labels.shape[1] + 1
    where = np.clip(a_lens[:, None] + np.arange(u1)[None, :], 0, s - 1)
    at_text = jnp.take_along_axis(
        hidden, jnp.asarray(where)[..., None], 1).reshape(b * u1, -1)
    head = params["embed"] if "tied_head" in faults or m.lm_tied_head \
        else params["lm_head"]
    logits = jnp.concatenate([
        _head_block(at_text, head[i:i + head_rows], tuple(faults))
        for i in range(0, head.shape[0], head_rows)], axis=1)
    logits = mult(m, "mup_lm_head", faults) * logits.reshape(b, u1, -1)
    return {"logits": logits, "at": where,
            "steps": np.arange(u1)[None, :]
            <= np.asarray(label_lens)[:, None],
            "k": k, "v": v, "valid": valid, "mixer": mixed,
            "attn": attended, "mlp": fed,
            "state_prefill": states[:, 0],
            "state_last": states[:, 1],
            "conv_prefill": inputs[:, 2], "conv_last": inputs[:, 3]}


def rms_rel(got, want, mask=None) -> float:
    """Root-mean-square difference over the reference's root mean
    square, over the masked elements."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if mask is not None:
        mask = np.broadcast_to(np.asarray(mask, bool).reshape(
            np.shape(mask) + (1,) * (want.ndim - np.ndim(mask))),
            want.shape)
        got, want = got[mask], want[mask]
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / max(np.sqrt(np.mean(want ** 2)), 1e-30))
