"""Plain reference of the A.X-K1 decoder-only recogniser: the full
forward pass over each packed sequence in straightforward
``jax.numpy``, float32, matrix products at ``highest`` precision, with
EXPANDED keys and values; no cache, no absorbed form, no kernels, no
sorting. Independent of ``deepspeech_tpu``: it shares with the program
only the names of the parameters it is handed. Its blocks (attention,
one SwiGLU, the router) are compiled on their own and each upcasts the
matrices it is handed, so that at the published widths no more than
one block's float32 matrices live beside the program's weights; the
layers and the held experts are plain loops over them.

The layer equations (``model_type: axk1``; ISSUE 32 writes them out):

  layer      h = h + attention(RMSNorm(h)); h = h + ffn(RMSNorm(h));
             RMSNorm after the last layer; eps 1e-6, learned gain; no
             bias anywhere
  attention  c_q = RMSNorm(x W_qa); [q_nope | q_rope] = c_q W_qb per
             head (128 | 64); [c_kv | k_r] = x W_kva (512 | 64);
             c_kv = RMSNorm(c_kv); k_rope = rot(k_r), one for all
             heads; [k_nope | v] = c_kv W_kvb per head (128 | 128);
             scores (q_nope . k_nope + rot(q_rope) . k_rope)
             * 192^-0.5 * m^2, m = 0.1 * mscale_all_dim * ln(factor) + 1;
             causal softmax; concat_h(P v) W_o
  rot        YaRN frequencies (theta over 64 dims, factor 32, original
             4096, beta_fast 32, beta_slow 1), interleaved pairs
             (2i, 2i+1), cos and sin times mscale ratio (1)
  dense ffn  W_2 (silu(W_1 x) * (W_3 x)), layer 0
  experts    s = sigmoid(W_g x) over all 192; 8 groups of 24
             consecutive experts, a group scores its maximum, the 4
             best groups stay, the 8 best s inside them are chosen;
             w = s[chosen] / sum * 2.5; sum over chosen e HELD HERE of
             w_e SwiGLU_e(x), plus SwiGLU_shared(x)

Departures, all shared with the program and listed under ``assumed``
in ``configs/ax_k1.json``: the audio prefix (8 stacked frames projected
by one matrix, left-packed before the transcript, id 0 starts it),
positions from 0 at the first prefix frame, the float32 router,
``topk_method: "none"`` read as group-limited selection without a
bias, and THE SHARE (``experts_held`` experts from ``expert_offset``,
the vocabulary slice).

``faults`` names departures put in on purpose, for the controls of
``benchmark/tests/test_axk1_ref_control.py``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

FAULTS = ("float8_weights", "plain_top8", "no_mscale",
          "cache_before_norm", "no_key_rotation", "no_shared_expert",
          "scale_1")

def _w(x, faults=()):
    """A weight as float32; under ``float8_weights`` every matrix is
    first rounded to float8 (e4m3), the nearest precision below the
    configuration's bfloat16, where it is used (a second copy of the
    weights would not fit beside the first)."""
    if "float8_weights" in faults and np.ndim(x) >= 2:
        x = jnp.asarray(x).astype(jnp.float8_e4m3fn)
    return jnp.asarray(x, jnp.float32)


def _mm(a, b, faults=()):
    return jnp.matmul(a, _w(b, faults), precision=jax.lax.Precision.HIGHEST)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _w(gain)


def mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_frequencies(m) -> np.ndarray:
    d, base, factor = m.mla_rope_dim, m.lfm_rope_theta, m.rope_yarn_factor
    freq = base ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if factor <= 1:
        return freq
    beta_fast, beta_slow = m.rope_yarn_betas

    def dim_of(rotations):
        return d * math.log(m.rope_yarn_original
                            / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    extrapolated, interpolated = freq, freq / factor
    return interpolated * ramp + extrapolated * (1 - ramp)


def rot(m, x, positions):
    """x [B, S, ..., 64]: each pair (x[2i], x[2i+1]) is a point of the
    plane, turned by position * frequency_i (tables in float64)."""
    ang = positions[..., None].astype(np.float64) * yarn_frequencies(m)
    amp = mscale(m.rope_yarn_factor, m.rope_yarn_mscales[0]) \
        / mscale(m.rope_yarn_factor, m.rope_yarn_mscales[1])
    shape = ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[2:]
    cos = jnp.asarray((amp * np.cos(ang)).reshape(shape), jnp.float32)
    sin = jnp.asarray((amp * np.sin(ang)).reshape(shape), jnp.float32)
    re, im = x[..., 0::2], x[..., 1::2]
    return jnp.stack([re * cos - im * sin, re * sin + im * cos],
                     -1).reshape(x.shape)


@partial(jax.jit, static_argnums=(0, 3))
def attention(m, p, x, faults):
    """Returns the layer's output and the rows a cache would hold."""
    b, s, _ = x.shape
    nh, dn, dr, dv = (m.lfm_heads, m.mla_nope_dim, m.mla_rope_dim,
                      m.mla_v_dim)
    r = m.mla_kv_rank
    positions = np.broadcast_to(np.arange(s)[None, :], (b, s))
    c_q = rms_norm(_mm(x, p["q_a"], faults), p["q_norm"], m.lfm_norm_eps)
    q = _mm(c_q, p["q_b"], faults).reshape(b, s, nh, dn + dr)
    q_nope, q_rope = q[..., :dn], rot(m, q[..., dn:], positions)
    kv = _mm(x, p["kv_a"], faults)
    c_kv = rms_norm(kv[..., :r], p["kv_norm"], m.lfm_norm_eps)
    k_rope = kv[..., r:]
    if "no_key_rotation" not in faults:
        k_rope = rot(m, k_rope, positions)
    rows = jnp.concatenate([c_kv, k_rope], -1)
    if "cache_before_norm" in faults:
        c_kv = kv[..., :r]
    expanded = _mm(c_kv, p["kv_b"], faults).reshape(b, s, nh, dn + dv)
    k_nope, v = expanded[..., :dn], expanded[..., dn:]
    scale = (dn + dr) ** -0.5
    if "no_mscale" not in faults:
        scale *= mscale(m.rope_yarn_factor, m.rope_yarn_mscales[1]) ** 2
    hi = jax.lax.Precision.HIGHEST
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope, precision=hi)
              + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope, precision=hi)
              ) * scale
    future = np.triu(np.ones((s, s), bool), 1)
    scores = jnp.where(future, -jnp.inf, scores)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v,
                     precision=hi)
    return _mm(out.reshape(b, s, nh * dv), p["o"], faults), rows


@partial(jax.jit, static_argnums=(4,))
def swiglu(w1, w3, w2, x, faults=()):
    return _mm(jax.nn.silu(_mm(x, w1, faults)) * _mm(x, w3, faults), w2,
               faults)


@partial(jax.jit, static_argnums=(0, 3))
def routing(m, router, x, faults):
    """The router's scores ``[B, S, E]``, the chosen experts and their
    combine weights ``[B, S, k]``."""
    scores = jax.nn.sigmoid(_mm(x, router, faults))
    chosen = select(m, scores, faults)
    w = jnp.take_along_axis(scores, chosen, -1)
    w = w / jnp.sum(w, -1, keepdims=True)
    if "scale_1" not in faults:
        w = w * m.moe_routed_scale
    return scores, chosen, w


def select(m, scores, faults):
    """Group-limited top-k of the scores ``[..., E]``."""
    if "plain_top8" in faults:
        return jax.lax.top_k(scores, m.lfm_top_k)[1]
    g = m.moe_groups
    per = m.lfm_experts // g
    grouped = scores.reshape(scores.shape[:-1] + (g, per))
    best = jnp.max(grouped, -1)                              # [..., G]
    kept = jax.lax.top_k(best, m.moe_groups_kept)[1]
    keep = jnp.any(kept[..., :, None] == np.arange(g), -2)   # [..., G]
    masked = jnp.where(keep[..., None], grouped, -jnp.inf)
    return jax.lax.top_k(masked.reshape(scores.shape), m.lfm_top_k)[1]


@partial(jax.jit, static_argnums=(0, 8))
def held_expert(m, w13, w2, i, x, chosen, w, valid, faults):
    """Held expert ``i`` (id ``expert_offset + i``) applied to EVERY
    position, weighted where the position chose it: its part of the
    layer's result, and how many valid positions chose it."""
    e = m.expert_offset + i
    f = m.lfm_expert_dim
    w13 = jax.lax.dynamic_index_in_dim(w13, i, keepdims=False)
    w2 = jax.lax.dynamic_index_in_dim(w2, i, keepdims=False)
    y = swiglu(w13[:, :f], w13[:, f:], w2, x, faults)
    w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), -1) * valid
    return w_e[..., None] * y, jnp.sum(jnp.any(chosen == e, -1) * valid)


def experts(m, p, x, valid, faults):
    """The held experts' part of the routed feed-forward plus the
    shared expert, the router's scores, choices and combine weights
    (as a map over all experts), and the routed (position, expert)
    pairs whose expert is held here."""
    scores, chosen, w = routing(m, p["router"], x, faults)
    out = jnp.zeros_like(x)
    pairs = 0.0
    for i in range(p["w13"].shape[0]):
        part, n = held_expert(m, p["w13"], p["w2"], np.int32(i), x,
                              chosen, w, valid, faults)
        out = out + part
        pairs = pairs + n
    if m.moe_shared_experts and "no_shared_expert" not in faults:
        sh = p["shared"]
        out = out + swiglu(sh["w1"]["kernel"], sh["w3"]["kernel"],
                           sh["w2"]["kernel"], x, faults)
    dense = jnp.sum(jnp.where(
        chosen[..., None] == np.arange(m.lfm_experts), w[..., None], 0.0),
        -2)
    return out, scores, chosen, dense, pairs


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
            faults=()):
    """Everything the comparison reads, as a dict: ``logits`` [B, U+1,
    V] at each stream's text positions (what decode step j emits is at
    [:, j]) and ``steps`` [B, U+1] marking those a stream has,
    ``rows`` per layer [B, S, 576], ``valid`` [B, S], the last expert
    layer's ``scores`` and combine ``weights`` [B, S, E], every expert
    layer's ``chosen`` and ``pairs_held``."""
    s = seq_positions
    feats = np.asarray(feats, np.float32)
    b, t, nf = feats.shape
    k = m.frame_stack
    n = -(-t // k)
    x = np.pad(feats, [(0, 0), (0, n * k - t), (0, 0)]).reshape(
        b, n, k * nf)
    a_lens = -(-np.asarray(lens) // k)
    audio, text, ids = layout(a_lens, labels, label_lens, s)
    valid = jnp.asarray(audio | text)
    pre = _mm(jnp.asarray(x), params["prefix"]["kernel"], faults)
    pre = jnp.pad(pre, [(0, 0), (0, s - n), (0, 0)])
    emb = _w(jnp.take(params["embed"], jnp.asarray(ids), axis=0), faults)
    h = jnp.where(audio[..., None], pre,
                  jnp.where(text[..., None], emb, 0.0))
    rows, scores, weights, chosen, pairs = [], None, None, [], []
    for i in range(len(m.lfm_layer_types)):
        p = params[f"layer{i}"]
        y = rms_norm(h, p["op_norm"]["scale"], m.lfm_norm_eps)
        out, r = attention(m, p["attn"], y, faults)
        h = h + out
        rows.append(r)
        y = rms_norm(h, p["ffn_norm"]["scale"], m.lfm_norm_eps)
        if i < m.lfm_dense_layers:
            f = p["ffn"]
            h = h + swiglu(f["w1"]["kernel"], f["w3"]["kernel"],
                           f["w2"]["kernel"], y, faults)
        else:
            out, scores, ch, weights, npairs = experts(
                m, p["moe"], y, valid, faults)
            h = h + out
            chosen.append(ch)
            pairs.append(npairs)
    hidden = rms_norm(h, params["out_norm"]["scale"], m.lfm_norm_eps)
    u1 = labels.shape[1] + 1
    at = np.clip(a_lens[:, None] + np.arange(u1)[None, :], 0, s - 1)
    at_text = jnp.take_along_axis(hidden, jnp.asarray(at)[..., None], 1)
    head = params["embed"] if m.lm_tied_head else params["lm_head"]
    logits = jnp.einsum("bud,vd->buv", at_text, _w(head, faults),
                        precision=jax.lax.Precision.HIGHEST)
    return {"logits": logits, "at": at,
            "steps": np.arange(u1)[None, :]
            <= np.asarray(label_lens)[:, None],
            "rows": rows, "valid": np.asarray(valid), "scores": scores,
            "weights": weights, "chosen": chosen,
            "pairs_held": jnp.stack(pairs) if pairs else jnp.zeros(0)}


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


def chosen_differ_share(got, want, mask) -> float:
    """Share of masked (position, layer) whose chosen SET of experts
    differs (rounding upstream flips near-ties)."""
    differ = total = 0
    mask = np.asarray(mask, bool).reshape(-1)
    for g, w in zip(got, want):
        g = np.sort(np.asarray(g).reshape(mask.size, -1), -1)[mask]
        w = np.sort(np.asarray(w).reshape(mask.size, -1), -1)[mask]
        differ += int(np.any(g != w, axis=-1).sum())
        total += g.shape[0]
    return differ / max(total, 1)
