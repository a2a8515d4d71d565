"""Plain reference of the Trinity-Large-Preview decoder-only recogniser
(``model_type: afmoe``): the full forward pass over each packed
sequence (prefix + start + labels) in straightforward ``jax.numpy``,
float32, matrix products at ``highest`` precision, band and causal
masks by index arithmetic; no cache, no ring, no kernels, no sorting,
no loop of steps. Independent of ``deepspeech_tpu``: it shares with
the program only the names of the parameters it is handed. Its blocks
(the projections, attention over one block of queries, one SwiGLU over
one block of positions, the router) are compiled on their own and each
upcasts the matrices it is handed, so that at the published widths no
more than one block's float32 matrices and one ``[queries, S]`` block
of scores live beside the program's weights; the layers, the query
blocks and the held experts are plain loops over them.

The layer equations (ISSUE 41 writes them out; the family's public
modelling code is ``modeling_afmoe.py``):

  layer      a = Attn(N1(h)); h = h + N2(a); f = FF(N3(h)); h = h + N4(f)
             N1..N4 RMSNorm with gain [D], eps 1e-5; RMSNorm after the
             last layer; no bias anywhere
  Attn(x)    q = x Wq -> [48, 128], k = x Wk -> [8, 128], v = x Wv ->
             [8, 128], g = x Wg -> [48, 128]; q, k <- RMSNorm over each
             head (gains [128]); on a SLIDING layer q, k <- rope at the
             position (whole head, rotate-half pairing, theta 10000),
             on a GLOBAL layer nothing; scores q_i . k_j / sqrt(128)
             over j <= i (global) or i - W < j <= i (sliding: W keys,
             its own among them); softmax; o = sum_j p_ij v_j; heads
             6g .. 6g+5 read key/value head g; (o * sigmoid(g)) Wo
  dense FF   W_2 (silu(W_1 x) * (W_3 x)), the leading layer
  experts    s = sigmoid(W_r x) over all 256; chosen = top-4 of s +
             expert_bias (the bias chooses, it does not weigh); w =
             2.448 * s[chosen] / sum s[chosen]; sum over chosen e HELD
             HERE of w_e SwiGLU_e(x), plus SwiGLU_shared(x)
  ends       h_0 = sqrt(3072) * Emb(t) at text positions and sqrt(3072)
             * (frames W_prefix) at prefix positions; logits =
             Norm(h_L) W_head^T

Departures, all shared with the program and listed under ``assumed``
in ``configs/trinity_large.json``: the audio prefix (8 stacked frames
projected by one matrix, left-packed before the transcript, id 0
starts it) and ITS sqrt(D), positions from 0 at the first prefix
frame, the float32 router, ``route_norm``'s denominator without the
family's 1e-20 (the program's ``route`` adds 1e-6; four sigmoids sum to
about 2, so neither shows in float32), the seeded norm gains, and THE
SHARE (``experts_held`` experts from ``expert_offset``, the vocabulary
slice). ``load_balance_coeff`` belongs to training and is unused.

``faults`` names departures put in on purpose, for the controls of
``benchmark/tests/test_trinity_ref_control.py``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

FAULTS = ("float8_weights", "no_window", "window_plus_1",
          "rope_on_global", "no_rope_on_sliding", "no_gate",
          "no_post_norm", "no_embed_scale", "scale_1", "bias_as_weight",
          "ring_mod_w_plus_1")
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


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _w(gain)


def head_dim(m) -> int:
    return m.lfm_head_dim or m.lfm_hidden // m.lfm_heads


def rope(m, x):
    """``x [B, S, H, hd]`` at positions 0..S-1: the halves ``(x1, x2)``
    of a head become ``(x1 cos - x2 sin, x2 cos + x1 sin)``, pair i
    turning by ``position * theta^(-2i/hd)`` (tables in float64)."""
    s, hd = x.shape[1], x.shape[-1]
    freq = m.lfm_rope_theta ** (-np.arange(0, hd, 2, dtype=np.float64)
                                / hd)
    ang = np.arange(s, dtype=np.float64)[:, None] * freq[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnums=(0, 1, 4))
def projections(m, kind, p, x, faults):
    """q ``[B, S, heads, hd]``, k and v ``[B, S, kv, hd]`` (q and k
    normed per head and, on a layer that has positions, rotated) and
    the gate's pre-activation ``[B, S, heads * hd]``."""
    b, s, _ = x.shape
    nh, nkv, hd = m.lfm_heads, m.lfm_kv_heads, head_dim(m)
    q = _mm(x, p["q"]["kernel"], faults).reshape(b, s, nh, hd)
    k = _mm(x, p["k"]["kernel"], faults).reshape(b, s, nkv, hd)
    v = _mm(x, p["v"]["kernel"], faults).reshape(b, s, nkv, hd)
    q = rms_norm(q, p["q_norm"]["scale"], m.lfm_norm_eps)
    k = rms_norm(k, p["k_norm"]["scale"], m.lfm_norm_eps)
    turns = kind == "sliding_attention"
    if "rope_on_global" in faults:
        turns = True
    if "no_rope_on_sliding" in faults:
        turns = False
    if turns:
        q, k = rope(m, q), rope(m, k)
    gate = None
    if m.lfm_attn_gate and "no_gate" not in faults:
        gate = _mm(x, p["gate"]["kernel"], faults)
    return q, k, v, gate


@partial(jax.jit, static_argnums=(0, 1, 6))
def attention_block(m, kind, q, k, v, i0, faults):
    """The queries ``i0 .. i0 + Q`` (``q [B, Q, heads, hd]``) against
    ALL keys: ``[B, Q, heads * hd]``."""
    b, nq, nh, hd = q.shape
    s, nkv = k.shape[1], k.shape[2]
    i = i0 + jnp.arange(nq)[:, None]
    j = jnp.arange(s)[None, :]
    seen = j <= i
    if kind == "sliding_attention" and "no_window" not in faults:
        w = m.lfm_window + ("window_plus_1" in faults)
        seen = seen & (i - j < w)
    q = q.reshape(b, nq, nkv, nh // nkv, hd)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", q, k, precision=HI) \
        / np.sqrt(hd)
    scores = jnp.where(seen, scores, -jnp.inf)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", jax.nn.softmax(scores, -1), v,
                     precision=HI)
    return out.reshape(b, nq, nh * hd)


def attention(m, kind, p, x, faults, q_block):
    """The layer's output, its keys and values, and the gated heads'
    output before ``Wo``."""
    q, k, v, gate = projections(m, kind, p, x, faults)
    s = x.shape[1]
    out = jnp.concatenate([
        attention_block(m, kind, q[:, i0:i0 + q_block], k, v,
                        np.int32(i0), faults)
        for i0 in range(0, s, q_block)], axis=1)
    if gate is not None:
        out = out * jax.nn.sigmoid(gate)
    return _mm(out, p["o"]["kernel"], faults), k, v, out


@partial(jax.jit, static_argnums=(4,))
def _swiglu(w1, w3, w2, x, faults=()):
    return _mm(jax.nn.silu(_mm(x, w1, faults)) * _mm(x, w3, faults), w2,
               faults)


def swiglu(w1, w3, w2, x, faults=(), rows: int = 2048):
    """Over ``rows`` positions at a time (the dense layer's hidden
    activations are four times as wide as ``x``)."""
    flat = x.reshape(-1, x.shape[-1])
    out = [_swiglu(w1, w3, w2, flat[i:i + rows], faults)
           for i in range(0, flat.shape[0], rows)]
    return jnp.concatenate(out).reshape(x.shape[:-1] + (-1,))


@partial(jax.jit, static_argnums=(0, 4))
def routing(m, router, bias, x, faults):
    """The router's scores ``[B, S, E]``, the chosen experts and their
    combine weights ``[B, S, k]``."""
    scores = jax.nn.sigmoid(_mm(x, router, faults))
    choose_by = scores if bias is None else scores + bias
    chosen = jax.lax.top_k(choose_by, m.lfm_top_k)[1]
    w = jnp.take_along_axis(
        choose_by if "bias_as_weight" in faults else scores, chosen, -1)
    w = w / jnp.sum(w, -1, keepdims=True)
    if "scale_1" not in faults:
        w = w * m.moe_routed_scale
    return scores, chosen, w


@partial(jax.jit, static_argnums=(0, 8))
def held_expert(m, w13, w2, i, x, chosen, w, valid, faults):
    """Held expert ``i`` (id ``expert_offset + i``) applied to EVERY
    position, weighted where the position chose it: its part of the
    layer's result, and how many valid positions chose it."""
    e = m.expert_offset + i
    f = m.lfm_expert_dim
    w13 = jax.lax.dynamic_index_in_dim(w13, i, keepdims=False)
    w2 = jax.lax.dynamic_index_in_dim(w2, i, keepdims=False)
    y = _swiglu(w13[:, :f], w13[:, f:], w2, x, faults)
    w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), -1) * valid
    return w_e[..., None] * y, jnp.sum(jnp.any(chosen == e, -1) * valid)


def experts(m, p, bias, x, valid, faults):
    """The held experts' part of the routed feed-forward plus the
    shared expert, the router's scores, choices and combine weights
    (as a map over all experts), and the routed (position, expert)
    pairs on each held expert."""
    scores, chosen, w = routing(m, p["router"], bias, x, faults)
    out = jnp.zeros_like(x)
    pairs = []
    for i in range(p["w13"].shape[0]):
        part, n = held_expert(m, p["w13"], p["w2"], np.int32(i), x,
                              chosen, w, valid, faults)
        out = out + part
        pairs.append(n)
    if m.moe_shared_experts:
        sh = p["shared"]
        out = out + swiglu(sh["w1"]["kernel"], sh["w3"]["kernel"],
                           sh["w2"]["kernel"], x, faults)
    dense = jnp.sum(jnp.where(
        chosen[..., None] == np.arange(m.lfm_experts), w[..., None], 0.0),
        -2)
    return out, scores, chosen, dense, jnp.stack(pairs)


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


def forward(m, params, buffers, feats, lens, labels, label_lens,
            seq_positions, faults=(), q_block: int = 256):
    """Everything the comparison reads, as a dict: ``logits`` [B, U+1,
    V] at each stream's text positions (what decode step j emits is at
    [:, j]) and ``steps`` [B, U+1] marking those a stream has; per
    layer ``k`` and ``v`` [B, S, kv, hd]; ``valid`` [B, S]; ``gated``:
    the gated heads' output before ``Wo`` [B, S, heads * hd] of the
    last layer of each attention kind (sliding first); the last expert
    layer's ``scores`` and combine ``weights`` [B, S, E]; every expert
    layer's ``chosen`` sets and ``pairs`` on each held expert [layers,
    held]."""
    s = seq_positions
    feats = np.asarray(feats, np.float32)
    b, t, nf = feats.shape
    fs = m.frame_stack
    n = -(-t // fs)
    x = np.pad(feats, [(0, 0), (0, n * fs - t), (0, 0)]).reshape(
        b, n, fs * nf)[:, :s]
    a_lens = -(-np.asarray(lens) // fs)
    audio, text, ids = layout(a_lens, labels, label_lens, s)
    valid = jnp.asarray(audio | text)
    pre = _mm(jnp.asarray(x), params["prefix"]["kernel"], faults)
    pre = jnp.pad(pre, [(0, 0), (0, s - pre.shape[1]), (0, 0)])
    emb = _w(jnp.take(params["embed"], jnp.asarray(ids), axis=0), faults)
    h = jnp.where(audio[..., None], pre,
                  jnp.where(text[..., None], emb, 0.0))
    if m.lfm_embed_scale and "no_embed_scale" not in faults:
        h = h * np.float32(np.sqrt(m.lfm_hidden))
    eps = m.lfm_norm_eps
    post = m.lfm_post_norms and "no_post_norm" not in faults
    keys, values, gated = [], [], {}
    scores, weights, chosen, pairs = None, None, [], []
    for i, kind in enumerate(m.lfm_layer_types):
        p = params[f"layer{i}"]
        y = rms_norm(h, p["op_norm"]["scale"], eps)
        out, k, v, g = attention(m, kind, p["attn"], y, faults, q_block)
        if post:
            out = rms_norm(out, p["op_post_norm"]["scale"], eps)
        h = h + out
        keys.append(k)
        values.append(v)
        gated[kind] = g
        y = rms_norm(h, p["ffn_norm"]["scale"], eps)
        if i < m.lfm_dense_layers:
            f = p["ffn"]
            out = swiglu(f["w1"]["kernel"], f["w3"]["kernel"],
                         f["w2"]["kernel"], y, faults)
        else:
            bias = None
            if m.moe_select_bias:
                bias = buffers[f"layer{i}"]["moe"]["expert_bias"]
            out, scores, ch, weights, npairs = experts(
                m, p["moe"], bias, y, valid, faults)
            chosen.append(ch)
            pairs.append(npairs)
        if post:
            out = rms_norm(out, p["ffn_post_norm"]["scale"], eps)
        h = h + out
    hidden = rms_norm(h, params["out_norm"]["scale"], eps)
    u1 = labels.shape[1] + 1
    at = np.clip(a_lens[:, None] + np.arange(u1)[None, :], 0, s - 1)
    at_text = jnp.take_along_axis(hidden, jnp.asarray(at)[..., None], 1)
    head = params["embed"] if m.lm_tied_head else params["lm_head"]
    logits = jnp.einsum("bud,vd->buv", at_text, _w(head, faults),
                        precision=HI)
    return {"logits": logits, "at": at,
            "steps": np.arange(u1)[None, :]
            <= np.asarray(label_lens)[:, None],
            "k": keys, "v": values, "valid": np.asarray(valid),
            "gated": [gated[kind] for kind in (
                "sliding_attention", "full_attention") if kind in gated],
            "scores": scores, "weights": weights, "chosen": chosen,
            "pairs": jnp.stack(pairs) if pairs else jnp.zeros((0, 0))}


def cache_view(k, v, last, rows: int, faults=()):
    """What a cache of ``rows`` rows a stream should hold once each
    stream's position ``last [B]`` is written, from the reference's
    keys and values ``[B, S, kv, hd]``, in the program's layout ``[B,
    rows, 2 * kv, hd]`` (key heads, then value heads): slot ``p mod rows``
    holds the newest position p <= last of its class (a ring where
    ``rows`` is below the positions, else row p in slot p), and
    ``held [B, rows]`` marks the slots that hold one."""
    last = np.asarray(last)[:, None]
    slot = np.arange(rows)[None, :]
    if "ring_mod_w_plus_1" in faults:      # rows laid out at p mod (W+1)
        pos = last - (last - slot) % (rows + 1)
    else:
        pos = last - (last - slot) % rows
    held = pos >= 0
    at = np.maximum(pos, 0)
    both = np.concatenate([np.asarray(k), np.asarray(v)], axis=2)
    out = np.take_along_axis(both, at[:, :, None, None], axis=1)
    return np.where(held[:, :, None, None], out, 0.0), held


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
