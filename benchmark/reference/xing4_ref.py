"""Plain reference of the Xing4.0 decoder-only recogniser: the full
forward pass of the model AND of its multi-token-prediction module over
each packed sequence in straightforward ``jax.numpy``, float32, matrix
products at ``highest`` precision, with EXPANDED keys and values; no
cache, no absorbed form, no kernels, no sorting, no loop of steps, no
drafting. Independent of ``deepspeech_tpu``: it shares with the program
only the names of the parameters it is handed. Small pieces that are
the same mathematics as A.X-K1's (the norm, YaRN's rotation, one
SwiGLU, one held expert, the packed layout, the error measures) are
``axk1_ref``'s; attention is written out again here because one of the
controls changes its mask. Blocks are compiled on their own and each
upcasts the matrices it is handed, and the head is applied a slice of
the vocabulary at a time, so that at the published widths no more than
one block's float32 matrices live beside the program's weights.

The equations (``model_type: xing4_0``; ISSUE 39 writes them out), per
position, ``X [n, D]`` the n = ``hc_mult`` = 4 residual streams:

  sub-layer  x~ = RMSNorm(vec(X)) over all n*D values, learned gain;
             H~ = a * (x~ phi) + b, phi [n*D, n + n + n*n] (pre, post,
             res; res row-major), a three scalars, b a bias;
             H_pre = sigmoid(H~pre); H_post = 2 sigmoid(H~post);
             M = exp(clamp(H~res, -30, 30)); 20 times: M /= rowsum + eps,
             M /= colsum + eps (eps 1e-6); H_res = M;
             X' = H_res X + H_post (outer) F(H_pre X), F with its pre-norm
  layer      attention sub-layer, then feed-forward sub-layer
  ends       X_0 = the input (projected audio frame or embedding) in all
             n streams; hidden = sum_i X_L[i]; logits = Head(Norm_f(.))
  attention  latent attention as A.X-K1's at these widths (ranks 768 /
             512, 32 heads of 128 | 64 | 128, YaRN factor 64)
  dense ffn  W_2 (silu(W_1 x) * (W_3 x)), layer 0
  experts    s = sigmoid(W_g x) over all 64; chosen = top-4 of s + bias
             (``noaux_tc``: the bias chooses, it does not weigh);
             w = s[chosen] / (sum + 1e-6) * 2; sum_e w_e SwiGLU_e(x) +
             SwiGLU_shared(x); every expert is held here
  MTP        z_i = W_eh [RMSNorm_e(input_{i+1}) ; RMSNorm_h(hidden_i)],
             in all n streams, one more expert layer (own
             hyper-connections), summed, Head(Norm_m(.)): the
             distribution of the token after next

Departures, all shared with the program and listed under ``assumed``
in ``configs/xing4_29b_a4b.json``: the audio prefix (8 stacked frames
projected by one matrix, left-packed before the transcript, id 0 starts
it), positions from 0 at the first prefix frame, the float32 router,
the two ends of the streams, what the module reads of them and that its
next input over the prefix is the next projected frame.

``faults`` names departures put in on purpose, for the controls of
``benchmark/tests/test_xing4_ref_control.py``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.axk1_ref import (  # noqa: F401  (re-exported)
    _mm, _w, chosen_differ_share, held_expert, layout, mscale, rms_norm,
    rms_rel, rot, swiglu)

FAULTS = ("float8_weights", "sinkhorn_1", "post_without_2", "no_clamp",
          "bias_weighs", "scale_1", "eh_swapped", "second_blind")
HI = jax.lax.Precision.HIGHEST


@partial(jax.jit, static_argnums=(0, 3))
def hyper(m, p, x, faults):
    """The three coefficient sets of one sub-layer from the streams
    ``x [B, S, n, D]``: ``H_pre [B, S, n]``, ``H_post [B, S, n]``,
    ``H_res [B, S, n, n]``."""
    b, s, n, d = x.shape
    normed = rms_norm(x.reshape(b, s, n * d), p["norm"], m.lfm_norm_eps)
    raw = _mm(normed, p["phi"], faults)
    a, bias = _w(p["alpha"]), _w(p["bias"])
    pre = jax.nn.sigmoid(a[0] * raw[..., :n] + bias[:n])
    post = jax.nn.sigmoid(a[1] * raw[..., n:2 * n] + bias[n:2 * n])
    if "post_without_2" not in faults:
        post = 2.0 * post
    res = (a[2] * raw[..., 2 * n:] + bias[2 * n:]).reshape(b, s, n, n)
    if "no_clamp" not in faults:
        res = jnp.clip(res, *m.hc_res_clamp)
    mat = jnp.exp(res)
    for _ in range(1 if "sinkhorn_1" in faults else m.hc_sinkhorn_iters):
        mat = mat / (jnp.sum(mat, -1, keepdims=True) + m.hc_eps)
        mat = mat / (jnp.sum(mat, -2, keepdims=True) + m.hc_eps)
    return pre, post, mat


def sub_layer(m, p, x, f, faults):
    """``X' = H_res X + H_post (outer) F(H_pre X)``; returns it, what F
    gave beside its output, and the coefficients."""
    pre, post, res = hyper(m, p, x, faults)
    y, extra = f(jnp.einsum("bsn,bsnd->bsd", pre, x, precision=HI))
    out = jnp.einsum("bsij,bsjd->bsid", res, x, precision=HI) \
        + post[..., None] * y[..., None, :]
    return out, extra, (pre, post, res)


@partial(jax.jit, static_argnums=(0, 4))
def attention(m, p, x, hidden, faults):
    """Latent attention over a whole sequence; ``hidden [B, S, S]``
    marks (query, key) pairs hidden beside the future. Returns the
    layer's output and the rows a cache would hold."""
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
    k_rope = rot(m, kv[..., r:], positions)
    rows = jnp.concatenate([c_kv, k_rope], -1)
    expanded = _mm(c_kv, p["kv_b"], faults).reshape(b, s, nh, dn + dv)
    k_nope, v = expanded[..., :dn], expanded[..., dn:]
    scale = (dn + dr) ** -0.5 \
        * mscale(m.rope_yarn_factor, m.rope_yarn_mscales[1]) ** 2
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope, precision=HI)
              + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope, precision=HI)
              ) * scale
    future = np.triu(np.ones((s, s), bool), 1)
    scores = jnp.where(future | hidden[:, None], -jnp.inf, scores)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v,
                     precision=HI)
    return _mm(out.reshape(b, s, nh * dv), p["o"], faults), rows


@partial(jax.jit, static_argnums=(0, 4))
def routing(m, router, bias, x, faults):
    """The router's scores ``[B, S, E]``, the chosen experts and their
    combine weights ``[B, S, k]``."""
    scores = jax.nn.sigmoid(_mm(x, router, faults))
    biased = scores + _w(bias)
    chosen = jax.lax.top_k(biased, m.lfm_top_k)[1]
    w = jnp.take_along_axis(
        biased if "bias_weighs" in faults else scores, chosen, -1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    if "scale_1" not in faults:
        w = w * m.moe_routed_scale
    return scores, chosen, w


def experts(m, p, bias, x, valid, faults):
    """The routed feed-forward (every expert applied to every position,
    weighted where the position chose it) plus the shared expert; the
    router's scores, choices, combine weights as a map over all
    experts, and the valid positions' pairs on each expert."""
    scores, chosen, w = routing(m, p["router"], bias, x, faults)
    out = jnp.zeros_like(x)
    pairs = []
    for i in range(p["w13"].shape[0]):
        part, n = held_expert(m, p["w13"], p["w2"], np.int32(i), x,
                              chosen, w, valid, faults)
        out = out + part
        pairs.append(n)
    sh = p["shared"]
    out = out + swiglu(sh["w1"]["kernel"], sh["w3"]["kernel"],
                       sh["w2"]["kernel"], x, faults)
    dense = jnp.sum(jnp.where(
        chosen[..., None] == np.arange(m.lfm_experts), w[..., None], 0.0),
        -2)
    return out, (scores, chosen, dense, jnp.stack(pairs))


def decoder_layer(m, p, bias, x, valid, hidden, sparse, faults):
    """One layer over the streams ``x [B, S, n, D]``. Returns them, the
    cache rows, the expert block's outputs (None for a dense layer) and
    the feed-forward sub-layer's coefficients."""
    def operator(y):
        y = rms_norm(y, p["op_norm"]["scale"], m.lfm_norm_eps)
        return attention(m, p["attn"], y, hidden, faults)

    def feed_forward(y):
        y = rms_norm(y, p["ffn_norm"]["scale"], m.lfm_norm_eps)
        if sparse:
            return experts(m, p["moe"], bias, y, valid, faults)
        f = p["ffn"]
        return swiglu(f["w1"]["kernel"], f["w3"]["kernel"],
                      f["w2"]["kernel"], y, faults), None

    x, rows, _ = sub_layer(m, p["op_hc"], x, operator, faults)
    x, routed, mix = sub_layer(m, p["ffn_hc"], x, feed_forward, faults)
    return x, rows, routed, mix


@partial(jax.jit, static_argnums=(2,))
def _head_block(h, head, faults):
    return jnp.einsum("bud,vd->buv", h, _w(head, faults), precision=HI)


def head_logits(h, head, faults, block: int = 16384):
    """``h [B, U, D]`` against the head ``[V, D]``, a block of rows at
    a time (float32 copies of one block, never of the whole head)."""
    return jnp.concatenate(
        [_head_block(h, head[i:i + block], faults)
         for i in range(0, head.shape[0], block)], axis=-1)


def forward(m, params, buffers, feats, lens, labels, label_lens,
            seq_positions, faults=()):
    """Everything the comparison reads, as a dict: ``logits`` [B, U+1,
    V] at each stream's text positions (what the step of token j emits
    is at [:, j]) and ``steps`` [B, U+1] marking those a stream has;
    ``draft_logits`` the module's at the same positions and
    ``draft_steps`` those where it has a next input; ``rows`` per layer
    (the module's last) [B, S, 576]; ``valid`` [B, S]; the last expert
    layer's ``scores`` and combine ``weights`` [B, S, E]; every expert
    layer's ``chosen`` and, with the module's last, ``pairs`` per expert;
    the last layer's feed-forward ``h_pre``, ``h_post``, ``h_res``."""
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
    inputs = jnp.where(audio[..., None], pre,
                       jnp.where(text[..., None], emb, 0.0))
    hidden = np.zeros((b, s, s), bool)
    if "second_blind" in faults:
        # The second position of a verified pair (odd text positions)
        # does not see the first.
        odd = text & ((np.arange(s)[None, :] - a_lens[:, None]) % 2 == 1)
        at = np.arange(1, s)
        hidden[:, at, at - 1] = odd[:, 1:]
    hidden = jnp.asarray(hidden)
    streams = m.hc_streams
    h = jnp.broadcast_to(inputs[:, :, None, :], (b, s, streams)
                         + inputs.shape[-1:])
    rows, chosen, pairs = [], [], []
    scores = weights = mix = None
    for i in range(len(m.lfm_layer_types)):
        name = f"layer{i}"
        sparse = i >= m.lfm_dense_layers
        bias = buffers[name]["moe"]["expert_bias"] if sparse else None
        h, r, routed, mix = decoder_layer(
            m, params[name], bias, h, valid, hidden, sparse, faults)
        rows.append(r)
        if routed is not None:
            scores, ch, weights, npairs = routed
            chosen.append(ch)
            pairs.append(npairs)
    last = jnp.sum(h, axis=2)
    normed = rms_norm(last, params["out_norm"]["scale"], m.lfm_norm_eps)
    u1 = labels.shape[1] + 1
    at = np.clip(a_lens[:, None] + np.arange(u1)[None, :], 0, s - 1)
    head = params["embed"] if m.lm_tied_head else params["lm_head"]

    def at_text(y):
        return jnp.take_along_axis(y, jnp.asarray(at)[..., None], 1)

    out = {"logits": head_logits(at_text(normed), head, faults), "at": at,
           "steps": np.arange(u1)[None, :]
           <= np.asarray(label_lens)[:, None],
           "valid": np.asarray(valid), "scores": scores,
           "weights": weights, "h_pre": mix[0], "h_post": mix[1],
           "h_res": mix[2]}
    if m.lm_draft_layers:
        p = params["draft0"]
        # The module's next input at position i is the model's input at
        # i + 1, where there is one.
        ahead = jnp.pad(inputs[:, 1:], [(0, 0), (0, 1), (0, 0)])
        follows = jnp.asarray(np.pad(np.asarray(valid)[:, 1:],
                                     [(0, 0), (0, 1)]))
        halves = [rms_norm(ahead, p["embed_norm"]["scale"], m.lfm_norm_eps),
                  rms_norm(last, p["hidden_norm"]["scale"], m.lfm_norm_eps)]
        if "eh_swapped" in faults:
            halves.reverse()
        z = _mm(jnp.concatenate(halves, -1), p["eh_proj"]["kernel"],
                faults)
        z = jnp.broadcast_to(z[:, :, None, :], h.shape)
        z, r, routed, _ = decoder_layer(
            m, p["layer"], buffers["draft0"]["layer"]["moe"]["expert_bias"],
            z, follows, hidden, True, faults)
        rows.append(r)
        pairs.append(routed[3])
        normed = rms_norm(jnp.sum(z, axis=2), p["out_norm"]["scale"],
                          m.lfm_norm_eps)
        out["draft_logits"] = head_logits(at_text(normed), head, faults)
        out["draft_steps"] = np.arange(u1)[None, :] \
            < np.asarray(label_lens)[:, None]
        out["follows"] = np.asarray(follows)
    out.update(rows=rows, chosen=chosen, pairs=jnp.stack(pairs))
    return out
