"""Plain reference of the SmallThinker-21BA3B-Instruct decoder-only
recogniser (``model_name: smallthinker_21b_instruct``) on the TRAINING
path: forward pass, loss and every parameter's gradient in
straightforward ``jax.numpy``, float32, matrix products at ``highest``
precision, band and causal masks by index arithmetic; no kernels, no
sorting, no dispatch. Independent of ``deepspeech_tpu``: it shares
with the program only the names of the parameters it is handed.

The layer, as ISSUE 45 writes it (``x [S, 2560]`` the residual stream
of one sequence, positions from 0 at the first prefix frame, every
Linear without bias):

  1. r = x W_r over all 64 experts, from the layer's INPUT, before any
     norm; E = top6(r); w = softmax(r[E]) over the six chosen logits
     (a softmax over all 64, the chosen six renormalised); no bias, no
     groups, no scale
  2. a = RMSNorm(x; eps 1e-6); q = a W_q as 28 heads of 128, k = a W_k,
     v = a W_v as 4 heads of 128; on a SLIDING layer q and k are
     rotated over the whole head (rotate-half pairing, theta 1.5e6), on
     a GLOBAL layer nothing; no q/k norm, no gate
  3. query i attends keys j <= i, and i - 4096 < j on a sliding layer;
     scores q k / sqrt(128), softmax; query head h reads key/value head
     h // 7; y = x + concat(heads) W_o
  4. m = RMSNorm(y); out = sum over e in E HELD HERE of w_e * (relu(m
     G_e) * (m U_e)) D_e; x' = y + out
  5. the shell: audio prefix projected [1288, 2560], transcript
     embedding, final RMSNorm, an UNTIED head, loss = mean over
     utterances of the summed cross-entropy of their u + 1 targets over
     the vocabulary slice; padded positions are neither routed nor
     scored

Departures, all shared with the program and listed under ``assumed``
in ``configs/smallthinker_21b_a3b.json``: the audio prefix and ids,
the float32 router, and THE SHARE (``experts_held`` experts from
``expert_offset``, the vocabulary slice; with 64 held it is the uncut
layer). One departure is this file's own, and changes no number: a
block of queries' attention and a held expert's feed-forward are
wrapped in ``jax.checkpoint``, so that the backward pass computes their
``[Q, S]`` probabilities and ``[S, 768]`` activations again instead of
keeping every block's: at 6,784 positions and 28 heads the kept
probabilities alone would be 5 GB a layer beside the program's
weights. The held experts are a ``lax.scan`` (one body compiled, not
sixteen).

``faults`` names departures put in on purpose, for the controls of
``benchmark/tests/test_smallthinker_ref_control.py``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

FAULTS = ("float8_weights", "router_normed_input", "router_post_attn",
          "sigmoid_scores", "softmax_all_no_renorm", "silu_experts",
          "rope_on_global", "no_rope_on_sliding", "window_plus_1",
          "no_window", "qk_norm", "kv_head_mod", "tied_head")
HI = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gain


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


@partial(jax.checkpoint, static_argnums=(0, 1, 6))
def attention_block(m, kind, q, k, v, i0, faults):
    """The queries ``i0 .. i0 + Q`` (``q [B, Q, heads, hd]``) against
    ALL keys ``k, v [B, S, kv, hd]``: ``[B, Q, heads * hd]``."""
    b, nq, nh, hd = q.shape
    s, nkv = k.shape[1], k.shape[2]
    i = i0 + jnp.arange(nq)[:, None]
    j = jnp.arange(s)[None, :]
    seen = j <= i
    if kind == "sliding_attention" and "no_window" not in faults:
        seen = seen & (i - j < m.lfm_window + ("window_plus_1" in faults))
    # query head h reads key/value head h // (heads / kv)
    of = np.arange(nh) % nkv if "kv_head_mod" in faults \
        else np.arange(nh) // (nh // nkv)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k[:, :, of], precision=HI) \
        / np.sqrt(hd)
    scores = jnp.where(seen, scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1),
                     v[:, :, of], precision=HI)
    return out.reshape(b, nq, nh * hd)


def attention(m, kind, p, x, faults, q_block):
    b, s, _ = x.shape
    nh, nkv, hd = m.lfm_heads, m.lfm_kv_heads, head_dim(m)
    q = _mm(x, p["q"]["kernel"]).reshape(b, s, nh, hd)
    k = _mm(x, p["k"]["kernel"]).reshape(b, s, nkv, hd)
    v = _mm(x, p["v"]["kernel"]).reshape(b, s, nkv, hd)
    if "qk_norm" in faults:
        q = rms_norm(q, 1.0, m.lfm_norm_eps)
        k = rms_norm(k, 1.0, m.lfm_norm_eps)
    turns = kind == "sliding_attention"
    if "rope_on_global" in faults:
        turns = True
    if "no_rope_on_sliding" in faults:
        turns = False
    if turns:
        q, k = rope(m, q), rope(m, k)
    out = jnp.concatenate([
        attention_block(m, kind, q[:, i0:i0 + q_block], k, v,
                        np.int32(i0), faults)
        for i0 in range(0, s, q_block)], axis=1)
    return _mm(out, p["o"]["kernel"])


def routing(m, router, x, faults, pinned=None):
    """The router's logits ``[B, S, E]``, the layer's own choice of
    experts, the experts routed to and their combine weights ``[B, S,
    k]``. ``pinned`` takes the place of the layer's own choice in the
    last two (the weights are still its own logits of the experts so
    chosen)."""
    logits = _mm(x, router)
    own = chosen = jax.lax.top_k(logits, m.lfm_top_k)[1]
    if pinned is not None:
        chosen = jnp.asarray(pinned).reshape(chosen.shape)
    picked = jnp.take_along_axis(logits, chosen, -1)
    if "sigmoid_scores" in faults:
        w = jax.nn.sigmoid(picked)
        w = w / jnp.sum(w, -1, keepdims=True)
    elif "softmax_all_no_renorm" in faults:
        w = jnp.take_along_axis(jax.nn.softmax(logits, -1), chosen, -1)
    else:
        w = jax.nn.softmax(picked, -1)
    return logits, own, chosen, w


@partial(jax.checkpoint, static_argnums=(4,))
def expert(w13, w2, x, w_e, faults):
    """One gated expert over every position, weighted: ``w_e * (act(x
    G) * (x U)) D``, gate and up side by side in ``w13``."""
    f = w2.shape[0]
    act = jax.nn.silu if "silu_experts" in faults else jax.nn.relu
    return w_e[..., None] * _mm(
        act(_mm(x, w13[:, :f])) * _mm(x, w13[:, f:]), w2)


def experts(m, p, x, own, chosen, w, valid, faults):
    """The held experts' part of the routed feed-forward, one expert
    after the other over ALL positions with the weight each position
    gives it (zero where it did not choose it), and the (position,
    expert) pairs the layer's OWN choice puts on each held expert."""
    def one(out, held):
        i, w13, w2 = held
        e = m.expert_offset + i
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), -1) * valid
        return (out + expert(w13, w2, x, w_e, faults),
                jnp.sum(jnp.any(own == e, -1) * valid))

    return jax.lax.scan(one, jnp.zeros_like(x), (
        jnp.arange(p["w13"].shape[0]), p["w13"], p["w2"]))


def layout(a_lens, labels, label_lens, s):
    """Which of the ``s`` positions hold audio, which text, the ids
    embedded at the text positions (id 0 starts a transcript) and the
    target at each (id 0 ends it)."""
    u_max = labels.shape[1]
    t = np.arange(s)[None, :] - a_lens[:, None]
    audio = t < 0
    text = (t >= 0) & (t <= label_lens[:, None])
    pad = jnp.pad(labels, [(0, 0), (1, 1)])     # id 0 before and after
    ids = jnp.take_along_axis(pad, jnp.clip(t, 0, u_max), 1)
    targets = jnp.take_along_axis(pad, jnp.clip(t + 1, 1, u_max + 1), 1)
    targets = jnp.where(t < label_lens[:, None], targets, 0)
    return audio, text, jnp.where(text, ids, 0), jnp.where(text, targets, 0)


def forward(m, params, feats, lens, labels, label_lens, seq_positions,
            faults=(), pinned=None, q_block: int = 256):
    """Everything the comparison reads, as a dict: ``hidden`` [B, S, D]
    (normed), ``valid`` [B, S], ``logp`` [B, U+1] of the targets and
    ``logp_mask``, ``nll`` [B], the last layer's router ``logits`` [B,
    S, E], every layer's OWN ``chosen`` sets [B, S, k] and the
    ``pairs_held`` [layers, held] they put on each held expert.
    ``pinned``: one chosen set per layer, ``[B * S, k]`` or ``[B, S,
    k]``, to route by in place of the layers' own."""
    b, t, nf = feats.shape
    fs = m.frame_stack
    s = seq_positions
    n = min(-(-t // fs), s)
    x = jnp.pad(feats.astype(jnp.float32), [(0, 0), (0, -t % fs), (0, 0)]
                ).reshape(b, -1, fs * nf)[:, :n]
    a_lens = -(-lens // fs)
    audio, text, ids, targets = layout(a_lens, labels, label_lens, s)
    valid = audio | text
    pre = _mm(x, params["prefix"]["kernel"])
    pre = jnp.pad(pre, [(0, 0), (0, s - n), (0, 0)])
    emb = jnp.take(params["embed"], ids, axis=0)
    h = jnp.where(audio[..., None], pre,
                  jnp.where(text[..., None], emb, 0.0))
    eps = m.lfm_norm_eps
    logits, chosen, pairs = None, [], []
    for i, kind in enumerate(m.lfm_layer_types):
        p = params[f"layer{i}"]
        pin = None if pinned is None else pinned[i]
        normed = rms_norm(h, p["op_norm"]["scale"], eps)
        tap = normed if "router_normed_input" in faults else h
        if "router_post_attn" not in faults:
            logits, own, ch, w = routing(m, p["moe"]["router"], tap,
                                         faults, pin)
        h = h + attention(m, kind, p["attn"], normed, faults, q_block)
        if "router_post_attn" in faults:
            logits, own, ch, w = routing(m, p["moe"]["router"], h,
                                         faults, pin)
        y = rms_norm(h, p["ffn_norm"]["scale"], eps)
        out, n_pairs = experts(m, p["moe"], y, own, ch, w, valid, faults)
        h = h + out
        chosen.append(own)
        pairs.append(n_pairs)
    hidden = rms_norm(h, params["out_norm"]["scale"], eps)
    # the logits of the u + 1 text positions of each utterance only
    u1 = labels.shape[1] + 1
    at = jnp.clip(a_lens[:, None] + np.arange(u1)[None, :], 0, s - 1)
    at_text = jnp.take_along_axis(hidden, at[..., None], 1)
    head = params["embed"] if "tied_head" in faults else params["lm_head"]
    logp_all = jax.nn.log_softmax(
        jnp.einsum("bud,vd->buv", at_text, head, precision=HI), -1)
    want = jnp.take_along_axis(targets, at, 1)
    logp = jnp.take_along_axis(logp_all, want[..., None], -1)[..., 0]
    mask = np.arange(u1)[None, :] <= label_lens[:, None]
    return {"hidden": hidden, "valid": valid, "logp": logp,
            "logp_mask": mask,
            "nll": -jnp.sum(jnp.where(mask, logp, 0.0), axis=1),
            "logits": logits, "chosen": chosen,
            "pairs_held": jnp.stack(pairs)}


def float8(params):
    """Every matrix rounded to float8 (e4m3), the nearest precision
    below the configuration's bfloat16 (the control
    ``float8_weights``)."""
    return jax.tree.map(
        lambda x: x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        if np.ndim(x) >= 2 else x, params)


def loss_and_grads(m, params, feats, lens, labels, label_lens,
                   seq_positions, faults=(), pinned=None,
                   q_block: int = 256):
    """Mean over utterances of the summed cross-entropy, its gradient
    with respect to every parameter, and the forward pass's readings. A
    gradient is a sum over the pairs routed to each expert, so the
    comparison pins the chosen sets to the system's (``pinned``): the
    near-ties that bfloat16 flips are bounded on their own and do not
    drown the gradients' reading."""
    params = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), params)
    if "float8_weights" in faults:
        params = float8(params)

    def mean_nll(p):
        out = forward(m, p, feats, lens, labels, label_lens,
                      seq_positions, faults, pinned, q_block)
        return jnp.mean(out["nll"]), out

    (loss, out), grads = jax.value_and_grad(mean_nll, has_aux=True)(params)
    return loss, grads, out


def clip_by_global_norm(grads, max_norm):
    """The gradients' global norm, and the gradients scaled down to
    ``max_norm`` where it is larger."""
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-30))
    return norm, jax.tree.map(lambda g: g * scale, grads)


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


def chosen_differ_share(got, want, valid) -> float:
    """Share of valid (position, layer) whose chosen SET of experts
    differs (rounding upstream flips near-ties)."""
    differ = total = 0
    valid = np.asarray(valid, bool).reshape(-1)
    for g, w in zip(got, want):
        g = np.sort(np.asarray(g).reshape(valid.size, -1), -1)[valid]
        w = np.sort(np.asarray(w).reshape(valid.size, -1), -1)[valid]
        differ += int(np.any(g != w, axis=-1).sum())
        total += g.shape[0]
    return differ / max(total, 1)
