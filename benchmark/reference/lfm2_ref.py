"""Plain reference of the LFM2 decoder-only recogniser: forward pass,
loss and gradients in straightforward ``jax.numpy``, float32, matrix
products at ``highest`` precision; no kernels, no sorting, no packing
tricks. Independent of ``deepspeech_tpu/models/lfm2.py`` and
``deepspeech_tpu/ops/moe*.py``: it shares with the program only the
names of the parameters it is handed.

The layer equations are those of the published ``config.json``
(``model_type: lfm2_moe``; ISSUE 30 writes them out):

  layer      h = h + operator(RMSNorm(h)); h = h + ffn(RMSNorm(h));
             RMSNorm after the last layer; eps 1e-5, learned gain
  conv       [B, C, x] = split3(W_in h); z = B * x;
             c_t = sum_{j<3} k_j * z_{t-j} (zeros before position 0);
             out = W_out (C * c)
  attention  32 query / 8 key-value heads of 64; RMSNorm over each
             head of q and k; rotary over the whole head, theta 1e6,
             rotate-half; causal softmax(q k^T / 8); W_o
  dense ffn  W_2 (silu(W_1 x) * (W_3 x))
  experts    s = sigmoid(W_g x) over all 64; top-4 of s + b chosen;
             w = s[chosen] / (sum + 1e-6) * routed_scaling_factor;
             sum over chosen e of w_e * W2_e (silu(W1_e x) * (W3_e x))

Departures from the published description, all shared with the program
and listed under ``assumed`` in ``configs/lfm2_24b_a2b.json``:
(a) the input: 8 stacked spectrogram frames projected by one matrix
are the prefix of the sequence, left-packed before the transcript;
id 0 starts and ends a transcript; the loss is the summed
cross-entropy of an utterance's u+1 targets; (b) the output head is
the tied embedding matrix; (c) the router's product and sigmoid are
float32; (d) the selection bias is a buffer held at its seeded value;
(e) THE SHARE: of the 64 experts only ``experts_held`` from
``expert_offset`` are here, and what the others would have added is
left out (the experts are a loop over dense products with a mask);
the vocabulary is the chip's slice. With ``experts_held`` = 64 it is
the uncut layer. The gate and up matrices arrive side by side as
``w13`` and are split here.

``faults`` names departures put in on purpose, for the controls of
``benchmark/tests/test_lfm2_ref_control.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

ROUTED_SCALING_FACTOR = 1.0    # as published

FAULTS = ("no_norm_topk", "no_select_bias", "no_qk_norm", "two_taps",
          "noncausal_filter", "route_padding", "loss_on_padding")


def _mm(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gain


def short_conv(p, h, taps, faults):
    bcx = _mm(h, p["in_proj"]["kernel"])
    d = h.shape[-1]
    gate_b, gate_c, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    z = gate_b * x
    s = z.shape[1]
    if "two_taps" in faults:
        taps = 2
    c = jnp.zeros_like(z)
    for j in range(taps):
        if "noncausal_filter" in faults:   # looks ahead by j
            shifted = jnp.pad(z, [(0, 0), (0, j), (0, 0)])[:, j:j + s]
        else:
            shifted = jnp.pad(z, [(0, 0), (j, 0), (0, 0)])[:, :s]
        c = c + p["filter"][j] * shifted
    return _mm(gate_c * c, p["out_proj"]["kernel"])


def rope(x, theta):
    """x [B, S, H, D]; pairs (i, i + D/2)."""
    s, d = x.shape[1], x.shape[-1]
    freq = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.arange(s, dtype=np.float64)[:, None] * freq[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(m, p, h, faults):
    b, s, d = h.shape
    nh, nkv = m.lfm_heads, m.lfm_kv_heads
    hd = d // nh
    q = _mm(h, p["q"]["kernel"]).reshape(b, s, nh, hd)
    k = _mm(h, p["k"]["kernel"]).reshape(b, s, nkv, hd)
    v = _mm(h, p["v"]["kernel"]).reshape(b, s, nkv, hd)
    if "no_qk_norm" not in faults:
        q = rms_norm(q, p["q_norm"]["scale"], m.lfm_norm_eps)
        k = rms_norm(k, p["k_norm"]["scale"], m.lfm_norm_eps)
    q, k = rope(q, m.lfm_rope_theta), rope(k, m.lfm_rope_theta)
    # Each key/value head serves heads/kv_heads consecutive query heads.
    k = jnp.repeat(k, nh // nkv, axis=2)
    v = jnp.repeat(v, nh // nkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        precision=jax.lax.Precision.HIGHEST) / np.sqrt(hd)
    future = np.triu(np.ones((s, s), bool), 1)
    scores = jnp.where(future, -jnp.inf, scores)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v,
                     precision=jax.lax.Precision.HIGHEST)
    return _mm(out.reshape(b, s, d), p["o"]["kernel"])


def swiglu(w1, w3, w2, x):
    return _mm(jax.nn.silu(_mm(x, w1)) * _mm(x, w3), w2)


def experts(m, p, bias, x, valid, faults, pinned=None):
    """The held experts' part of the routed feed-forward, the router's
    scores and choices, and the number of routed (position, expert)
    pairs whose expert is held here. ``pinned`` [B, S, k], if given,
    takes the place of the layer's own choice (the weights are still
    its own scores of the experts so chosen)."""
    scores = jax.nn.sigmoid(_mm(x, p["router"]))           # [B, S, E]
    chosen_by = scores
    if "no_select_bias" not in faults:      # use_expert_bias
        chosen_by = scores + bias
    _, chosen = jax.lax.top_k(chosen_by, m.lfm_top_k)      # [B, S, k]
    if pinned is not None:
        chosen = jnp.asarray(pinned).reshape(chosen.shape)
    w = jnp.take_along_axis(scores, chosen, -1)
    if "no_norm_topk" not in faults:        # norm_topk_prob
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    w = w * ROUTED_SCALING_FACTOR
    routed = jnp.ones_like(valid) if "route_padding" in faults else valid
    f = m.lfm_expert_dim
    out = jnp.zeros_like(x)
    pairs = 0.0
    for i in range(p["w13"].shape[0]):
        e = m.expert_offset + i
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), -1) * routed
        y = swiglu(p["w13"][i][:, :f], p["w13"][i][:, f:], p["w2"][i], x)
        out = out + w_e[..., None] * y
        pairs = pairs + jnp.sum(jnp.any(chosen == e, -1) * routed)
    return out, scores, chosen, pairs


def layout(a_lens, labels, label_lens, s):
    """Which of the ``s`` positions hold audio, which text, the ids
    embedded at the text positions and the target at each."""
    b, u_max = labels.shape
    pos = np.arange(s)[None, :]
    t = pos - a_lens[:, None]                   # 0 at the start symbol
    audio = t < 0
    text = (t >= 0) & (t <= label_lens[:, None])
    pad = jnp.pad(labels, [(0, 0), (1, 1)])     # id 0 before and after
    ids = jnp.take_along_axis(pad, jnp.clip(t, 0, u_max), 1)
    targets = jnp.take_along_axis(pad, jnp.clip(t + 1, 1, u_max + 1), 1)
    targets = jnp.where(t < label_lens[:, None], targets, 0)
    return audio, text, jnp.where(text, ids, 0), jnp.where(text, targets, 0)


def forward(m, params, buffers, feats, lens, labels, label_lens,
            seq_positions, faults=(), pinned=None):
    """Everything the comparison reads, as a dict: ``hidden`` [B,S,D]
    (normed), ``valid`` [B,S], ``logp`` [B,U+1] of the targets and
    ``logp_mask``, ``nll`` [B], each expert layer's ``scores``,
    ``chosen`` and ``pairs_held``. ``pinned``: one chosen set per
    expert layer to route by in place of the layers' own."""
    params = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), params)
    b, t, nf = feats.shape
    k = m.frame_stack
    n = -(-t // k)
    x = jnp.pad(feats.astype(jnp.float32), [(0, 0), (0, n * k - t), (0, 0)]
                ).reshape(b, n, k * nf)
    a_lens = -(-lens // k)
    s = seq_positions
    audio, text, ids, targets = layout(a_lens, labels, label_lens, s)
    valid = audio | text
    pre = _mm(x, params["prefix"]["kernel"])
    pre = jnp.pad(pre, [(0, 0), (0, s - n), (0, 0)])
    emb = params["embed"][ids]
    h = jnp.where(audio[..., None], pre,
                  jnp.where(text[..., None], emb, 0.0))
    scores, chosen, pairs = [], [], []
    for i, kind in enumerate(m.lfm_layer_types):
        p = params[f"layer{i}"]
        y = rms_norm(h, p["op_norm"]["scale"], m.lfm_norm_eps)
        if kind == "conv":
            h = h + short_conv(p["conv"], y, m.lfm_conv_taps, faults)
        else:
            h = h + attention(m, p["attn"], y, faults)
        y = rms_norm(h, p["ffn_norm"]["scale"], m.lfm_norm_eps)
        if i < m.lfm_dense_layers:
            f = p["ffn"]
            h = h + swiglu(f["w1"]["kernel"], f["w3"]["kernel"],
                           f["w2"]["kernel"], y)
        else:
            bias = buffers[f"layer{i}"]["moe"]["expert_bias"]
            out, sc, ch, n = experts(
                m, p["moe"], bias, y, valid, faults,
                None if pinned is None else pinned[len(chosen)])
            h = h + out
            scores.append(sc)
            chosen.append(ch)
            pairs.append(n)
    hidden = rms_norm(h, params["out_norm"]["scale"], m.lfm_norm_eps)
    logits = _mm(hidden, params["embed"].T)                  # [B, S, V]
    logp_all = jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                   targets[..., None], -1)[..., 0]
    counted = jnp.ones_like(text) if "loss_on_padding" in faults else text
    nll = -jnp.sum(jnp.where(counted, logp_all, 0.0), axis=1)
    # The targets' log-probabilities in transcript order, [B, U+1].
    u1 = labels.shape[1] + 1
    at = jnp.clip(a_lens[:, None] + np.arange(u1)[None, :], 0, s - 1)
    return {"hidden": hidden, "valid": valid,
            "logp": jnp.take_along_axis(logp_all, at, 1),
            "logp_mask": np.arange(u1)[None, :] <= label_lens[:, None],
            "nll": nll, "scores": scores, "chosen": chosen,
            "pairs_held": jnp.stack(pairs) if pairs else jnp.zeros(0)}


def loss_and_grads(m, params, buffers, feats, lens, labels, label_lens,
                   seq_positions, faults=(), pinned=None):
    """Mean over utterances of the summed cross-entropy, and its
    gradient with respect to every parameter. A gradient is a sum over
    the pairs routed to each expert, so the comparison pins the chosen
    sets to the system's (``pinned``): the few per cent of near-ties
    that bfloat16 flips are bounded on their own (``chosen_differ``)
    and do not drown the gradients' reading."""
    def mean_nll(p):
        return jnp.mean(forward(m, p, buffers, feats, lens, labels,
                                label_lens, seq_positions, faults,
                                pinned)["nll"])

    return jax.value_and_grad(mean_nll)(params)


# AdamW as the configuration file states it (``assumed`` (i)): optax's
# defaults, no weight decay.
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


def clip_by_global_norm(grads, max_norm):
    """The gradients' global norm, and the gradients scaled down to
    ``max_norm`` where it is larger."""
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-30))
    return norm, jax.tree.map(lambda g: g * scale, grads)


def adamw_first_update(g, lr):
    """The change AdamW makes to a parameter in its FIRST step (both
    moments start at zero), for the clipped gradient ``g``, and the
    moments it leaves: with the bias corrections m^ = g and v^ = g^2,
    so the change is -lr * g / (|g| + eps), the gradient's sign."""
    mu, nu = (1 - ADAM_B1) * g, (1 - ADAM_B2) * g * g
    m_hat, v_hat = mu / (1 - ADAM_B1), nu / (1 - ADAM_B2)
    return -lr * m_hat / (jnp.sqrt(v_hat) + ADAM_EPS), mu, nu


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
