"""Plain reference of the MiniCPM-SALA decoder-only recogniser
(``model_type: minicpm_sala``): the full forward pass over each packed
sequence (prefix + start + labels) in straightforward ``jax.numpy``,
float32, matrix products at ``highest`` precision; the linear-attention
recurrence a ``lax.scan`` over the positions, never chunked; the sparse
layer's selection by the equations with exact scores, its attention a
dense masked softmax in blocks of queries (so that one block's ``[heads,
queries, S]`` scores fit beside the program); no cache, no kernels, no
loop of steps, no batching tricks. Independent of ``deepspeech_tpu``: it
shares with the program only the names of the parameters it is handed.
It upcasts ONE layer's matrices and ONE block of vocabulary rows at a
time.

The layers (ISSUE 54 writes them out), ``x [S, D]`` one sequence's
residual stream, D = 4,096, r = scale_depth / sqrt(32) = 0.2475:

  shell      h_0 = 12 * (Emb(t) | frames W_prefix); every sub-layer
             x += r * f(RMSNorm(x)); SwiGLU 16,384;
             logits = (RMSNorm(h_L) W_head^T) / 16, untied
  lightning  q, k = RoPE(RMSNorm_head(x W_q)), RoPE(RMSNorm_head(x W_k))
             (32 heads of 128, theta 1e4), v = x W_v; a head's state
             S_t = lambda_h S_{t-1} + k_t^T v_t ([128, 128], zero before
             position 0), o_t = q_t S_t / sqrt(128); lambda_h = exp(-s_h
             (1 - l / 31 + 1e-5)), s_h = 2^(-8 h / 32), h = 1..32, l the
             PUBLISHED layer index; y = (RMSNorm(o_t) * sigmoid(x W_g))
             W_o, the norm over all 4,096 channels
  minicpm4   q = RMSNorm_head(x W_q) (32 heads), k = RMSNorm_head(x
             W_k), v = x W_v (2 heads of 128), no positions; query head
             h reads key/value head h // 16. A query whose sequence is at
             most dense_len rows attends to rows 0..t. Past it: pooled
             keys Kc_j = mean(k[16 j : 16 j + 32]) for the windows whole
             inside 0..t; per key/value head p_h = softmax_j(q_h . Kc_j /
             sqrt(128)), s_j = sum over its 16 heads; block b (rows 64 b
             .. 64 b + 63) scores max s_j over the windows that overlap
             it; read = block 0, the 32 blocks that hold the last 2,048
             rows (t // 64 - 31 .. t // 64), and the 64 best of the
             rest; softmax over the read rows <= t;
             y = (heads * sigmoid(x W_g)) W_o

Departures, all shared with the program and listed under ``assumed`` in
``configs/minicpm_sala.json``: the audio prefix (8 stacked frames
projected by one matrix, left-packed before the transcript, id 0 starts
it) entering at the embeddings' scale; positions from 0 at the first
prefix frame; the seeded norm gains; and WHICH SEQUENCE a query's
``dense_len`` switch looks at: a served transcript is one prefill call
over the prefix (a rows) and then single steps, so a prefix query's
sequence is the prefix (a rows) and a text query's the rows up to its
own (t + 1): the full forward pass here takes the switch the served
calls take, or prefill + steps could equal no single pass.

``faults`` names departures put in on purpose, for the controls of
``benchmark/tests/test_minicpm_sala_ref_control.py``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

FAULTS = (
    "float8_weights", "bf16_state", "pool_stride_wrong",
    "topk_without_forced", "decay_without_layer", "no_output_norm",
    "no_depth_scale", "no_qk_norm", "rope_on_sparse", "no_rope_on_linear",
    "no_gate", "dense_everywhere", "kv_h_mod", "emb_scale_1",
    "logits_unscaled", "tied_head")
HI = jax.lax.Precision.HIGHEST
SPARSE, LINEAR = "sparse_attention", "linear_attention"


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


def decays(m, index: int, faults=()):
    """``lambda_h [heads]`` of the linear layer at published index
    ``index``."""
    h = np.arange(1, m.lin_heads + 1, dtype=np.float64)
    slope = 2.0 ** (-8.0 * h / m.lin_heads)
    if "decay_without_layer" not in faults:
        slope = slope * (1.0 - index / (m.lin_depth - 1) + 1e-5)
    return np.exp(-slope).astype(np.float32)


def linear_attention(m, p, x, index, at, faults):
    """The mixer's gated output before ``W_o`` ``[B, S, D']``, and the
    state ``[B, n, H, hd, hd]`` (``S[k, v]``) after each position of
    ``at [B, n]``; ``index``: the layer's published index, or its
    decays ``lambda_h [heads]`` themselves."""
    b, s, _ = x.shape
    nh, hd = m.lin_heads, m.lin_head_dim
    q, k, v = (_mm(x, p[n]["kernel"], faults).reshape(b, s, nh, hd)
               for n in ("q", "k", "v"))
    if "no_qk_norm" not in faults:
        q = rms_norm(q, p["q_norm"]["scale"], m.lfm_norm_eps)
        k = rms_norm(k, p["k_norm"]["scale"], m.lfm_norm_eps)
    if "no_rope_on_linear" not in faults:
        q, k = rope(m.lin_rope_theta, q), rope(m.lin_rope_theta, k)
    lam = decays(m, index, faults) if np.ndim(index) == 0 else index
    lam = jnp.asarray(lam)[None, :, None, None]
    carried = jnp.bfloat16 if "bf16_state" in faults else jnp.float32

    def step(carry, t):
        state, kept = carry
        state = lam * state.astype(jnp.float32) \
            + k[:, t, :, :, None] * v[:, t, :, None, :]
        state = state.astype(carried)
        o = jnp.sum(q[:, t, :, :, None] * state.astype(jnp.float32),
                    axis=-2)
        hit = (at == t)[:, :, None, None, None]
        kept = jnp.where(hit, state.astype(jnp.float32)[:, None], kept)
        return (state, kept), o

    zero = jnp.zeros((b, nh, hd, hd), carried)
    (_, kept), o = jax.lax.scan(
        step, (zero, jnp.zeros((b, at.shape[1], nh, hd, hd), jnp.float32)),
        jnp.arange(s))
    o = jnp.moveaxis(o, 0, 1).reshape(b, s, nh * hd) / np.sqrt(hd)
    if "no_output_norm" not in faults:
        o = rms_norm(o, p["o_norm"], m.lfm_norm_eps)
    if "no_gate" not in faults:
        o = o * jax.nn.sigmoid(_mm(x, p["gate"]["kernel"], faults))
    return o, kept


def pooled_keys(m, k, faults=()):
    """``Kc_j = mean(k[stride j : stride j + kernel])`` over the whole
    windows of ``k [B, S, kv, hd]``."""
    kernel, stride = m.sparse_kernel, m.sparse_stride
    if "pool_stride_wrong" in faults:
        stride = kernel
    n = max((k.shape[1] - kernel) // stride + 1, 0)
    rows = np.arange(n)[:, None] * stride + np.arange(kernel)[None, :]
    return jnp.mean(k[:, rows], axis=2), stride


def selection(m, q, pooled, stride, t, dense, blocks, faults=()):
    """``[B, kv, Q, blocks]`` bool: the blocks the queries ``q [B, Q,
    kv, rep, hd]`` at rows ``t [B, Q]`` read (``dense [B, Q]``: every
    block in reach)."""
    kernel, block = m.sparse_kernel, m.sparse_block
    n = pooled.shape[1]
    hd = q.shape[-1]
    mine = (t // block)[:, None, :, None]
    b = np.arange(blocks)[None, None, None, :]
    reach = b <= mine
    if n == 0:
        return jnp.broadcast_to(reach, (q.shape[0], q.shape[2],
                                        q.shape[1], blocks))
    logits = jnp.einsum("bqgrd,bjgd->bgrqj", q, pooled, precision=HI) \
        / np.sqrt(hd)
    whole = (np.arange(n) * stride + kernel - 1)[None, None, :] \
        <= t[:, :, None]
    at = whole[:, None, None]
    probs = jax.nn.softmax(jnp.where(at, logits, -jnp.inf), axis=-1)
    probs = jnp.where(at, probs, 0.0)       # no whole window: no score
    score = jnp.where(whole[:, None], jnp.sum(probs, axis=2), -jnp.inf)
    # windows that overlap block c: rows stride j .. stride j + kernel - 1
    # against rows block c .. block c + block - 1
    j0 = np.arange(n) * stride
    over = (j0[None, :] + kernel - 1 >= np.arange(blocks)[:, None] * block) \
        & (j0[None, :] <= np.arange(blocks)[:, None] * block + block - 1)
    width = max(int(over.sum(1).max()), 1)
    table = np.full((blocks, width), n)
    for c in range(blocks):
        hit = np.nonzero(over[c])[0]
        table[c, :len(hit)] = hit
    padded = jnp.pad(score, [(0, 0)] * 3 + [(0, 1)],
                     constant_values=-jnp.inf)
    # a block in reach that no whole window overlaps yet ranks last,
    # not nowhere
    by_block = jnp.maximum(jnp.max(padded[..., table], axis=-1), -1e30)
    forced = (b < m.sparse_init_blocks) \
        | (b > mine - m.sparse_window // block)
    if "topk_without_forced" in faults:
        forced = jnp.zeros_like(forced)
    forced = forced | dense[:, None, :, None]
    total = m.sparse_init_blocks + m.sparse_window // block + m.sparse_topk
    ranked = jnp.where(reach, jnp.where(forced, jnp.inf, by_block),
                       -jnp.inf)
    if blocks <= total:
        return ranked > -jnp.inf
    least = jax.lax.top_k(ranked, total)[0][..., -1:]
    return (ranked >= least) & (ranked > -jnp.inf)


def sparse_attention(m, p, x, a_lens, q_block, faults):
    """The mixer's gated output before ``W_o`` ``[B, S, D']``, its keys
    and values ``[B, S, kv, hd]``, the pooled keys ``[B, n, kv, hd]``
    and the selection ``[B, kv, S, NB]``."""
    b, s, _ = x.shape
    nh, nkv = m.lfm_heads, m.lfm_kv_heads
    hd = m.lfm_head_dim or m.lfm_hidden // nh
    rep = nh // nkv
    q = _mm(x, p["q"]["kernel"], faults).reshape(b, s, nh, hd)
    k = _mm(x, p["k"]["kernel"], faults).reshape(b, s, nkv, hd)
    v = _mm(x, p["v"]["kernel"], faults).reshape(b, s, nkv, hd)
    if "no_qk_norm" not in faults:
        q = rms_norm(q, p["q_norm"]["scale"], m.lfm_norm_eps)
        k = rms_norm(k, p["k_norm"]["scale"], m.lfm_norm_eps)
    if "rope_on_sparse" in faults:
        q, k = rope(1e4, q), rope(1e4, k)
    if "kv_h_mod" in faults:        # head h on key/value head h % kv
        q = q.reshape(b, s, rep, nkv, hd).swapaxes(2, 3)
    q = q.reshape(b, s, nkv, rep, hd)
    pooled, stride = pooled_keys(m, k, faults)
    blocks = -(-s // m.sparse_block)
    pad = -s % q_block
    tiles = jnp.moveaxis(jnp.pad(
        q, [(0, 0), (0, pad)] + [(0, 0)] * 3).reshape(
            b, -1, q_block, nkv, rep, hd), 1, 0)
    lens = jnp.asarray(a_lens)
    key_block = np.arange(s) // m.sparse_block

    def tile(at):
        qs, i0 = at
        t = jnp.broadcast_to(i0 + jnp.arange(q_block)[None, :],
                             (b, q_block))
        # the served calls' sequences: the prefix for a prefix query,
        # the rows up to its own for a text query
        length = jnp.where(t < lens[:, None], lens[:, None], t + 1)
        dense = length <= m.sparse_dense_len
        if "dense_everywhere" in faults:
            dense = jnp.ones_like(dense)
        sel = selection(m, qs, pooled, stride, t, dense, blocks, faults)
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", qs, k, precision=HI) \
            / np.sqrt(hd)
        seen = sel[..., key_block] \
            & (np.arange(s)[None, None, None, :] <= t[:, None, :, None])
        probs = jax.nn.softmax(
            jnp.where(seen[:, :, None], scores, -jnp.inf), axis=-1)
        out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v, precision=HI)
        return out, sel

    out, sel = jax.lax.map(tile, (tiles, jnp.arange(0, s + pad, q_block)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s + pad, nkv, rep, hd)[:, :s]
    sel = jnp.moveaxis(sel, 0, 2).reshape(b, nkv, s + pad, blocks)[:, :, :s]
    if "kv_h_mod" in faults:
        out = out.swapaxes(2, 3)
    out = out.reshape(b, s, nh * hd)
    if "no_gate" not in faults:
        out = out * jax.nn.sigmoid(_mm(x, p["gate"]["kernel"], faults))
    return out, k, v, pooled, sel


@partial(jax.jit, static_argnums=(0, 1, 7, 8, 9))
def layer(m, kind, lam, p, x, a_lens, at, q_block, faults, rows=4096):
    """One layer: the new residual stream and what the comparison reads
    of its mixer (a dict); ``lam [heads]``: a linear layer's decays (an
    argument, so that the linear layers share one compiled program)."""
    eps = m.lfm_norm_eps
    depth = 1.0 if "no_depth_scale" in faults else m.mup_residual
    u = rms_norm(x, p["op_norm"]["scale"], eps)
    if kind == SPARSE:
        gated, k, v, pooled, sel = sparse_attention(
            m, p["sparse"], u, a_lens, q_block, faults)
        out = {"k": k, "v": v, "pooled": pooled, "chosen": sel,
               "gated_sparse": gated}
        mixed = _mm(gated, p["sparse"]["o"]["kernel"], faults)
    elif kind == LINEAR:
        gated, states = linear_attention(m, p["lin"], u, lam, at, faults)
        out = {"gated_linear": gated, "states": states}
        mixed = _mm(gated, p["lin"]["o"]["kernel"], faults)
    else:
        raise ValueError(f"layer type {kind!r}")
    y = x + depth * mixed
    f = rms_norm(y, p["ffn_norm"]["scale"], eps)
    w = p["ffn"]
    fed = []
    for i in range(0, f.shape[1], rows):      # 16,384 wide: in row blocks
        part = f[:, i:i + rows]
        fed.append(_mm(
            _mm(part, w["w3"]["kernel"], faults) * jax.nn.silu(
                _mm(part, w["w1"]["kernel"], faults)),
            w["w2"]["kernel"], faults))
    return y + depth * jnp.concatenate(fed, axis=1), out


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
            faults=(), q_block: int = 256, head_rows: int = 32768):
    """Everything the comparison reads, as a dict: ``logits`` [B, U+1,
    V] at each stream's text positions (what decode step j emits is at
    [:, j]) and ``steps`` [B, U+1] marking those a stream has; of the
    LAST sparse layer ``k`` and ``v`` [B, S, kv, hd], ``pooled`` [B, n,
    kv, hd] (window j the rows 16 j .. 16 j + 31), ``chosen`` [B, kv, S,
    NB] and ``gated_sparse`` [B, S, D']; of the LAST linear layer
    ``gated_linear`` and the state ``[B, H, hd, hd]`` (``S[k, v]``)
    after the prefix (``state_prefill``: position ``a - 1``) and after
    the stream's last step (``state_last``: position ``a + u``);
    ``valid`` [B, S]."""
    s = seq_positions
    faults = tuple(faults)
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
    h = jnp.where(audio[..., None], pre,
                  jnp.where(text[..., None], emb, 0.0))
    if "emb_scale_1" not in faults:
        h = m.mup_embedding * h
    at = jnp.asarray(np.stack(
        [a_lens - 1, a_lens + np.asarray(label_lens)], axis=1))
    index = m.lin_layer_index or tuple(range(len(m.lfm_layer_types)))
    out = {}
    for i, kind in enumerate(m.lfm_layer_types):
        h, got = layer(m, kind, jnp.asarray(decays(m, index[i], faults)),
                       params[f"layer{i}"], h, jnp.asarray(a_lens), at,
                       q_block, faults)
        out.update(got)       # the last layer of a kind stays
    hidden = rms_norm(h, params["out_norm"]["scale"], m.lfm_norm_eps)
    u1 = labels.shape[1] + 1
    where = np.clip(a_lens[:, None] + np.arange(u1)[None, :], 0, s - 1)
    at_text = jnp.take_along_axis(
        hidden, jnp.asarray(where)[..., None], 1).reshape(b * u1, -1)
    head = params["embed"] if "tied_head" in faults or m.lm_tied_head \
        else params["lm_head"]
    logits = jnp.concatenate([
        _head_block(at_text, head[i:i + head_rows], faults)
        for i in range(0, head.shape[0], head_rows)], axis=1)
    logits = logits.reshape(b, u1, -1)
    if "logits_unscaled" not in faults:
        logits = m.mup_lm_head * logits
    states = out.pop("states", None)
    if states is not None:
        out["state_prefill"], out["state_last"] = states[:, 0], states[:, 1]
    out.update(logits=logits, at=where, valid=valid,
               steps=np.arange(u1)[None, :]
               <= np.asarray(label_lens)[:, None])
    return out


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
    """Share of the valid (query, key/value head) whose chosen blocks
    differ from the reference's: ``got, want [B, kv, S, NB]`` bool (the
    wider of the two is cut to the other's blocks), ``valid [B, S]``."""
    got, want = np.asarray(got, bool), np.asarray(want, bool)
    nb = min(got.shape[-1], want.shape[-1])
    same = np.all(got[..., :nb] == want[..., :nb], axis=-1)   # [B, kv, S]
    mask = np.broadcast_to(np.asarray(valid, bool)[:, None], same.shape)
    return float(1.0 - same[mask].mean()) if mask.any() else 0.0


def blocks_differ_share(got, want, valid) -> float:
    """Share of the reference's chosen BLOCKS the other side did not
    choose (and of its own the reference did not: half the symmetric
    difference over the reference's count), over the valid (query,
    key/value head): a near-tie at the selection's edge moves one block
    of a set, a wrong rule many."""
    got, want = np.asarray(got, bool), np.asarray(want, bool)
    nb = min(got.shape[-1], want.shape[-1])
    got, want = got[..., :nb], want[..., :nb]
    mask = np.broadcast_to(np.asarray(valid, bool)[:, None],
                           want.shape[:-1])
    moved = np.sum(got != want, axis=-1)[mask].sum()
    return float(0.5 * moved / max(np.sum(want, axis=-1)[mask].sum(), 1))
