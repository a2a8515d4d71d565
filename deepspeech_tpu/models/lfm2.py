"""The decoder-only speech recogniser, and the LFM2 block.

``LFM2ASR`` is the shell of three families, the block chosen by the
preset's data: LFM2's layers below; the A.X-K1 block's latent attention
(``models/axk1.py``) with a shared expert beside the routed ones and a
head of its own; and the Xing4.0 block, which is the second over
``hc_streams`` residual streams mixed by hyper-connections
(``models/mhc.py``) with ``lm_draft_layers`` multi-token-prediction
modules (``DraftModule``) after the last layer. A fourth family's block
(``model_name: smallthinker``) is LFM2's with the preset's data: no
q/k norms, a router that reads the layer's INPUT before attention
(``moe_route_pre_attn``), a softmax over the chosen logits, gated-ReLU
experts. A fifth's (``model_type: falcon_h1``) is the HYBRID layer: a
Mamba-2 state-space mixer (``Mamba2Mixer``; the recurrence in
``ops/ssd_pallas.py``) and grouped-query attention side by side on one
normed input, summed, with muP multipliers that are the preset's data
(``mup_*``) and no expert layer. A sixth's (``model_type:
minicpm_sala``) has two layer kinds of its own: "sparse_attention",
grouped-query attention whose queries, past ``sparse_dense_len`` rows,
read a SELECTION of their cache's blocks (``Attention.select_sequence``
/ ``select_step``: keys mean-pooled over windows, a query's scores
against them pooled to blocks, the first block, a local window and the
top-k of the rest; ``ops/attn_pallas.py`` has the two kernels), and
"linear_attention" (``LinearAttention``: one constant decay a head, on
``ops/ssd_pallas.py``'s recurrence), each sub-layer's output times the
family's depth scaling (``mup_residual``). ``hidden`` / ``loss``
are the training path (the draft modules are not trained here);
``prefill`` and ``step`` the serving path through a cache
(``decode/lm_greedy.py``), for the layer kinds that have one (latent
and grouped-query attention: rows by position, under a selection with
pooled keys beside them; the hybrid layer: rows and a recurrent state;
linear attention: a state alone), and
``verify`` / ``draft`` its form over a few positions a stream, for a
loop that drafts for itself.

It is what ``train.objective="lm"`` trains: the acoustic
frames of an utterance, stacked and projected, are the prefix of the
decoder's sequence; the transcript follows and is trained by
next-token cross-entropy. The decoder from its embeddings to its
logits is LFM2's (``model_type: lfm2_moe``): pre-norm residual layers
whose operator is a gated short convolution or grouped-query attention
(per-head RMSNorm on q and k, rotary positions), and whose
feed-forward is a dense SwiGLU in the leading layers and a sparse
expert block (``ops/moe.py``) after them; RMSNorm before the tied
output head.

One utterance's sequence is LEFT-PACKED: ``a = ceil(frames /
frame_stack)`` prefix positions, then the embedding of id 0 (start),
then its ``u`` label ids; padding only on the right, so causality
alone keeps it out of every valid position. The target at the start
position is label 1, ..., at the last label id 0 (end). Padded
positions are not routed and earn no loss.
"""

from __future__ import annotations

from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..config import ModelConfig
from ..ops import attn_pallas, mhc_pallas, moe, ssd_pallas
from ..utils.impl import on_tpu
from . import mhc
from .axk1 import LatentAttention
from .rnn import stack_frames

_INIT = nn.initializers.normal(0.02)
BIAS_STD = 0.01
# Every decoder layer is rematerialised: it keeps its matrix products'
# results (the grouped ones by name) and recomputes the element-wise
# work between them (norms, gates, softmax, rotations) in the backward
# pass. At the benchmark cell's shapes that is 1.4 GB of temporaries
# less (compiled ahead of time for a v5e) for a few per cent of time.
REMAT_POLICY = jax.checkpoint_policies.save_from_both_policies(
    jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    jax.checkpoint_policies.save_only_these_names("moe_rows"))
# Past one block of queries the products' results are 35 kB a position
# and layer (3.8 GB for four recordings of 6,784 positions in four
# layers): such a layer keeps its input, the attention kernel's result
# and log-sum-exp and the grouped products' results by name (7.2 kB a
# position and 1.6 kB a routed pair), and runs the rest of its forward
# pass again in the backward pass: the projections, not the kernels.
LONG_REMAT_POLICY = jax.checkpoint_policies.save_only_these_names(
    "moe_rows", "attn_out", "attn_lse")


def remat_policy(cfg: ModelConfig):
    """What a rematerialised layer keeps: by the sequence the preset
    states (``lfm_seq_positions``), one block or more."""
    return (LONG_REMAT_POLICY if cfg.lfm_seq_positions > Attention.block
            else REMAT_POLICY)


def seq_positions(cfg: ModelConfig, frames: int, max_label_len: int
                  ) -> int:
    """Positions every sequence of a ``frames`` bucket is padded to."""
    least = -(-frames // cfg.frame_stack) + 1 + max_label_len
    if cfg.lfm_seq_positions:
        if cfg.lfm_seq_positions < least:
            raise ValueError(
                f"lfm_seq_positions={cfg.lfm_seq_positions} cannot hold "
                f"{frames} frames and {max_label_len} labels ({least})")
        return cfg.lfm_seq_positions
    return -(-least // 8) * 8


def gain_init(std: float):
    """Ones, or (``lfm_norm_gain_std``) 1 + normal(std)."""
    if not std:
        return nn.initializers.ones
    return lambda rng, shape, dtype=jnp.float32: (
        1.0 + std * jax.random.normal(rng, shape, dtype))


class RMSNorm(nn.Module):
    eps: float
    gain_std: float = 0.0

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", gain_init(self.gain_std),
                           (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (y * scale).astype(x.dtype)


class Linear(nn.Module):
    """``x @ kernel`` without bias: float32 parameter, operands in the
    activations' dtype, float32 accumulation."""

    features: int

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", _INIT, (x.shape[-1], self.features))
        return jnp.dot(x, kernel.astype(x.dtype))


def scaled(x, by):
    """``x`` times a muP multiplier (a number, or a vector over the
    last axis), in float32, in ``x``'s dtype."""
    return (x.astype(jnp.float32) * jnp.asarray(by, jnp.float32)
            ).astype(x.dtype)


class ShortConv(nn.Module):
    """Gated short convolution: ``[B, C, x] = split3(W_in h)``,
    ``c_t = sum_j k_j * (B * x)_{t-j}`` (depthwise, causal, zeros
    before position 0), ``W_out (C * c)``."""

    cfg: ModelConfig

    @nn.compact
    def __call__(self, h):
        d, taps = self.cfg.lfm_hidden, self.cfg.lfm_conv_taps
        gate_b, gate_c, x = jnp.split(
            Linear(3 * d, name="in_proj")(h), 3, axis=-1)
        z = gate_b * x
        filt = self.param("filter", nn.initializers.normal(taps ** -0.5),
                          (taps, d)).astype(z.dtype)
        c = z * filt[0]
        for j in range(1, taps):
            c = c + filt[j] * jnp.pad(z, [(0, 0), (j, 0), (0, 0)]
                                      )[:, :z.shape[1]]
        return Linear(d, name="out_proj")(gate_c * c)


def _softplus_inverse(rng, shape, dtype=jnp.float32):
    """``dt_bias``: the inverse softplus of a step drawn log-uniform
    in 0.001 .. 0.1."""
    dt = jnp.exp(jax.random.uniform(rng, shape, dtype)
                 * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
    return dt + jnp.log(-jnp.expm1(-dt))


class Mamba2Mixer(nn.Module):
    """The Mamba-2 state-space mixer (``ops/ssd_pallas.py`` has the
    recurrence): ``[z | x | B | C | dt] = (W_in u)`` times the family's
    multipliers by segment, a causal depthwise convolution of
    ``ssm_conv`` taps with bias and silu over ``[x | B | C]`` (zeros
    before position 0, the LAST tap on the current position), ``dt =
    softplus(dt + dt_bias)``, the recurrence a head, ``y * silu(z)``,
    RMSNorm over each group's channels with one gain, ``W_out``.

    ``__call__(u [B, S, D], valid [B, S])`` is the SEQUENCE form
    (positions 0..S-1, left-packed: padding only on the right) and
    returns the output and what the stream's cache holds after its last
    valid position ``a - 1``: the float32 state ``[B, heads, state,
    head]`` and the convolution's last ``taps - 1`` INPUTS ``[B, taps -
    1, channels]`` (positions ``a - 3 .. a - 1``; zeros where the
    stream is shorter). With ``cache``, that pair, it is the DECODE
    form, one new position a stream; a stream that is not live
    (``valid [B, 1]``) leaves its cache as it is."""

    cfg: ModelConfig

    @nn.compact
    def __call__(self, u, valid, cache=None):
        cfg = self.cfg
        b, s, _ = u.shape
        d, nh, n, g = (cfg.ssm_d_ssm, cfg.ssm_heads, cfg.ssm_state,
                       cfg.ssm_groups)
        p, taps, wide = d // nh, cfg.ssm_conv, d + 2 * g * n
        f32 = jnp.float32
        proj = Linear(2 * d + 2 * g * n + nh, name="in_proj")(u)
        by = np.repeat(np.asarray(cfg.mup_ssm, np.float32),
                       [d, d, g * n, g * n, nh]) * cfg.mup_ssm_in
        if np.any(by != 1.0):
            proj = scaled(proj, by)
        z, taken, dt = jnp.split(proj, [d, d + wide], axis=-1)
        filt = self.param("filter", nn.initializers.normal(taps ** -0.5),
                          (taps, wide)).astype(f32)
        bias = self.param("conv_bias", _INIT, (wide,)).astype(f32)
        dt_bias = self.param("dt_bias", _softplus_inverse, (nh,))
        a = -jnp.exp(self.param(
            "A_log", lambda rng, shape: jnp.log(jax.random.uniform(
                rng, shape, f32, 1.0, 16.0)), (nh,)).astype(f32))
        skip = self.param("D", nn.initializers.ones, (nh,)).astype(f32)
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
        if cache is None:
            ahead = jnp.pad(taken, [(0, 0), (taps - 1, 0), (0, 0)])
            conv = sum(filt[j] * ahead[:, j:j + s] for j in range(taps))
            # the inputs at a - taps + 1 .. a - 1 of each stream
            at = jnp.sum(valid, axis=1)[:, None] + jnp.arange(taps - 1)
            held = jnp.take_along_axis(ahead, at[..., None], axis=1)
        elif s != 1:
            raise NotImplementedError(
                f"the state-space mixer decodes one new position a "
                f"stream, not {s}")
        else:
            state, held = cache
            ahead = jnp.concatenate([held, taken.astype(held.dtype)],
                                    axis=1)
            conv = jnp.einsum("jc,bjc->bc", filt, ahead)[:, None]
            held = jnp.where(valid[..., None], ahead[:, 1:], held)
        conv = jax.nn.silu(conv + bias).astype(u.dtype)
        x, keys, reads = jnp.split(conv, [d, d + g * n], axis=-1)
        x = x.reshape(b, s, nh, p)
        keys, reads = (v.reshape(b, s, g, n) for v in (keys, reads))
        if cache is None:
            y, state = ssd_pallas.ssd_scan(x, dt, a, keys, reads, skip,
                                           valid, cfg.ssm_chunk)
        else:
            y, state = ssd_pallas.ssd_step(
                state, x[:, 0], dt[:, 0], a, keys[:, 0], reads[:, 0],
                skip, valid[:, 0])
            y = y[:, None]
        gated = y.reshape(b, s, g, d // g).astype(f32) * jax.nn.silu(
            z.astype(f32)).reshape(b, s, g, d // g)
        gated = gated * jax.lax.rsqrt(jnp.mean(
            gated * gated, axis=-1, keepdims=True) + cfg.lfm_norm_eps)
        gain = self.param("norm", gain_init(cfg.lfm_norm_gain_std), (d,))
        out = Linear(cfg.lfm_hidden, name="out_proj")(
            (gated.reshape(b, s, d) * gain).astype(u.dtype))
        if cfg.mup_ssm_out != 1.0:
            out = scaled(out, cfg.mup_ssm_out)
        return out, (state, held)


def mixer_both_forms(cfg: ModelConfig, params, x, split: int,
                     state_dtype=jnp.float32):
    """One state-space mixer on ``x [B, S, D]`` in both forms: the
    sequence form over all positions; and the sequence form over the
    first ``split``, then the decode form one position at a time over
    the rest through the cache it left, the state carried in
    ``state_dtype``. Returns the two outputs at the positions from
    ``split`` on ``[B, S - split, D]`` (decode form first) and the two
    states after the last position."""
    mixer = Mamba2Mixer(cfg)
    b, s, _ = x.shape
    valid = jnp.ones((b, s), bool)
    seq, (whole, _) = mixer.apply({"params": params}, x, valid)
    _, (state, held) = mixer.apply({"params": params}, x[:, :split],
                                   valid[:, :split])

    def step(cache, x_t):
        out, (state, held) = mixer.apply(
            {"params": params}, x_t[:, None], valid[:, :1], cache)
        return (state.astype(state_dtype), held), out[:, 0]

    (state, _), dec = jax.lax.scan(
        step, (state.astype(state_dtype), held),
        jnp.moveaxis(x[:, split:], 1, 0))
    return jnp.moveaxis(dec, 0, 1), seq[:, split:], state, whole


def rotary(x, theta: float, pos=None):
    """Rotary embedding over the whole head, rotate-half pairing;
    ``x [B, S, H, D]`` at positions 0..S-1, or at ``pos [B, S]``."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if pos is None:
        ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
        ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    else:
        ang = pos.astype(jnp.float32)[..., None] * inv
        ang = jnp.concatenate([ang, ang], axis=-1)[:, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return (x32 * jnp.cos(ang) + rot * jnp.sin(ang)).astype(x.dtype)


def head_dim(cfg: ModelConfig) -> int:
    """A grouped-query head's size: the preset's own, or ``hidden /
    heads``."""
    return cfg.lfm_head_dim or cfg.lfm_hidden // cfg.lfm_heads


def attends_in_kernels(cfg: ModelConfig) -> bool:
    """Whether grouped-query attention runs as the kernels of
    ``ops/attn_pallas.py`` (the sequence past one block, and the decode
    form): on a TPU, with heads of whole lane tiles."""
    return on_tpu() and attn_pallas.fits(head_dim(cfg))


def reach_mask(i0: int, sq: int, j0: int, sk: int, window: int):
    """``[sq, sk]``: which of the keys ``j0 .. j0 + sk`` each of the
    queries ``i0 .. i0 + sq`` attends to: ``j <= i``, and ``i - window <
    j`` where there is a window."""
    if i0 == j0 == 0 and sq == sk and not window:
        # One block without a window: the mask as LFM2's trained layers
        # have always built it, so their lowered step does not move.
        return jnp.tril(jnp.ones((sq, sk), bool))
    i = i0 + jnp.arange(sq)[:, None]
    j = j0 + jnp.arange(sk)[None, :]
    seen = j <= i
    return seen & (j > i - window) if window else seen


def ring_positions(pos, rows: int):
    """``[B, rows]``: the position each slot of a cache of ``rows`` rows
    holds once position ``pos [B]`` is written (row p lives in slot ``p
    mod rows``): the newest ``p <= pos`` of the slot's class, negative
    where the stream has not reached the slot. A cache that never wraps
    (``rows`` > every position) holds position s in slot s."""
    slot = jnp.arange(rows)[None, :]
    return pos[:, None] - (pos[:, None] - slot) % rows


def cached_attend(q, keys, values, pos, window: int):
    """The decode form's plain mixing, and the kernel's oracle: one
    query a stream ``q [B, kv, rep, hd]`` at position ``pos [B]``
    against the slots of ``keys, values [B, R, kv, hd]`` whose position
    it can reach; ``[B, kv, rep, hd]``."""
    held = ring_positions(pos, keys.shape[1])
    seen = held >= 0
    if window:
        seen &= pos[:, None] - held < window
    scores = jnp.einsum("bgrd,bkgd->bgrk", q, keys,
                        preferred_element_type=jnp.float32)
    scores = jnp.where(seen[:, None, None, :],
                       scores * (q.shape[-1] ** -0.5), -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bgrk,bkgd->bgrd", probs, values)


SPARSE = "sparse_attention"     # queries read a selection of blocks
LINEAR = "linear_attention"     # a constant-decay state, no rows
_OUT = float("-inf")
_FORCED = 1e30


def selected_blocks(cfg: ModelConfig) -> int:
    """Blocks a query past ``sparse_dense_len`` reads at most: the
    first ones, those that hold its last ``sparse_window`` rows, and
    the ``sparse_topk`` best of the rest."""
    return (cfg.sparse_init_blocks + cfg.sparse_window // cfg.sparse_block
            + cfg.sparse_topk)


def select_list_len(cfg: ModelConfig) -> int:
    """Entries of the decode form's index list, which holds the blocks
    BEFORE the local window (that is read as one run): what a query
    under the selection reads of them, or every such block of a sequence
    of ``sparse_dense_len`` rows, whole steps of the kernel."""
    local = cfg.sparse_window // cfg.sparse_block
    n = max(selected_blocks(cfg),
            -(-cfg.sparse_dense_len // cfg.sparse_block)) - local
    per = attn_pallas.SELECT_PER_STEP
    return -(-n // per) * per


def pooled_rows(cfg: ModelConfig, rows: int) -> int:
    """Pooled keys a cache of ``rows`` rows holds: its whole windows of
    ``sparse_kernel`` rows every ``sparse_stride``, up to a multiple of
    8."""
    n = max((rows - cfg.sparse_kernel) // cfg.sparse_stride + 1, 1)
    return -(-n // 8) * 8


def pool_keys(cfg: ModelConfig, k):
    """``Kc_j = mean(k[stride j : stride j + kernel])`` over the whole
    windows of ``k [B, S, kv, hd]``: ``[B, n, kv, hd]`` (n at least 1:
    zeros where the sequence holds no whole window), summed in
    float32."""
    s = k.shape[1]
    n = (s - cfg.sparse_kernel) // cfg.sparse_stride + 1
    if n < 1:
        return jnp.zeros((k.shape[0], 1) + k.shape[2:], k.dtype)
    rows = (np.arange(n)[:, None] * cfg.sparse_stride
            + np.arange(cfg.sparse_kernel)[None, :])
    return jnp.mean(k[:, rows].astype(jnp.float32), axis=2).astype(k.dtype)


def block_scores(cfg: ModelConfig, q, pooled, t, dense, blocks: int):
    """What the selection ranks, ``[B, kv, Q, blocks]`` float32: for
    the queries ``q [B, Q, kv, rep, hd]`` at rows ``t [B, Q]`` against
    the pooled keys ``pooled [B, n, kv, hd]``: per head a softmax over
    the windows whole inside ``0 .. t``, summed over the key/value
    head's ``rep`` heads, a block the maximum over the windows that
    overlap it; a block every query reads (the first ones and those of
    the local window; all in reach where the query's sequence is
    ``dense``, ``[B, Q]`` bool) scores ``_FORCED`` and one past the
    query's own ``-inf``."""
    kernel, stride, block = (cfg.sparse_kernel, cfg.sparse_stride,
                             cfg.sparse_block)
    n = pooled.shape[1]
    logits = jnp.einsum("bqgrd,bjgd->bgrqj", q, pooled,
                        preferred_element_type=jnp.float32)
    logits = logits * (q.shape[-1] ** -0.5)
    whole = (np.arange(n) * stride + kernel - 1)[None, None, :] \
        <= t[:, :, None]                                   # [B, Q, n]
    at = whole[:, None, None]
    probs = jax.nn.softmax(jnp.where(at, logits, -1e30), axis=-1)
    score = jnp.sum(jnp.where(at, probs, 0.0), axis=2)     # [B, g, Q, n]
    score = jnp.where(whole[:, None], score, _OUT)
    # the windows that overlap block b: j_lo(b) .. j_hi(b)
    b0 = np.arange(blocks) * block
    lo = -((kernel - 1 - b0) // stride)
    hi = (b0 + block - 1) // stride
    table = lo[:, None] + np.arange(int(np.max(hi - lo)) + 1)[None, :]
    table = np.where((table >= 0) & (table <= hi[:, None]) & (table < n),
                     table, n)
    padded = jnp.pad(score, [(0, 0)] * 3 + [(0, 1)],
                     constant_values=_OUT)
    by_block = jnp.max(padded[..., table], axis=-1)        # [B, g, Q, NB]
    mine = (t // block)[:, None, :, None]
    b = np.arange(blocks)[None, None, None, :]
    reach = b <= mine
    forced = (b < cfg.sparse_init_blocks) \
        | (b > mine - cfg.sparse_window // block) \
        | dense[:, None, :, None]
    return jnp.where(reach, jnp.where(forced, _FORCED, by_block), _OUT)


def select_mask(cfg: ModelConfig, scores):
    """``[..., blocks]`` bool: the :func:`selected_blocks` best of
    :func:`block_scores` (all forced ones among them, however many),
    and nothing out of reach."""
    k = selected_blocks(cfg)
    if scores.shape[-1] <= k:
        return scores > _OUT
    least = jax.lax.top_k(scores, k)[0][..., -1:]
    return (scores >= least) & (scores > _OUT)


def selected_attend(q, k, v, sel, i0: int, block: int):
    """The plain mixing under a selection, and the kernels' oracle:
    queries ``i0 ..`` ``q [B, sq, kv, rep, hd]`` against the keys ``0
    ..`` ``k, v [B, sk, kv, hd]``, query i reading the keys ``j <= i``
    of the blocks ``sel [B, kv, sq, NB]`` marks."""
    sq, sk = q.shape[1], k.shape[1]
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores * (q.shape[-1] ** -0.5)
    seen = sel[..., np.arange(sk) // block] & reach_mask(i0, sq, 0, sk, 0)
    scores = jnp.where(seen[:, :, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bgrqk,bkgd->bqgrd", probs, v)


def cached_attend_selected(q, keys, values, sel, pos, block: int):
    """The decode form's plain mixing under a selection, and the
    kernel's oracle: one query a stream ``q [B, kv, rep, hd]`` at row
    ``pos [B]`` against the rows ``<= pos`` of the blocks ``sel [B, kv,
    NB]`` marks of ``keys, values [B, kv, R, hd]``; zeros where nothing
    is marked (a stream that is not live). The cache is head-major,
    ``[B, kv, R, hd]``."""
    rows = np.arange(keys.shape[2])
    seen = sel[..., rows // block] & (rows[None, None, :]
                                      <= pos[:, None, None])
    scores = jnp.einsum("bgrd,bgkd->bgrk", q, keys,
                        preferred_element_type=jnp.float32)
    scores = jnp.where(seen[:, :, None],
                       scores * (q.shape[-1] ** -0.5), -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bgrk,bgkd->bgrd", probs, values)
    return jnp.where(jnp.any(sel, axis=-1)[..., None, None], out, 0)


def rows_selected(cfg: ModelConfig, pos, xp=jnp):
    """Cache rows a query at row ``pos`` reads in a sparse layer: all
    ``pos + 1`` up to ``sparse_dense_len``, else those ``<= pos`` of
    its :func:`selected_blocks` blocks (fewer where fewer are in
    reach)."""
    block = cfg.sparse_block
    blocks = xp.minimum(pos // block + 1, selected_blocks(cfg))
    chosen = (blocks - 1) * block + pos % block + 1
    return xp.where(pos + 1 <= cfg.sparse_dense_len, pos + 1, chosen)


class Attention(nn.Module):
    """Causal grouped-query attention: RMSNorm over each head of q and
    of k (``lfm_qk_norm``), then the rotation where the layer's
    ``kind`` has one (``lfm_rope_kinds``), every key/value head shared
    by ``heads / kv_heads`` query heads; a "sliding_attention" layer
    sees the last
    ``lfm_window`` keys, its own among them; with ``lfm_attn_gate`` the
    heads' output is multiplied by the sigmoid of a projection of the
    layer's input before ``o``.

    ``__call__(h [B, S, D])`` is the SEQUENCE form (training, prefill;
    positions 0..S-1) and returns the output and the keys (normed and
    rotated) and values it computed, ``(k, v)``, ``[B, S, kv_heads,
    head]`` each: the rows of the layer's cache. It works in blocks of ``block`` queries, each
    against the keys its positions can reach and no others, so no
    ``[S, S]`` array exists past one block and a sliding layer's work is
    bounded by S x (window + block). With ``cache``, the pair of
    arrays ``(keys, values)`` ``[B, R, kv_heads, head]`` each (two
    arrays, so that a step's products read them as they lie), it is the
    DECODE form, one new position a stream: the new row is written in
    slot ``pos mod R`` (a ring of R = ``lfm_window``
    rows for a sliding layer; R above every position for a global one,
    which never wraps) and the query attends to the slots whose
    position it can reach (``live [B, 1]``: the streams that write;
    the kernel ``gqa_attn_decode`` where :func:`attends_in_kernels`
    holds, which fetches a stream's rows once, only the row tiles in
    reach and none for a stream that is not live, whose output is then
    zeros; elsewhere, and as its oracle, :func:`cached_attend`); it
    returns the output and the cache. Keys are stored rotated by their
    ABSOLUTE position, so a ring's order means nothing to the
    softmax."""

    cfg: ModelConfig
    kind: str = "full_attention"
    block: int = 512

    @nn.compact
    def __call__(self, h, pos=None, cache=None, live=None):
        cfg = self.cfg
        b, s, d = h.shape
        nh, nkv = cfg.lfm_heads, cfg.lfm_kv_heads
        hd, rep = head_dim(cfg), nh // nkv
        window = cfg.lfm_window if self.kind == "sliding_attention" else 0
        scope = "gqa_attn_" + ("window" if window else "global")
        if self.kind == SPARSE:
            scope = SPARSE
        if cfg.mup_attn_in != 1.0:
            h = scaled(h, cfg.mup_attn_in)
        q = Linear(nh * hd, name="q")(h).reshape(b, s, nh, hd)
        k = Linear(nkv * hd, name="k")(h).reshape(b, s, nkv, hd)
        if cfg.mup_key != 1.0:            # on the keys, before rotation
            k = scaled(k, cfg.mup_key)
        v = Linear(nkv * hd, name="v")(h).reshape(b, s, nkv, hd)
        std = cfg.lfm_norm_gain_std

        def placed(x, name):
            """A head's norm, then the layer kind's rotation."""
            if cfg.lfm_qk_norm:
                x = RMSNorm(cfg.lfm_norm_eps, std, name=name)(x)
            if self.kind not in cfg.lfm_rope_kinds:
                return x
            return rotary(x, cfg.lfm_rope_theta,
                          None if cache is None else pos)

        q, k = placed(q, "q_norm"), placed(k, "k_norm")
        q = q.reshape(b, s, nkv, rep, hd)

        def attend(q, k, v, i0: int, j0: int):
            """Queries ``i0 ..`` ``q [B, sq, kv, rep, hd]`` against the
            keys ``j0 ..`` ``k, v [B, sk, kv, hd]``."""
            scores = jnp.einsum("bqgrd,bkgd->bgrqk", q, k,
                                preferred_element_type=jnp.float32)
            scores = scores * (hd ** -0.5)
            scores = jnp.where(
                reach_mask(i0, q.shape[1], j0, k.shape[1], window),
                scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(h.dtype)
            return jnp.einsum("bgrqk,bkgd->bqgrd", probs, v)

        def blockwise(q, k, v):
            """:func:`attend` a block of queries at a time, each
            against the keys in its reach: the plain form of the
            sequence past one block, forward and (differentiated)
            backward, and the kernels' oracle."""
            outs = []
            for i0 in range(0, s, self.block):
                i1 = min(i0 + self.block, s)
                j0 = max(0, i0 - window + 1) if window else 0
                outs.append(attend(q[:, i0:i1], k[:, j0:i1],
                                   v[:, j0:i1], i0, j0))
            return jnp.concatenate(outs, axis=1)

        if cache is None and self.kind == SPARSE:
            out, kept = self.select_sequence(q, k, v, live)
        elif cache is None:
            kept = (k, v)
            with jax.named_scope(scope):
                if s <= self.block:
                    out = attend(q, k, v, 0, 0)
                elif attends_in_kernels(cfg):
                    out = attn_pallas.gqa_attention(q, k, v, window)
                else:
                    out = blockwise(q, k, v)
        elif s != 1:
            raise NotImplementedError(
                f"grouped-query attention decodes one new position a "
                f"stream, not {s}: several at once (a loop that verifies "
                f"its drafts) is latent attention's alone")
        elif self.kind == SPARSE:
            out, kept = self.select_step(
                q[:, 0], k[:, 0], v[:, 0], cache, pos[:, 0], live[:, 0])
        else:
            with jax.named_scope(scope):
                at, r = pos[:, 0], cache[0].shape[1]
                # A stream that has finished (not ``live``) writes
                # nothing: in a ring its slot holds a row it still owns.
                at_slot = (jnp.arange(b), at % r)
                with jax.named_scope("cache_update"):
                    keys, values = (c.at[at_slot].set(jnp.where(
                        live[:, :, None], row[:, 0].astype(c.dtype),
                        c[at_slot])) for c, row in zip(cache, (k, v)))
                if attends_in_kernels(cfg):
                    out = attn_pallas.gqa_decode(
                        q[:, 0], keys, values, at, live[:, 0], window)
                else:
                    out = cached_attend(q[:, 0], keys, values, at, window)
            kept = (keys, values)
        out = out.reshape(b, s, nh * hd)
        if cfg.lfm_attn_gate:
            gate = Linear(nh * hd, name="gate")(h)
            out = (out.astype(jnp.float32) * jax.nn.sigmoid(
                gate.astype(jnp.float32))).astype(h.dtype)
        self.sow("intermediates", "gated", out)
        with jax.named_scope("attn_out"):
            out = Linear(d, name="o")(out)
        if cfg.mup_attn_out != 1.0:
            out = scaled(out, cfg.mup_attn_out)
        return out, kept

    def select_sequence(self, q, k, v, valid):
        """The sequence form of a "sparse_attention" layer: the pooled
        keys of the whole windows inside each stream's ``valid``
        positions (zeros past them), every query's selection in blocks
        of ``block`` queries (no array over all queries and all
        windows), and the mixing under it (the kernel
        ``gqa_attn_select_fwd`` past one block where
        :func:`attends_in_kernels` holds). Returns the output ``[B, S,
        kv, rep, hd]`` and the cache's rows ``(k, v, pooled)``, keys
        and values HEAD-MAJOR ``[B, kv, S, hd]`` as the cache holds
        them (a head's block of rows one contiguous piece)."""
        cfg = self.cfg
        b, s = q.shape[:2]
        size, step = cfg.sparse_block, self.block
        lens = jnp.sum(valid, axis=1)
        blocks = -(-s // size)
        with jax.named_scope("sparse_select"):
            pooled = pool_keys(cfg, k)
            n = pooled.shape[1]
            whole = (np.arange(n) * cfg.sparse_stride + cfg.sparse_kernel
                     )[None, :] <= lens[:, None]
            pooled = jnp.where(whole[..., None, None], pooled, 0)
            dense = jnp.broadcast_to(
                (lens <= cfg.sparse_dense_len)[:, None], (b, step))
            pad = -s % step
            tiles = jnp.moveaxis(
                jnp.pad(q, [(0, 0), (0, pad)] + [(0, 0)] * 3).reshape(
                    (b, -1, step) + q.shape[2:]), 1, 0)

            def chosen(at):
                tile, i0 = at
                t = jnp.broadcast_to(i0 + jnp.arange(step)[None, :],
                                     (b, step))
                return select_mask(cfg, block_scores(
                    cfg, tile, pooled, t, dense, blocks))

            sel = jax.lax.map(chosen, (tiles, jnp.arange(
                0, s + pad, step)))                  # [T, B, kv, step, NB]
            sel = jnp.moveaxis(sel, 0, 2).reshape(
                sel.shape[1:3] + (s + pad, blocks))[:, :, :s]
        self.sow("intermediates", "selected", sel)
        with jax.named_scope(SPARSE):
            if s > step and attends_in_kernels(cfg):
                out = attn_pallas.gqa_select_attention(q, k, v, sel, size)
            else:
                out = jnp.concatenate([
                    selected_attend(q[:, i0:i0 + step], k[:, :i0 + step],
                                    v[:, :i0 + step], sel[:, :, i0:i0 + step],
                                    i0, size)
                    for i0 in range(0, s, step)], axis=1)
        return out, (k.swapaxes(1, 2), v.swapaxes(1, 2), pooled)

    def select_step(self, q, k, v, cache, at, live):
        """The decode form of a "sparse_attention" layer: a live stream
        writes its new row ``k, v [B, kv, hd]`` at ``at [B]`` of the
        cache ``(keys, values [B, kv, R, hd], pooled)``, and where that
        row ends a pooling window, the window's pooled key; the query
        ``q [B, kv, rep, hd]`` ranks the blocks through the pooled keys
        and reads the selected ones (the kernel
        ``gqa_attn_select_decode``, which fetches those blocks only,
        where :func:`attends_in_kernels` holds). Returns the output
        ``[B, kv, rep, hd]`` (zeros for a stream that is not ``live``)
        and the cache."""
        cfg = self.cfg
        keys, values, pooled = cache
        b, rows = keys.shape[0], keys.shape[2]
        kernel, stride, size = (cfg.sparse_kernel, cfg.sparse_stride,
                                cfg.sparse_block)
        nkv, hd = keys.shape[1], keys.shape[3]
        with jax.named_scope("cache_update"):
            # A (stream, head) a row of a [B x kv, R, hd] view, so that
            # the write is the scatter a full cache's row write is:
            # indexed [B, :, at] XLA kept the cache rows-major in the
            # loop and copied it whole for the kernel every step.
            line = jnp.arange(b * nkv)
            slot = (line, jnp.repeat(at, nkv))
            alive = jnp.repeat(live, nkv)[:, None]
            keys, values = (
                c.reshape(b * nkv, rows, hd).at[slot].set(jnp.where(
                    alive, row.reshape(b * nkv, hd).astype(c.dtype),
                    c.reshape(b * nkv, rows, hd)[slot]))
                for c, row in zip((keys, values), (k, v)))
            # the window's rows by (stream, row), as the rows' own
            # write is indexed: a slice a stream made XLA copy the
            # whole cache into another layout every step
            last = jnp.maximum(at - kernel + 1, 0)[:, None] \
                + jnp.arange(kernel)[None, :]
            window = keys[line[:, None], jnp.repeat(last, nkv, axis=0)]
            new = jnp.mean(window.astype(jnp.float32), axis=1
                           ).astype(pooled.dtype).reshape(b, nkv, hd)
            keys, values = (c.reshape(b, nkv, rows, hd)
                            for c in (keys, values))
            ends = live & (at >= kernel - 1) \
                & ((at + 1 - kernel) % stride == 0)
            slot = (jnp.arange(b), jnp.clip((at + 1 - kernel) // stride, 0,
                                            pooled.shape[1] - 1))
            pooled = pooled.at[slot].set(jnp.where(
                ends[:, None, None], new, pooled[slot]))
        blocks = -(-rows // size)
        with jax.named_scope("sparse_select"):
            sel = select_mask(cfg, block_scores(
                cfg, q[:, None], pooled, at[:, None],
                (at + 1 <= cfg.sparse_dense_len)[:, None], blocks))[:, :, 0]
            sel = sel & live[:, None, None]
            # the local window's blocks are read as one run of rows
            local = cfg.sparse_window // size
            first = jnp.maximum(at // size - local + 1, 0)
            if attends_in_kernels(cfg):
                idx, count = attn_pallas.select_list(
                    sel, select_list_len(cfg), first)
        self.sow("intermediates", "selected", sel)
        with jax.named_scope(SPARSE):
            if attends_in_kernels(cfg):
                out = attn_pallas.gqa_select_decode(
                    q, keys, values, idx, count, at, first * size, live,
                    size, cfg.sparse_window)
            else:
                out = cached_attend_selected(q, keys, values, sel, at, size)
        return out, (keys, values, pooled)


def decay_slopes(cfg: ModelConfig, index: int):
    """``[heads]``: what a "linear_attention" layer's state loses a
    position, ``lambda_h = exp(-slope_h)``: ``s_h (1 - l / (depth - 1) +
    1e-5)`` with ``s_h = 2^(-8 h / heads)``, h = 1 .. heads, and l the
    layer's PUBLISHED index (Lightning Attention-2)."""
    h = np.arange(1, cfg.lin_heads + 1, dtype=np.float64)
    layer = 1.0 - index / max(cfg.lin_depth - 1, 1) + 1e-5
    return (2.0 ** (-8.0 * h / cfg.lin_heads) * layer).astype(np.float32)


class LinearAttention(nn.Module):
    """Linear attention with one constant decay a head
    (``lightning-attn``): ``q, k = RoPE(RMSNorm_head(W_q x)),
    RoPE(RMSNorm_head(W_k x))``, ``v = W_v x``; a head's state ``S_t =
    lambda_h S_{t-1} + k_t^T v_t`` (float32, zero before position 0),
    ``o_t = q_t S_t / sqrt(head)``; ``W_o (RMSNorm(o_t) *
    sigmoid(W_g x))``, the norm over all heads' channels with one gain.
    The recurrence is ``ops/ssd_pallas.py``'s with ``x = v, B = k, C =
    q, dt = 1`` (0 at padding), ``A = -slope_h``, no skip and as many
    groups as heads; the division by ``sqrt(head)`` is taken on the
    float32 output, where it costs no rounding of ``q``.

    ``__call__(h [B, S, D], valid [B, S])`` is the SEQUENCE form and
    returns the output and the cache: the state after each stream's
    last valid position ``([B, heads, head, head] float32,)``. With
    ``cache`` it is the DECODE form, one new position a stream at ``pos
    [B, 1]``; a stream that is not live leaves its state as it is."""

    cfg: ModelConfig
    index: int = 0

    @nn.compact
    def __call__(self, h, valid, pos=None, cache=None):
        cfg = self.cfg
        b, s, d = h.shape
        nh, hd = cfg.lin_heads, cfg.lin_head_dim
        f32 = jnp.float32
        std = cfg.lfm_norm_gain_std
        with jax.named_scope(LINEAR):
            q, k, v = (Linear(nh * hd, name=n)(h).reshape(b, s, nh, hd)
                       for n in ("q", "k", "v"))
            at = None if cache is None else pos
            if cfg.lfm_qk_norm:
                q = RMSNorm(cfg.lfm_norm_eps, std, name="q_norm")(q)
                k = RMSNorm(cfg.lfm_norm_eps, std, name="k_norm")(k)
            q = rotary(q, cfg.lin_rope_theta, at)
            k = rotary(k, cfg.lin_rope_theta, at)
            a = -jnp.asarray(decay_slopes(cfg, self.index))
            ones = jnp.ones((b, s, nh), f32)
            if cache is None:
                y, state = ssd_pallas.ssd_scan(
                    v, ones, a, k, q, None, valid, cfg.ssm_chunk)
            elif s != 1:
                raise NotImplementedError(
                    f"linear attention decodes one new position a "
                    f"stream, not {s}")
            else:
                y, state = ssd_pallas.ssd_step(
                    cache[0], v[:, 0], ones[:, 0], a, k[:, 0], q[:, 0],
                    None, valid[:, 0])
                y = y[:, None]
            o = y.reshape(b, s, nh * hd).astype(f32) * (hd ** -0.5)
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                                  + cfg.lfm_norm_eps)
            gain = self.param("o_norm", gain_init(std), (nh * hd,))
            gate = Linear(nh * hd, name="gate")(h)
            out = (o * gain * jax.nn.sigmoid(gate.astype(f32))
                   ).astype(h.dtype)
        self.sow("intermediates", "gated", out)
        with jax.named_scope("attn_out"):
            out = Linear(d, name="o")(out)
        return out, (state,)


def both_forms(cfg: ModelConfig, kind: str, params, x, at, rows: int,
               block: int = 512):
    """One grouped-query attention layer of ``kind`` on ``x [B, S, D]``
    in both forms: the sequence form over all positions, then the
    decode form at each position of ``at`` (an index array)
    against a cache of ``rows`` rows that holds what the sequence form
    gave for the positions before it (slot ``p mod rows``: a ring where
    ``rows`` < S), every (row, position) a stream of its own. Returns
    the two outputs at those positions, ``[B, len(at), D]`` each."""
    layer = Attention(cfg, kind, block)
    b = x.shape[0]
    seq, kv = layer.apply({"params": params}, x)
    at = jnp.asarray(at)
    held = ring_positions(at - 1, rows)
    # [B, n, rows, heads, hd]: a stream a (row, position)
    cache = tuple(
        jnp.where((held >= 0)[None, :, :, None, None],
                  c[:, jnp.maximum(held, 0)], 0).reshape(
                      (b * len(at), rows) + c.shape[2:]) for c in kv)
    dec, _ = layer.apply(
        {"params": params}, x[:, at].reshape(b * len(at), 1, -1),
        jnp.tile(at, b)[:, None], cache,
        jnp.ones((b * len(at), 1), bool))
    return dec.reshape(b, len(at), -1), seq[:, at]


class SwiGLU(nn.Module):
    """``w2 (silu(w1 x) * (w3 x))``; ``mup`` (a family's
    ``mlp_multipliers``, where not 1): the gate's argument and the
    output each times one."""

    width: int
    mup: tuple = (1.0, 1.0)

    @nn.compact
    def __call__(self, x):
        gate = Linear(self.width, name="w1")(x)
        up = Linear(self.width, name="w3")(x)
        gate = gate.astype(jnp.float32)
        if self.mup[0] != 1.0:
            gate = gate * self.mup[0]
        act = (jax.nn.silu(gate) * up.astype(jnp.float32)).astype(x.dtype)
        out = Linear(x.shape[-1], name="w2")(act)
        return out if self.mup[1] == 1.0 else scaled(out, self.mup[1])


class SparseExperts(nn.Module):
    """The routed feed-forward: this chip's ``experts_held`` experts of
    the router's ``lfm_experts`` (``ops/moe.expert_layer``), chosen by
    the preset's selection rule, plus the shared expert where the
    family has one (every chip computes it alike). The selection bias
    (``moe_select_bias``) is a buffer, not a parameter: it lives in the
    ``buffers`` collection, held at its seeded value. In a trained
    model that bias evens the experts' loads; a seeded one can only
    uneven them, so it is seeded small (std ``BIAS_STD``: enough to
    move the chosen set of a quarter of the positions, so a build that
    drops it is seen; at 0.05 single experts drew three times the mean
    load and a step's time followed the seed).

    A family may route from another tensor than the one its experts
    read (``moe_route_pre_attn``): :meth:`route` scores and chooses,
    and ``__call__`` takes that ``routing`` in place of its own."""

    cfg: ModelConfig

    def setup(self):
        cfg = self.cfg
        d, g, f = cfg.lfm_hidden, cfg.experts_held, cfg.lfm_expert_dim
        self.router = self.param("router", _INIT, (d, cfg.lfm_experts))
        self.w13 = self.param("w13", _INIT, (g, d, 2 * f))
        self.w2 = self.param("w2", _INIT, (g, f, d))
        if cfg.moe_select_bias:
            self.expert_bias = self.variable(
                "buffers", "expert_bias",
                lambda: BIAS_STD * jax.random.normal(
                    self.make_rng("params"), (cfg.lfm_experts,),
                    jnp.float32))
        if cfg.moe_shared_experts:
            self.shared = SwiGLU(cfg.moe_shared_experts * f)

    def route(self, x) -> moe.Routing:
        """The routing of the positions of ``x [B, S, D]`` or ``[N,
        D]``, ``[N, .]``."""
        cfg = self.cfg
        routing = moe.route(
            x.reshape(-1, x.shape[-1]), self.router,
            self.expert_bias.value if cfg.moe_select_bias else None,
            cfg.lfm_top_k, cfg.moe_groups, cfg.moe_groups_kept,
            cfg.moe_routed_scale, cfg.moe_score_func)
        self.sow("intermediates", "scores", routing.scores)
        self.sow("intermediates", "experts", routing.experts)
        self.sow("intermediates", "weights", routing.weights)
        return routing

    def __call__(self, x, valid, routing=None):
        cfg = self.cfg
        b, s, d = x.shape
        flat = x.reshape(b * s, d)
        if routing is None:
            routing = self.route(flat)
        out, counters = moe.expert_layer(
            flat, valid.reshape(-1), routing, self.w13, self.w2,
            offset=cfg.expert_offset, rows_bound=cfg.moe_rows_bound,
            impl=cfg.moe_impl, act=cfg.moe_expert_act)
        out = out.reshape(b, s, d)
        if cfg.moe_groups > 1:
            # Groups a valid position's chosen experts lie in: never
            # more than ``moe_groups_kept``.
            group = routing.experts // (cfg.lfm_experts // cfg.moe_groups)
            used = jnp.any(group[:, :, None] == jnp.arange(
                cfg.moe_groups)[None, None, :], axis=1)
            counters["groups_used"] = jnp.sum(
                used * valid.reshape(-1, 1))
        if cfg.moe_shared_experts:
            with jax.named_scope("moe_shared"):
                out = out + self.shared(x)
        return out, counters


ATTENTION_KINDS = ("full_attention", "sliding_attention")
# The hybrid layer: a state-space mixer beside attention that sees all.
HYBRID = "ssm_attention"


class DecoderLayer(nn.Module):
    """``h + operator(norm(h))``, then ``h + ffn(norm(h))``, each
    sub-layer's output through a norm of its own before it is added
    where the family has sandwich norms (``lfm_post_norms``); where
    the family's router reads the layer's input (``moe_route_pre_attn``)
    the experts are chosen from ``h`` as it arrives, before its norm
    and its operator, and the feed-forward applies that choice to
    ``norm(h + operator(..))``; with
    ``hc_streams`` > 1 the residual ``h [B, S, n, D]`` is n streams and
    each of the two sub-layers reads and writes them through its own
    hyper-connection (``models/mhc.py``). Returns
    the new ``h``, the expert block's counters (None for a dense
    feed-forward) and the layer's cache: what this call's positions
    would put there (the sequence form: latent rows, or an attention
    layer's keys and values), or the ``cache`` handed in with the
    new rows of each stream written at ``pos`` (the decode form, which
    latent and grouped-query attention have). A kind without a cache
    returns None. A HYBRID layer's operator is two, side by side on the
    one normed input and summed: the state-space mixer and attention
    that sees all; its cache is attention's keys and values, then the
    mixer's state and convolution inputs, four arrays."""

    cfg: ModelConfig
    kind: str      # "conv" | "latent_attention" | ATTENTION_KINDS | HYBRID
    #              # | SPARSE | LINEAR
    sparse: bool
    index: int = 0          # the layer's PUBLISHED index (LINEAR's decay)

    def residual(self, name: str, h, f):
        """``h`` after the sub-layer ``f`` (``x -> (y, extra)``, its
        pre-norm inside), and ``extra``; the sub-layer's output times
        the family's depth scaling where it has one (``mup_residual``)."""
        if self.cfg.hc_streams == 1:
            y, extra = f(h)
            if self.cfg.mup_residual != 1.0:
                y = scaled(y, self.cfg.mup_residual)
            return h + y, extra
        hyper = mhc.HyperConnection(self.cfg, name=name)
        if mhc_pallas.in_kernels(*h.shape[-2:]):
            with jax.named_scope("mhc"):
                coef, x = hyper(h, kernels=True)
            y, extra = f(x)
            with jax.named_scope("mhc"):
                return mhc.kernel_write(h, y, coef), extra
        with jax.named_scope("mhc"):
            h_pre, h_post, h_res = hyper(h)
            x = mhc.read(h_pre, h)
        y, extra = f(x)
        with jax.named_scope("mhc"):
            return mhc.write(h_res, h_post, h, y), extra

    @nn.compact
    def __call__(self, h, valid, pos=None, cache=None):
        cfg = self.cfg

        def norm(name):
            return RMSNorm(cfg.lfm_norm_eps, cfg.lfm_norm_gain_std,
                           name=name)

        def after(name, out):
            y, extra = out
            return (norm(name)(y) if cfg.lfm_post_norms else y), extra

        def operator(x):
            x = norm("op_norm")(x)
            if self.kind == "latent_attention":
                with jax.named_scope("latent_attention"):
                    return LatentAttention(cfg, name="attn")(x, pos, cache)
            if self.kind in ATTENTION_KINDS:
                return after("op_post_norm", Attention(
                    cfg, self.kind, name="attn")(x, pos, cache, valid))
            if self.kind == SPARSE:
                return Attention(cfg, SPARSE, name="sparse")(
                    x, pos, cache, valid)
            if self.kind == LINEAR:
                return LinearAttention(cfg, self.index, name="lin")(
                    x, valid, pos, cache)
            if self.kind == HYBRID:
                with jax.named_scope("ssm_mixer"):
                    mixed, held = Mamba2Mixer(cfg, name="mixer")(
                        x, valid, None if cache is None else cache[2:])
                attended, rows = Attention(cfg, self.kind, name="attn")(
                    x, pos, None if cache is None else cache[:2], valid)
                self.sow("intermediates", "branch_mixer", mixed)
                self.sow("intermediates", "branch_attn", attended)
                return mixed + attended, tuple(rows) + tuple(held)
            if cache is not None:
                raise ValueError(f"layer type {self.kind!r} has no cache")
            if self.kind == "conv":
                return ShortConv(cfg, name="conv")(x), None
            raise ValueError(f"layer type {self.kind!r}")

        def feed_forward(x):
            x = norm("ffn_norm")(x)
            if self.sparse:
                return after("ffn_post_norm", experts(x, valid, routing))
            out = SwiGLU(cfg.lfm_ffn_dim, tuple(cfg.mup_mlp), name="ffn")(x)
            if self.kind == HYBRID:
                self.sow("intermediates", "branch_mlp", out)
            return after("ffn_post_norm", (out, None))

        routing = None
        if self.sparse:
            experts = SparseExperts(cfg, name="moe")
            if cfg.moe_route_pre_attn:
                with jax.named_scope("moe_route_pre_attn"):
                    routing = experts.route(h)
        h, cache = self.residual("op_hc", h, operator)
        h, counters = self.residual("ffn_hc", h, feed_forward)
        return h, counters, cache


class DraftModule(nn.Module):
    """A multi-token-prediction module (DeepSeek-V3, arXiv:2412.19437
    section 2.2): at position i, from the model's last hidden state
    ``h_i`` (the sum of its streams, before the last norm) and the
    embedding of the NEXT input ``t_{i+1}``, ``z_i = W_eh [norm_e(emb);
    norm_h(h_i)]`` through one more expert layer of the model's kind
    (own hyper-connections, own cache row at position i). Returns its
    normed output, whose logits under the model's head are the
    distribution of ``t_{i+2}``, the expert block's counters and the
    cache (rows or array, as ``DecoderLayer``)."""

    cfg: ModelConfig

    @nn.compact
    def __call__(self, emb_next, h, valid, pos, cache=None):
        cfg = self.cfg
        z = Linear(cfg.lfm_hidden, name="eh_proj")(jnp.concatenate(
            [RMSNorm(cfg.lfm_norm_eps, name="embed_norm")(emb_next),
             RMSNorm(cfg.lfm_norm_eps, name="hidden_norm")(h)], axis=-1))
        x, counters, cache = DecoderLayer(
            cfg, "latent_attention", True, name="layer")(
                mhc.fan_out(z, cfg.hc_streams), valid, pos, cache)
        x = mhc.contract(x, cfg.hc_streams)
        return RMSNorm(cfg.lfm_norm_eps, name="out_norm")(x), counters, \
            cache


def pack(a_lens, labels, label_lens, s: int):
    """The left-packed layout of a batch: for each of ``s`` positions,
    whether it is audio, the id embedded there (text positions), the
    target id and whether a target is there at all."""
    u_max = labels.shape[1]
    tpos = jnp.arange(s)[None, :] - a_lens[:, None]      # 0 = start
    audio = tpos < 0
    text = (tpos >= 0) & (tpos <= label_lens[:, None])
    shifted = jnp.take_along_axis(
        labels, jnp.clip(tpos - 1, 0, u_max - 1), axis=1)
    ids = jnp.where(text & (tpos > 0), shifted, 0)
    nxt = jnp.take_along_axis(labels, jnp.clip(tpos, 0, u_max - 1), axis=1)
    targets = jnp.where(text & (tpos < label_lens[:, None]), nxt, 0)
    return audio, text, ids, targets


class LFM2ASR(nn.Module):
    cfg: ModelConfig
    max_label_len: int

    def setup(self):
        cfg = self.cfg
        layer_cls = nn.remat(DecoderLayer, policy=remat_policy(cfg))
        self.embed = self.param("embed", _INIT,
                                (cfg.vocab_size, cfg.lfm_hidden))
        self.prefix = Linear(cfg.lfm_hidden)
        self.layers = [
            layer_cls(cfg, kind, i >= cfg.lfm_dense_layers,
                      cfg.lin_layer_index[i] if cfg.lin_layer_index else i,
                      name=f"layer{i}")
            for i, kind in enumerate(cfg.lfm_layer_types)]
        self.out_norm = RMSNorm(cfg.lfm_norm_eps, cfg.lfm_norm_gain_std)
        self.drafts = [DraftModule(cfg, name=f"draft{i}")
                       for i in range(cfg.lm_draft_layers)]
        if not cfg.lm_tied_head:
            self.lm_head = self.param(
                "lm_head", _INIT, (cfg.vocab_size, cfg.lfm_hidden))

    def head(self):
        """The output head ``[V, D]``: the embedding matrix, or the
        family's own."""
        return self.embed if self.cfg.lm_tied_head else self.lm_head

    def enter(self, x):
        """What enters the first layer, from an embedding or a
        projected frame: times sqrt(D) where the family scales its
        embedding (``lfm_embed_scale``)."""
        if self.cfg.mup_embedding != 1.0:
            x = scaled(x, self.cfg.mup_embedding)
        if not self.cfg.lfm_embed_scale:
            return x
        return x * jnp.asarray(self.cfg.lfm_hidden ** 0.5, x.dtype)

    def hidden(self, features, feat_lens, labels, label_lens):
        """The normed final hidden state ``[B, S, D]`` of the packed
        batch, the output head's matrix ``[V, D]``, the batch's layout
        and each expert layer's counters."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        s = seq_positions(cfg, features.shape[1], self.max_label_len)
        with jax.named_scope("embed"):
            x, a_lens = stack_frames(features, feat_lens, cfg.frame_stack)
            audio, text, ids, targets = pack(a_lens, labels, label_lens, s)
            pre = self.prefix(x.astype(dtype))
            pre = jnp.pad(pre, [(0, 0), (0, s - pre.shape[1]), (0, 0)])
            emb = jnp.take(self.embed.astype(dtype), ids, axis=0)
            valid = audio | text
            h = self.enter(jnp.where(audio[..., None], pre,
                                     jnp.where(text[..., None], emb, 0)))
        pos = jnp.broadcast_to(jnp.arange(s)[None, :], valid.shape)
        h = mhc.fan_out(h, cfg.hc_streams)
        counters = []
        for layer in self.layers:
            h, c, _ = layer(h, valid, pos)
            if c is not None:
                counters.append(c)
        h = mhc.contract(h, cfg.hc_streams)
        layout = {"valid": valid, "targets": targets, "a_lens": a_lens}
        return self.out_norm(h), self.head(), layout, counters

    def loss(self, features, feat_lens, labels, label_lens):
        """Per-utterance summed cross-entropy over the ``u + 1`` target
        positions, and the routing counters of the step. The logits are
        computed at the text positions only, against the output head
        (this chip's slice of the vocabulary)."""
        h, head, layout, counters = self.hidden(
            features, feat_lens, labels, label_lens)
        if self.cfg.mup_lm_head != 1.0:      # on the logits
            h = scaled(h, self.cfg.mup_lm_head)
        with jax.named_scope("lm_head"):
            logp, mask = target_logp(h, head, layout, labels, label_lens)
            nll = -jnp.sum(logp * mask, axis=1)
        valid = layout["valid"]
        stats = {"valid_positions": jnp.sum(valid),
                 "padded_positions": valid.size - jnp.sum(valid)}
        if self.cfg.lfm_window:
            stats.update(reach_pairs(jnp.sum(valid, axis=1),
                                     self.cfg.lfm_window))
        if counters:
            stats.update(stack_counters(counters))
        return nll, stats

    def prefill(self, features, feat_lens):
        """The serving path's first half: the audio prefix alone
        (positions ``0 .. a-1`` of each stream) through the layers.
        Returns each layer's rows to cache (latent attention's ``[B, A,
        C]``, grouped-query attention's keys and values, a pair of ``[B,
        A, kv_heads, head]``;
        a draft module's after them), the prefix lengths, the expert
        layers' counters and, with a draft module, its first draft
        ``[B]``: the
        token after the start id. The module's next input at a prefix
        position is the next projected frame, and the start id's
        embedding at the last."""
        cfg = self.cfg
        with jax.named_scope("embed"):
            x, a_lens = stack_frames(features, feat_lens, cfg.frame_stack)
            pre = self.prefix(x.astype(jnp.dtype(cfg.dtype)))
        pos = jnp.broadcast_to(jnp.arange(pre.shape[1])[None, :],
                               pre.shape[:2])
        valid = pos < a_lens[:, None]
        with jax.named_scope("embed"):
            h = self.enter(pre)
        h = mhc.fan_out(h, cfg.hc_streams)
        rows, counters = [], []
        for layer in self.layers:
            h, c, r = layer(h, valid, pos)
            rows.append(r)
            if c is not None:
                counters.append(c)
        draft = None
        for module in self.drafts:
            with jax.named_scope("mtp_draft"):
                ahead = jnp.where(
                    (pos + 1 < a_lens[:, None])[..., None],
                    jnp.pad(pre[:, 1:], [(0, 0), (0, 1), (0, 0)]),
                    self.embed[0].astype(pre.dtype))
                out, c, r = module(ahead, mhc.contract(h, cfg.hc_streams),
                                   valid, pos)
                last = jnp.take_along_axis(
                    out, jnp.maximum(a_lens - 1, 0)[:, None, None], axis=1)
                draft = jnp.argmax(self.logits(last[:, 0]), axis=-1
                                   ).astype(jnp.int32)
            rows.append(r)
            counters.append(c)
        return rows, a_lens, stack_counters(counters), draft

    def logits(self, h):
        """``h [N, D]`` (normed) against the head, float32 (times the
        family's ``lm_head_multiplier`` where not 1)."""
        with jax.named_scope("lm_head"):
            out = jnp.dot(h, self.head().astype(h.dtype).T,
                          preferred_element_type=jnp.float32)
            by = self.cfg.mup_lm_head
            return out if by == 1.0 else out * by

    def step(self, tokens, pos, active, cache):
        """The serving path's second half: one new position a stream
        (the embedding of ``tokens [B]`` at ``pos [B]``) against the
        cache. Returns the logits ``[B, V]`` in float32, the cache with
        the new rows and the expert layers' counters; a stream that is
        not ``active`` is not routed."""
        n = self.cfg.hc_streams
        with jax.named_scope("embed"):
            h = self.enter(jnp.take(
                self.embed.astype(jnp.dtype(self.cfg.dtype)), tokens,
                axis=0)[:, None, :])
        h = mhc.fan_out(h, n)
        new, counters = [], []
        for layer, rows in zip(self.layers, cache):
            h, c, rows = layer(h, active[:, None], pos[:, None], rows)
            new.append(rows)
            if c is not None:
                counters.append(c)
        h = self.out_norm(mhc.contract(h, n))[:, 0]
        return self.logits(h), new, stack_counters(counters)

    def verify(self, tokens, pos, valid, cache):
        """:meth:`step` over ``q`` new positions a stream (``tokens``,
        ``pos``, ``valid`` are ``[B, q]``, the positions consecutive),
        each attending to the cache and to the new positions before it,
        through any number of residual streams. Returns the logits
        ``[B, q, V]``, the last hidden state a draft module reads
        ``[B, q, D]`` (the streams' sum, before the last norm), the
        cache with the new rows and the expert layers' counters."""
        cfg = self.cfg
        with jax.named_scope("embed"):
            h = self.enter(jnp.take(
                self.embed.astype(jnp.dtype(cfg.dtype)), tokens, axis=0))
        h = mhc.fan_out(h, cfg.hc_streams)
        new, counters = [], []
        for layer, rows in zip(self.layers, cache):
            h, c, rows = layer(h, valid, pos, rows)
            new.append(rows)
            if c is not None:
                counters.append(c)
        h = mhc.contract(h, cfg.hc_streams)
        b, q, d = h.shape
        logits = self.logits(self.out_norm(h).reshape(b * q, d))
        return logits.reshape(b, q, -1), h, new, stack_counters(counters)

    def draft(self, tokens, h, pos, valid, cache):
        """The draft module over ``q`` positions a stream against ITS
        cache: ``tokens [B, q]`` are the inputs that FOLLOW each
        position, ``h [B, q, D]`` what :meth:`verify` gave there.
        Returns its logits ``[B, q, V]`` (the token after next), its
        cache and its expert layer's counters."""
        with jax.named_scope("embed"):
            emb = jnp.take(self.embed.astype(h.dtype), tokens, axis=0)
        out, counters, cache = self.drafts[0](emb, h, valid, pos, cache)
        b, q, d = out.shape
        return self.logits(out.reshape(b * q, d)).reshape(b, q, -1), \
            cache, counters


def reach_pairs(lens, window: int) -> dict:
    """(query, key) pairs in reach of the valid positions of sequences
    of ``lens [B]`` valid positions (left-packed), in ONE layer of each
    kind: ``n (n + 1) / 2`` where the layer sees all, and ``min(i + 1,
    window)`` keys for query i where it has a window."""
    n = lens.astype(jnp.int32)
    near = jnp.minimum(n, window)
    return {"reach_pairs_global": jnp.sum(n * (n + 1) // 2),
            "reach_pairs_window": jnp.sum(
                near * (near + 1) // 2 + (n - near) * window)}


def stack_counters(counters: list) -> dict:
    """The expert layers' counters, one leading axis over the layers."""
    if not counters:
        return {}
    return jax.tree.map(lambda *xs: jnp.stack(xs), *counters)


def uncached_kinds(cfg: ModelConfig) -> list:
    """The preset's layer kinds that have no decode form yet."""
    return sorted(set(cfg.lfm_layer_types)
                  - {"latent_attention", HYBRID, SPARSE, LINEAR,
                     *ATTENTION_KINDS})


def target_logp(h, embed, layout, labels, label_lens):
    """Log-probability of each target, ``[B, U+1]``, and which of them
    count: the logits of the text positions against the head's matrix
    ``embed [V, D]``."""
    u1 = labels.shape[1] + 1
    at = jnp.clip(layout["a_lens"][:, None] + jnp.arange(u1)[None, :],
                  0, h.shape[1] - 1)
    ht = jnp.take_along_axis(h, at[..., None], axis=1)
    # One [B*(U+1), D] x [D, V] product: batched per utterance, its 65
    # rows a matrix would waste the MXU's tiles.
    logits = jnp.dot(ht.reshape(-1, ht.shape[-1]),
                     embed.astype(h.dtype).T,
                     preferred_element_type=jnp.float32)
    want = jnp.take_along_axis(layout["targets"], at, axis=1)
    logp = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                               want.reshape(-1, 1), axis=1).reshape(
                                   want.shape)
    mask = jnp.arange(u1)[None, :] <= label_lens[:, None]
    return logp, mask.astype(logp.dtype)


def create_lfm2_model(cfg: ModelConfig, max_label_len: int) -> LFM2ASR:
    if not cfg.lfm_layer_types:
        raise ValueError("objective='lm' needs model.lfm_layer_types")
    if cfg.expert_offset + cfg.experts_held > cfg.lfm_experts:
        raise ValueError(
            f"experts {cfg.expert_offset}..+{cfg.experts_held} are not "
            f"among the router's {cfg.lfm_experts}")
    if cfg.moe_route_pre_attn and cfg.hc_streams > 1:
        raise NotImplementedError(
            "a router that reads the layer's input has one residual "
            "stream to read (hc_streams = 1)")
    return LFM2ASR(cfg, max_label_len)


def seeded_variables(cfg, seed: int, dtype=None):
    """``(params, buffers)`` of the preset ``cfg`` (a ``Config``) from
    ``seed`` by the modules' own initialisers, made ON THE DEVICE in
    ``dtype`` (the model's compute dtype where None) one layer at a
    time: a model that fits the chip only in bfloat16 never exists in
    float32. Layers of one kind share one compiled initialisation (the
    key is its argument); the shell's own parameters (embedding, prefix
    projection, last norm, head) come from a model without layers."""
    import dataclasses

    m = cfg.model
    dtype = jnp.dtype(dtype or m.dtype)
    frames = 2 * m.frame_stack
    batch = (jnp.zeros((1, frames, cfg.features.num_features)),
             jnp.full((1,), frames, jnp.int32),
             jnp.zeros((1, cfg.data.max_label_len), jnp.int32),
             jnp.zeros((1,), jnp.int32))

    def cast(tree):
        return jax.tree.map(lambda x: x.astype(dtype), tree)

    def shell(rng):
        v = LFM2ASR(dataclasses.replace(m, lfm_layer_types=(),
                                        lm_draft_layers=0),
                    cfg.data.max_label_len).init(rng, *batch,
                                                 method="loss")
        return cast(v["params"])

    h = jnp.zeros((1, 2, m.lfm_hidden), dtype)
    where = (jnp.ones((1, 2), bool), jnp.arange(2)[None, :])
    oracle = dataclasses.replace(m, moe_impl="xla")

    def held(v):
        return cast(v["params"]), v.get("buffers", {})

    @partial(jax.jit, static_argnums=(1, 2))
    def layer(rng, kind, sparse):
        return held(DecoderLayer(oracle, kind, sparse).init(
            rng, mhc.fan_out(h, m.hc_streams), *where))

    @jax.jit
    def draft(rng):
        return held(DraftModule(oracle).init(rng, h, h, *where))

    rng = jax.random.PRNGKey(seed)
    params, buffers = jax.jit(shell)(rng), {}
    made = [(f"layer{i}", layer(jax.random.fold_in(rng, i + 1), kind,
                                i >= m.lfm_dense_layers))
            for i, kind in enumerate(m.lfm_layer_types)]
    made += [(f"draft{i}", draft(jax.random.fold_in(
        rng, len(m.lfm_layer_types) + i + 1)))
        for i in range(m.lm_draft_layers)]
    for name, (p, b) in made:
        params[name] = p
        if b:
            buffers[name] = b
    return params, buffers
