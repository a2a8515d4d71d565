"""The A.X-K1 block's attention (``model_type: axk1``): multi-head
latent attention, as a layer kind of the decoder-only recogniser of
``models/lfm2.py`` (``lfm_layer_types`` of ``"latent_attention"``).

The query goes through a low-rank path with a norm in it (``q_a``,
``q_norm``, ``q_b``) to ``heads`` x (``nope`` | ``rope``) values. Keys
and values come from ONE latent row a position: ``kv_a`` gives
``kv_rank`` values, normed, and ``rope`` more that are rotated and are
the positional part of EVERY head's key; ``kv_b`` expands the latent
row to each head's (``nope`` key | value). Scores are ``(q_nope .
k_nope + rot(q_rope) . k_rope)`` times ``(nope + rope)^-0.5 m^2``
(YaRN's ``m`` for ``mscale_all_dim``), causal softmax in float32.

The cache holds, per position, the ``kv_rank + rope`` values ``(c_kv
after its norm | k_rope after its rotation)`` and nothing per head.
The layer has TWO FORMS that agree:

- over a whole sequence (training, prefill) the latent rows are
  expanded to per-head keys and values;
- for one new position against a cache (a decode step), or a few
  consecutive ones (a draft behind the token it follows), they never
  are: ``kv_b``'s key half is absorbed into the query (``q~_h =
  q_nope,h W_UK,h^T``, ``kv_rank`` wide), the scores are taken against
  the cached rows themselves, the probabilities weigh the cached
  ``c_kv``, and ``kv_b``'s value half is applied to that sum.

Rotary pairs are interleaved (2i, 2i+1), positions count from 0 at the
first prefix frame.
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..config import ModelConfig

_INIT = nn.initializers.normal(0.02)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: ModelConfig) -> np.ndarray:
    """The rotary frequencies of ``mla_rope_dim`` / 2 pairs: the plain
    ``theta^(-2i/d)`` where a pair turns more than ``beta_fast`` times
    over the original context, that over ``factor`` where it turns
    fewer than ``beta_slow`` times, a linear ramp between."""
    d, base = cfg.mla_rope_dim, cfg.lfm_rope_theta
    plain = base ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    factor = cfg.rope_yarn_factor
    if factor <= 1:
        return plain

    def pair_of(turns):  # the pair that turns this often over the context
        return d * math.log(cfg.rope_yarn_original / (turns * 2 * math.pi)
                            ) / (2 * math.log(base))

    fast, slow = cfg.rope_yarn_betas
    low = max(math.floor(pair_of(fast)), 0)
    high = min(math.ceil(pair_of(slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    return plain / factor * ramp + plain * (1 - ramp)


def softmax_scale(cfg: ModelConfig) -> float:
    m = yarn_mscale(cfg.rope_yarn_factor, cfg.rope_yarn_mscales[1])
    return (cfg.mla_nope_dim + cfg.mla_rope_dim) ** -0.5 * m * m


def rotate(x, pos, cfg: ModelConfig):
    """``x [B, S, ..., rope]`` rotated to ``pos [B, S]``."""
    ang = pos.astype(jnp.float32)[..., None] * jnp.asarray(
        yarn_inv_freq(cfg), jnp.float32)
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[2:])
    mscale, mscale_all = cfg.rope_yarn_mscales
    amp = (yarn_mscale(cfg.rope_yarn_factor, mscale)
           / yarn_mscale(cfg.rope_yarn_factor, mscale_all))
    cos, sin = jnp.cos(ang) * amp, jnp.sin(ang) * amp
    x32 = x.astype(jnp.float32)
    even, odd = x32[..., 0::2], x32[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], -1)
    return out.reshape(x.shape).astype(x.dtype)


def rms(x, gain, eps: float):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * gain).astype(x.dtype)


class LatentAttention(nn.Module):
    """``__call__(x [B, S, D], pos [B, S])`` is the sequence form and
    returns the output and the rows to cache ``[B, S, kv_rank + rope]``;
    with ``cache [B, R, kv_rank + rope]`` it is the decode form over S
    new positions a stream (1, or a few consecutive ones): the new
    rows are written at ``pos`` and each position attends to rows ``0
    .. pos`` of the cache; it returns the output and the cache."""

    cfg: ModelConfig

    @nn.compact
    def __call__(self, x, pos, cache=None):
        cfg = self.cfg
        b, s, d = x.shape
        nh, rq, rkv = cfg.lfm_heads, cfg.mla_q_rank, cfg.mla_kv_rank
        dn, dr, dv = cfg.mla_nope_dim, cfg.mla_rope_dim, cfg.mla_v_dim
        eps = cfg.lfm_norm_eps

        def weight(name, shape):
            return self.param(name, _INIT, shape).astype(x.dtype)

        def gain(name, n):
            return self.param(name, nn.initializers.ones, (n,))

        c_q = rms(jnp.dot(x, weight("q_a", (d, rq))), gain("q_norm", rq),
                  eps)
        q = jnp.dot(c_q, weight("q_b", (rq, nh * (dn + dr)))
                    ).reshape(b, s, nh, dn + dr)
        q_nope, q_rope = q[..., :dn], rotate(q[..., dn:], pos, cfg)
        kv = jnp.dot(x, weight("kv_a", (d, rkv + dr)))
        rows = jnp.concatenate(
            [rms(kv[..., :rkv], gain("kv_norm", rkv), eps),
             rotate(kv[..., rkv:], pos, cfg)], axis=-1)
        w_kvb = weight("kv_b", (rkv, nh * (dn + dv))).reshape(
            rkv, nh, dn + dv)
        w_o = weight("o", (nh * dv, d))
        scale = softmax_scale(cfg)

        if cache is None:
            kv_h = jnp.einsum("bsc,chn->bshn", rows[..., :rkv], w_kvb)
            k = jnp.concatenate(
                [kv_h[..., :dn], jnp.broadcast_to(
                    rows[:, :, None, rkv:], (b, s, nh, dr))], axis=-1)
            qk = jnp.concatenate([q_nope, q_rope], axis=-1)
            scores = jnp.einsum("bqhn,bkhn->bhqk", qk, k,
                                preferred_element_type=jnp.float32)
            causal = jnp.tril(jnp.ones((s, s), bool))
            scores = jnp.where(causal, scores * scale, -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
            out = jnp.einsum("bhqk,bkhv->bqhv", probs, kv_h[..., dn:])
            with jax.named_scope("attn_out"):
                return jnp.dot(out.reshape(b, s, nh * dv), w_o), rows

        if s > 1:
            # q consecutive new positions a stream (a draft and what it
            # follows): all q rows are written first, and a position's
            # own mask hides the rows after it. One position (below)
            # keeps its own, three-dimensional contractions.
            with jax.named_scope("cache_update"):
                cache = cache.at[jnp.arange(b)[:, None], pos].set(rows)
            q_lat = jnp.einsum("bqhn,chn->bqhc", q_nope, w_kvb[..., :dn])
            qk = jnp.concatenate([q_lat, q_rope], axis=-1)
            scores = jnp.einsum("bqhc,brc->bhqr", qk, cache,
                                preferred_element_type=jnp.float32)
            seen = jnp.arange(cache.shape[1])[None, None, :] \
                <= pos[:, :, None]
            scores = jnp.where(seen[:, None], scores * scale, -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
            mixed = jnp.einsum("bhqr,brc->bqhc", probs, cache[..., :rkv])
            out = jnp.einsum("bqhc,chv->bqhv", mixed, w_kvb[..., dn:])
            with jax.named_scope("attn_out"):
                return jnp.dot(out.reshape(b, s, nh * dv), w_o), cache
        at = pos[:, 0]
        with jax.named_scope("cache_update"):
            cache = cache.at[jnp.arange(b), at].set(rows[:, 0])
        q_lat = jnp.einsum("bhn,chn->bhc", q_nope[:, 0], w_kvb[..., :dn])
        qk = jnp.concatenate([q_lat, q_rope[:, 0]], axis=-1)
        scores = jnp.einsum("bhc,brc->bhr", qk, cache,
                            preferred_element_type=jnp.float32)
        seen = jnp.arange(cache.shape[1])[None, :] <= at[:, None]
        scores = jnp.where(seen[:, None, :], scores * scale, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        mixed = jnp.einsum("bhr,brc->bhc", probs, cache[..., :rkv])
        out = jnp.einsum("bhc,chv->bhv", mixed, w_kvb[..., dn:])
        with jax.named_scope("attn_out"):
            return jnp.dot(out.reshape(b, 1, nh * dv), w_o), cache


def both_forms(cfg: ModelConfig, params, x, at, q: int = 1):
    """One layer on ``x [B, S, D]`` in both forms: the sequence form
    over all positions, then the decode form for the ``q`` consecutive
    positions from each of ``at`` (a numpy index array) against the
    cache the sequence form gave, every (row, start) a stream of its
    own. Returns the two outputs at those positions, ``[B, len(at) * q,
    D]`` each (the checks of ``chip_smoke.py`` and of the benchmark's
    drivers compare them)."""
    layer = LatentAttention(cfg)
    b, s, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    seq, rows = layer.apply({"params": params}, x, pos)
    span = np.asarray(at)[:, None] + np.arange(q)[None, :]
    one = x[:, span.reshape(-1)].reshape(b * len(at), q, -1)
    dec, _ = layer.apply({"params": params}, one,
                         jnp.tile(jnp.asarray(span), (b, 1)),
                         jnp.repeat(rows, len(at), axis=0))
    return dec.reshape(b, span.size, -1), seq[:, span.reshape(-1)]
