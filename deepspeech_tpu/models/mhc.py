"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880; the
``hc_*`` keys of ``model_type: xing4_0``): the residual path of the
decoder-only shell (``models/lfm2.py``) when ``hc_streams`` = n > 1.

The residual of one position is ``X [n, D]``, n streams. A sub-layer F
(attention or feed-forward, with its own pre-norm) does not read ``h``
and add to it; it reads a learned mix of the streams and writes back
through a post-mix, while the streams themselves are mixed by a doubly
stochastic matrix::

    x~     = RMSNorm(vec(X))                       over all n*D values
    H~     = a * (x~ @ phi) + b                    n + n + n*n logits
    H_pre  = sigmoid(H~pre)            [n]
    H_post = 2 sigmoid(H~post)         [n]
    H_res  = Sinkhorn(exp(clamp(H~res)))   [n, n], rows and columns
                                           sum to one (up to hc_eps)
    X'     = H_res @ X + H_post (outer) F(H_pre @ X)

``phi [n*D, n + n + n*n]`` is one matrix (its columns in that order),
``a`` three learned scalars (pre, post, res), ``b`` the logits' bias.
The coefficient path is float32 whatever the streams' dtype: the norm's
gain is folded into ``phi`` (``x~ @ phi = rsqrt(mean x^2 + eps) * (x @
(g * phi))``), so the normed ``[N, n*D]`` copy of the streams never
exists, and the Sinkhorn rounds run with the positions on the last
axis, where a chip's lanes are. The three mixes (:func:`read`,
:func:`write`) accumulate in float32 and give the streams' dtype back.

On a TPU, with streams of whole lane tiles, a sub-layer's passes over
the streams are two kernels (``ops/mhc_pallas.py``: coefficients and
read mix from one tile, the write-back from one);
``DecoderLayer.residual`` takes that route from the shapes. The
functions here are the form everywhere else, what the kernels are held
to (``tests/test_mhc_pallas.py``) and what a gradient through them is
computed by (:func:`kernel_read`, :func:`kernel_write`).

A trained model starts near the plain residual (``H_res`` near the
identity); the seeded ``b`` has std 1, so that on seeded weights the
mixing matrices are far from it and a fault in any of them is seen.
"""

from __future__ import annotations

from functools import partial

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..config import ModelConfig
from ..ops import mhc_pallas

_INIT = nn.initializers.normal(0.02)
_BIAS = nn.initializers.normal(1.0)


def sinkhorn_round(m, eps: float):
    """One round on ``m [n, n, ...]`` (rows on axis 0, columns on axis
    1): every row, then every column, divided by its sum + ``eps``.
    Written over the n * n entries one by one, each an array of all
    positions: sums of four are additions, not reductions, so a round
    is one element-wise chain, which a compiler makes one kernel
    (reductions over an axis of 4 were four kernels a round)."""
    n = m.shape[0]
    rows = [[m[i, j] for j in range(n)] for i in range(n)]
    for i in range(n):
        total = sum(rows[i][1:], rows[i][0]) + eps
        rows[i] = [x / total for x in rows[i]]
    for j in range(n):
        total = sum((rows[i][j] for i in range(1, n)), rows[0][j]) + eps
        for i in range(n):
            rows[i][j] = rows[i][j] / total
    return jnp.stack([jnp.stack(r) for r in rows])


def sinkhorn(m, iters: int, eps: float):
    """``iters`` rounds of :func:`sinkhorn_round` on a positive ``m``,
    as a loop of four rounds a turn: unrolled whole, 20 rounds in 16
    sub-layers took a compiler minutes."""
    return lax.fori_loop(0, iters, lambda _, x: sinkhorn_round(x, eps), m,
                         unroll=min(4, iters))


def fan_out(h, n: int):
    """``h [..., D]`` copied into ``n`` streams ``[..., n, D]``."""
    return h if n == 1 else jnp.broadcast_to(
        h[..., None, :], h.shape[:-1] + (n, h.shape[-1]))


def contract(x, n: int):
    """The sum of the ``n`` streams, ``[..., n, D] -> [..., D]``."""
    return x if n == 1 else jnp.sum(
        x.astype(jnp.float32), axis=-2).astype(x.dtype)


def read(h_pre, x):
    """A sub-layer's input ``H_pre @ X``: ``[..., n]``, ``[..., n, D]``
    -> ``[..., D]``."""
    return jnp.sum(h_pre[..., None] * x.astype(jnp.float32),
                   axis=-2).astype(x.dtype)


def write(h_res, h_post, x, y):
    """``H_res @ X + H_post (outer) y``: the streams after a sub-layer
    whose output is ``y [..., D]``."""
    x32 = x.astype(jnp.float32)
    n = x.shape[-2]
    mixed = sum(h_res[..., :, j, None] * x32[..., j, None, :]
                for j in range(n))
    return (mixed + h_post[..., None]
            * y.astype(jnp.float32)[..., None, :]).astype(x.dtype)


def coefficients(x, gain, phi, alpha, bias, *, norm_eps: float, clamp,
                 iters: int, eps: float):
    """``H_pre [..., n]``, ``H_post [..., n]`` and ``H_res [..., n, n]``
    (float32) from the streams ``x [..., n, D]`` and a
    :class:`HyperConnection`'s parameters."""
    n, d = x.shape[-2:]
    lead = x.shape[:-2]
    f32 = jnp.float32
    flat = x.reshape((-1, n * d)).astype(f32)
    inv = lax.rsqrt(jnp.mean(flat * flat, axis=-1) + norm_eps)
    raw = jnp.dot(flat, gain.astype(f32)[:, None] * phi.astype(f32),
                  precision=lax.Precision.HIGHEST)
    # Positions last from here: [n * (n + 2), N].
    scale = alpha.astype(f32)[np.repeat(np.arange(3), [n, n, n * n])]
    logits = (raw * inv[:, None]).T * scale[:, None] \
        + bias.astype(f32)[:, None]
    lo, hi = clamp
    res = sinkhorn(
        jnp.exp(jnp.clip(logits[2 * n:], lo, hi)).reshape(n, n, -1),
        iters, eps)
    h_pre = jax.nn.sigmoid(logits[:n]).T.reshape(lead + (n,))
    h_post = 2.0 * jax.nn.sigmoid(logits[n:2 * n]).T.reshape(lead + (n,))
    return h_pre, h_post, jnp.moveaxis(res, -1, 0).reshape(lead + (n, n))


def unpack(coef, n: int):
    """``(H_pre, H_post, H_res)`` of the kernels' coefficients ``[...,
    n * (n + 2)]``: ``H_pre``, ``H_post``, ``H_res`` row by row."""
    return (coef[..., :n], coef[..., n:2 * n],
            coef[..., 2 * n:].reshape(coef.shape[:-1] + (n, n)))


# The kernels have no derivative of their own: a gradient through them
# is the plain form's, computed from the same operands.
@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def kernel_read(x, gain, phi, alpha, bias, norm_eps, clamp, iters, eps):
    """``mhc_read`` (``ops/mhc_pallas.py``): ``(coefficients [..., n *
    (n + 2)], read mix [..., D])`` in one pass over the streams."""
    return mhc_pallas.read(x, gain, phi, alpha, bias, norm_eps=norm_eps,
                           clamp=clamp, iters=iters, eps=eps)


def _kernel_read_fwd(x, gain, phi, alpha, bias, *how):
    return kernel_read(x, gain, phi, alpha, bias, *how), (
        x, gain, phi, alpha, bias)


def _kernel_read_bwd(norm_eps, clamp, iters, eps, operands, cotangents):
    def plain(x, *params):
        h_pre, h_post, h_res = coefficients(
            x, *params, norm_eps=norm_eps, clamp=clamp, iters=iters, eps=eps)
        packed = jnp.concatenate(
            [h_pre, h_post, h_res.reshape(h_pre.shape[:-1] + (-1,))], axis=-1)
        return packed, read(h_pre, x)

    return jax.vjp(plain, *operands)[1](cotangents)


kernel_read.defvjp(_kernel_read_fwd, _kernel_read_bwd)


@jax.custom_vjp
def kernel_write(x, y, coef):
    """``mhc_write``: the streams ``x`` after a sub-layer whose output
    is ``y``, by the coefficients :func:`kernel_read` gave."""
    return mhc_pallas.write(x, y, coef)


def _kernel_write_bwd(operands, cotangent):
    def plain(x, y, coef):
        _, h_post, h_res = unpack(coef, x.shape[-2])
        return write(h_res, h_post, x, y)

    return jax.vjp(plain, *operands)[1](cotangent)


kernel_write.defvjp(lambda *a: (kernel_write(*a), a), _kernel_write_bwd)


class HyperConnection(nn.Module):
    """The coefficients of one sub-layer from the streams ``x [..., n,
    D]``: ``H_pre [..., n]``, ``H_post [..., n]``, ``H_res [..., n,
    n]``, float32. Sown as ``h_pre`` / ``h_post`` / ``h_res``.

    With ``kernels`` the pass over the streams is :func:`kernel_read`,
    which has the sub-layer's input from the same tile: the result is
    then ``(coefficients [..., n * (n + 2)], read mix [..., D])``, the
    coefficients as :func:`kernel_write` takes them."""

    cfg: ModelConfig

    @nn.compact
    def __call__(self, x, kernels: bool = False):
        cfg = self.cfg
        n, d = x.shape[-2:]
        gain = self.param("norm", nn.initializers.ones, (n * d,))
        phi = self.param("phi", _INIT, (n * d, n * (n + 2)))
        alpha = self.param("alpha", nn.initializers.ones, (3,))
        bias = self.param("bias", _BIAS, (n * (n + 2),))
        if kernels:
            coef, mix = kernel_read(
                x, gain, phi, alpha, bias, cfg.lfm_norm_eps,
                cfg.hc_res_clamp, cfg.hc_sinkhorn_iters, cfg.hc_eps)
            h_pre, h_post, h_res = unpack(coef, n)
        else:
            h_pre, h_post, h_res = coefficients(
                x, gain, phi, alpha, bias, norm_eps=cfg.lfm_norm_eps,
                clamp=cfg.hc_res_clamp, iters=cfg.hc_sinkhorn_iters,
                eps=cfg.hc_eps)
        self.sow("intermediates", "h_pre", h_pre)
        self.sow("intermediates", "h_post", h_post)
        self.sow("intermediates", "h_res", h_res)
        return (coef, mix) if kernels else (h_pre, h_post, h_res)
