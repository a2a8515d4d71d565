"""Causal grouped-query attention over a whole sequence as ONE Pallas
kernel, ``gqa_attn_fwd``: the scores of a query tile against a key tile
live in VMEM only, under a running row maximum and sum (the online
softmax of flash attention), so no score reaches HBM.

``gqa_attention(q [B,S,kv,rep,hd], k, v [B,S,kv,hd], window, oracle)``
returns ``[B,S,kv,rep,hd]``: query i attends to the keys ``j <= i``, and
``i - window < j`` where there is a window (``models/lfm2.reach_mask``'s
rule). Products are in the operands' dtype with float32 accumulation;
scale, mask, maximum, exponent and sum are float32; the probabilities
are cast to the values' dtype for the second product. They are cast
UNNORMALISED (``exp(s - m)`` with the running maximum ``m``) and the
sum divides once after the last key tile, where the blockwise loop the
kernel replaces rounds normalised probabilities (on the chip the two
differ by 0.29% rms of a bf16 result, and a layer's two forms agree as
before: PERF.md section 6, PR 42).

The grid is (row, key/value head, query tile, key tile), the key tile
innermost. A query tile holds its positions' ``rep`` query heads that
share the key/value head, so a K or V tile is fetched once for all of
them; the products run a head at a time. A query tile visits the key tiles ``first .. last``
that hold a key one of its queries can reach (:func:`reach`): none
above the diagonal, none older than the window; the grid's key axis is
as long as the longest such run, and a step past a tile's run fetches
nothing (its block index stays at ``last``) and computes nothing. Tiles
all of whose scores are in reach skip the mask. The sequence need not
be whole tiles: the last query and key tiles hang over its end, their
overhang is masked (values past the end are zeroed: a probability of 0
times whatever lies there must stay 0) and never written.

K and V are read where they lie, ``[B, S, kv x hd]``; the result is
``[B, kv, rep, S, hd]`` (a tile's rows a head at a time, whole tiles
of the layout Mosaic writes), transposed by the caller's program.

Differentiable through ``oracle(q, k, v)``, the caller's plain form of
the same function: the backward pass is the oracle's VJP, recomputed.
Nothing trains through it today (a trained layer's sequence is one
block); it is there so that no path raises under ``jax.grad``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernel_id import kernel_call

# Queries of one head and keys a tile: 7.8 ms a layer of Trinity's
# prefill sub-batch on the chip (2 x 5,250 positions, 48 / 8 heads of
# 128); 384 x 512, 512 x 512 and 256 x 1024 read 7.8-8.2, 128 x 512
# 10.8 (PERF.md section 6, PR 42). A head's scores are ``Q_TILE x
# K_TILE`` float32 in VMEM (0.5 MiB: Mosaic's default scoped limit
# holds the call).
Q_TILE = 256
K_TILE = 512
_MASKED = -1e30


def _run(i0, s: int, window: int, tq: int, tk: int, xp):
    """First and last key tile that hold a key in reach of the query
    tile that starts at ``i0`` (numpy for the static counts, jax.numpy
    for the traced tile of a grid step)."""
    lo = xp.maximum(i0 - window + 1, 0) // tk if window else 0 * i0
    return lo, (xp.minimum(i0 + tq, s) - 1) // tk


def reach(s: int, window: int, tq: int, tk: int):
    """For each query tile of ``tq`` positions of ``s``: the first and
    the last key tile of ``tk`` that hold a key in its queries' reach
    (every tile between them does), and the first and last of those
    ALL of whose keys every query of the tile reaches (first > last
    where there is none)."""
    i0 = np.arange(0, s, tq)
    first, last = _run(i0, s, window, tq, tk, np)
    # whole: the tile ends at or before the tile's first query, and
    # starts inside the window of its last
    whole_last = (i0 + 1) // tk - 1
    whole_first = -(-np.maximum(i0 + tq - window, 0) // tk) if window \
        else 0 * i0
    return first, last, whole_first, whole_last


def tile_counts(s: int, window: int, tq: int, tk: int) -> dict:
    """What a build's tiles cost, a (row, key/value head): key tiles
    the grid computes, those of them that hold a key in reach (all: the
    guard is exact), and those that hold a key out of reach as well and
    take the mask."""
    first, last, whole_first, whole_last = reach(s, window, tq, tk)
    computed = int(np.sum(last - first + 1))
    whole = int(np.sum(np.maximum(whole_last - whole_first + 1, 0)))
    i0 = np.arange(0, s, tq)
    in_reach = sum(
        1 for a, b in zip(i0, np.minimum(i0 + tq, s))
        for j0 in range(0, s, tk)
        if j0 < b and (not window or min(j0 + tk, s) - 1 > a - window))
    return {"key_tiles": computed, "key_tiles_in_reach": in_reach,
            "key_tiles_masked": computed - whole}


def _fwd(q, k, v, window: int, tq: int, tk: int, interpret: bool):
    b, s, nkv, rep, hd = q.shape
    scale = hd ** -0.5
    first, last, _, _ = reach(s, window, tq, tk)
    steps = int(np.max(last - first + 1))
    ragged = s % tk != 0
    # The running maximum and sum of a row, the same in every lane of a
    # lane tile where the shapes are whole lane tiles: a row's statistic
    # then meets its scores and its accumulator without a broadcast
    # along the lanes in every step (12.7 -> 8.6 ms a layer on the chip).
    lanes = 128 if tk % 128 == 0 and hd % 128 == 0 else 1

    def wide(x, n):
        """A row statistic ``[tq, lanes]`` against ``n`` columns."""
        return x if lanes == 1 else jnp.tile(x, (1, n // lanes))

    def run_of(qi):
        return _run(qi * tq, s, window, tq, tk, jnp)

    def body(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref):
        qi, step = pl.program_id(2), pl.program_id(3)
        lo, hi = run_of(qi)
        kt = lo + step
        i0, j0 = qi * tq, kt * tk

        @pl.when(step == 0)
        def _start():
            m_ref[...] = jnp.full_like(m_ref, _MASKED)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        def tile(masked: bool):
            keys, values = k_ref[...], v_ref[...]
            if masked:
                at = i0 + lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
                key = j0 + lax.broadcasted_iota(jnp.int32, (1, tk), 1)
                seen = key <= at
                if window:
                    seen = jnp.logical_and(seen, key > at - window)
                if ragged:
                    held = j0 + lax.broadcasted_iota(
                        jnp.int32, (tk, 1), 0) < s
                    values = jnp.where(held, values,
                                       jnp.zeros((), values.dtype))
            # a head at a time: the six products of a tile share its K
            # and V in VMEM, and one head's exponents run beside the
            # next one's product (8.6 -> 7.8 ms a layer on the chip)
            for r in range(rep):
                scores = lax.dot_general(
                    q_ref[:, r * hd:(r + 1) * hd], keys,
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                if masked:
                    scores = jnp.where(seen, scores, _MASKED)
                m_prev = m_ref[r]
                m_next = jnp.maximum(
                    m_prev, jnp.max(scores, axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_next)
                p = jnp.exp(scores - wide(m_next, tk))
                l_ref[r] = alpha * l_ref[r] + jnp.sum(
                    p, axis=1, keepdims=True)
                m_ref[r] = m_next
                acc_ref[r] = wide(alpha, hd) * acc_ref[r] + jnp.dot(
                    p.astype(values.dtype), values,
                    preferred_element_type=jnp.float32)

        # every key of the tile in every query's reach: no mask
        whole = j0 + tk - 1 <= i0
        if window:
            whole = jnp.logical_and(whole, j0 > i0 + tq - 1 - window)
        live = kt <= hi
        pl.when(jnp.logical_and(live, whole))(lambda: tile(False))
        pl.when(jnp.logical_and(live, jnp.logical_not(whole)))(
            lambda: tile(True))

        @pl.when(step == steps - 1)
        def _finish():
            for r in range(rep):
                o_ref[r] = (acc_ref[r] / wide(l_ref[r], hd)).astype(
                    o_ref.dtype)

    def q_index(bi, g, qi, step):
        return bi, qi, g

    def kv_index(bi, g, qi, step):
        lo, hi = run_of(qi)
        return bi, jnp.minimum(lo + step, hi), g

    def out_index(bi, g, qi, step):
        return bi, g, 0, qi, 0

    facts = {"b": b, "s": s, "kv": nkv, "rep": rep, "head": hd,
             "window": window, "q_tile": tq, "k_tile": tk,
             **tile_counts(s, window, tq, tk)}
    return kernel_call(
        body, kernel="gqa_attn_fwd", facts=facts,
        out_shape=jax.ShapeDtypeStruct((b, nkv, rep, s, hd), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            in_specs=[pl.BlockSpec((None, tq, rep * hd), q_index),
                      pl.BlockSpec((None, tk, hd), kv_index),
                      pl.BlockSpec((None, tk, hd), kv_index)],
            out_specs=pl.BlockSpec((None, None, rep, tq, hd), out_index),
            grid=(b, nkv, len(first), steps),
            scratch_shapes=[pltpu.VMEM((rep, tq, lanes), jnp.float32),
                            pltpu.VMEM((rep, tq, lanes), jnp.float32),
                            pltpu.VMEM((rep, tq, hd), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q.reshape(b, s, nkv * rep * hd), k.reshape(b, s, nkv * hd),
      v.reshape(b, s, nkv * hd))


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def gqa_attention(q, k, v, window: int, oracle, q_tile: int = Q_TILE,
                  k_tile: int = K_TILE, interpret: bool = False):
    """The kernel's result as the sequence form lays it out,
    ``[B, S, kv, rep, hd]``."""
    out = _fwd(q, k, v, window, q_tile, k_tile, interpret)
    b, s, nkv, rep, hd = q.shape
    # The barrier holds the transposition to this dtype and place: left
    # free, XLA converts the kernel's result to the float32 of the
    # layer's gate first and moves twice the bytes (a whole layer 30.7
    # ms against 29.2 on the chip, PERF.md section 6, PR 42).
    flat = lax.optimization_barrier(
        out.transpose(0, 3, 1, 2, 4).reshape(b, s, nkv * rep * hd))
    return flat.reshape(q.shape)


def _attention_fwd(q, k, v, window, oracle, q_tile, k_tile, interpret):
    return gqa_attention(q, k, v, window, oracle, q_tile, k_tile,
                         interpret), (q, k, v)


def _attention_bwd(window, oracle, q_tile, k_tile, interpret, res, grad):
    return jax.vjp(oracle, *res)[1](grad)


gqa_attention.defvjp(_attention_fwd, _attention_bwd)


def fits(head: int) -> bool:
    """Whether Mosaic takes the kernel's blocks: a head is whole lane
    tiles (K and V are read a head's columns at a time)."""
    return head % 128 == 0
