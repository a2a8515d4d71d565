"""Grouped-query attention's two forms as Pallas kernels:
``gqa_attn_fwd`` (:func:`gqa_attention`, a whole sequence, described
here) with its backward pair ``gqa_attn_bwd_dq`` / ``gqa_attn_bwd_dkv``,
and ``gqa_attn_decode`` (:func:`gqa_decode`, one position a stream
against its cache, described there); and both forms under a BLOCK
SELECTION, ``gqa_attn_select_fwd`` (:func:`gqa_select_attention`) and
``gqa_attn_select_decode`` (:func:`gqa_select_decode`, which fetches the
selected blocks only, by a scalar-prefetched index list), at the end of
the file.

Causal grouped-query attention over a whole sequence: the scores of a
query tile against a key tile live in VMEM only, under a running row
maximum and sum (the online softmax of flash attention), so no score
reaches HBM.

``gqa_attention(q [B,S,kv,rep,hd], k, v [B,S,kv,hd], window)``
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

Differentiable through two more kernels that hold no ``[S, S]`` array
either (flash attention's backward pass). Under ``jax.grad`` the
forward kernel also gives out each query's log-sum-exp ``[B, kv, rep,
S]`` (a served call's build has no such output and is the program it
was); the backward pass recomputes a tile's scores and probabilities
from it in VMEM, TRANSPOSED (keys down the sublanes, queries along the
lanes: a query's log-sum-exp and ``delta = sum(dout * out)`` then meet
their column as a row, without a relayout), and visits only the tiles
in reach, by the forward's rule:

- ``gqa_attn_bwd_dq``: the forward's grid (row, key/value head, query
  tile, key tile innermost); ``dq += (p * (dout . v - delta)) . k``
  summed over a query tile's key tiles in VMEM;
- ``gqa_attn_bwd_dkv``: grid (row, key/value head, key tile, query
  tile innermost; :func:`reach_of_keys`); ``dv += p^T . dout`` and
  ``dk += (p * (dout . v - delta))^T . q`` summed in VMEM over the query
  tiles AND over the ``rep`` query heads that share the key/value head.

``dout`` is read and ``dq`` written where q lies (``[B, S, kv x rep x
hd]``), ``dk`` and ``dv`` where k and v lie: nothing is transposed in
HBM. A tile that hangs over the sequence's end zeroes what it would
sum over there (queries in ``dkv``, keys in ``dq``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
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


def _wide(x, n: int):
    """A row statistic ``[rows, lanes]`` (lanes 1, or a lane tile that
    holds it in every lane) against ``n`` columns."""
    return x if x.shape[1] == 1 else jnp.tile(x, (1, n // x.shape[1]))


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


def _row(x):
    """A row statistic ``[rows, lanes]`` (the same in every lane) as
    one row ``[1, rows]``."""
    return x.reshape(1, -1) if x.shape[1] == 1 else x.T[:1]


def _fwd(q, k, v, window: int, tq: int, tk: int, interpret: bool,
         with_lse: bool = False):
    """The forward kernel's result ``[B, kv, rep, S, hd]`` and, asked
    for, each query's log-sum-exp ``[B, kv, rep, S]`` in float32."""
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

    def run_of(qi):
        return _run(qi * tq, s, window, tq, tk, jnp)

    def body(q_ref, k_ref, v_ref, o_ref, *rest):
        lse_ref = rest[0] if with_lse else None
        m_ref, l_ref, acc_ref = rest[-3:]
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
                p = jnp.exp(scores - _wide(m_next, tk))
                l_ref[r] = alpha * l_ref[r] + jnp.sum(
                    p, axis=1, keepdims=True)
                m_ref[r] = m_next
                acc_ref[r] = _wide(alpha, hd) * acc_ref[r] + jnp.dot(
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
                o_ref[r] = (acc_ref[r] / _wide(l_ref[r], hd)).astype(
                    o_ref.dtype)
                if with_lse:
                    lse_ref[r:r + 1, :] = _row(
                        m_ref[r] + jnp.log(l_ref[r]))

    def q_index(bi, g, qi, step):
        return bi, qi, g

    def kv_index(bi, g, qi, step):
        lo, hi = run_of(qi)
        return bi, jnp.minimum(lo + step, hi), g

    def out_index(bi, g, qi, step):
        return bi, g, 0, qi, 0

    def lse_index(bi, g, qi, step):
        return bi, g, 0, qi

    facts = {"b": b, "s": s, "kv": nkv, "rep": rep, "head": hd,
             "window": window, "q_tile": tq, "k_tile": tk,
             **tile_counts(s, window, tq, tk)}
    out_shape = jax.ShapeDtypeStruct((b, nkv, rep, s, hd), q.dtype)
    out_specs = pl.BlockSpec((None, None, rep, tq, hd), out_index)
    if with_lse:
        out_shape = (out_shape, jax.ShapeDtypeStruct(
            (b, nkv, rep, s), jnp.float32))
        out_specs = (out_specs,
                     pl.BlockSpec((None, None, rep, tq), lse_index))
    return kernel_call(
        body, kernel="gqa_attn_fwd", facts=facts,
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            in_specs=[pl.BlockSpec((None, tq, rep * hd), q_index),
                      pl.BlockSpec((None, tk, hd), kv_index),
                      pl.BlockSpec((None, tk, hd), kv_index)],
            out_specs=out_specs,
            grid=(b, nkv, len(first), steps),
            scratch_shapes=[pltpu.VMEM((rep, tq, lanes), jnp.float32),
                            pltpu.VMEM((rep, tq, lanes), jnp.float32),
                            pltpu.VMEM((rep, tq, hd), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q.reshape(b, s, nkv * rep * hd), k.reshape(b, s, nkv * hd),
      v.reshape(b, s, nkv * hd))


def reach_of_keys(s: int, window: int, tq: int, tk: int):
    """For each key tile of ``tk`` positions of ``s``: the first and the
    last query tile of ``tq`` that hold a query which reaches one of its
    keys (every tile between them does): :func:`reach`, transposed."""
    j0 = np.arange(0, s, tk)
    return _key_run(j0, s, window, tq, tk, np)


def _key_run(j0, s: int, window: int, tq: int, tk: int, xp):
    """:func:`reach_of_keys` for the key tile that starts at ``j0``."""
    last = (s - 1) // tq + 0 * j0
    if window:
        last = xp.minimum(
            (xp.minimum(j0 + tk, s) + window - 2) // tq, last)
    return j0 // tq, last


_BWD_VMEM = 64 * 1024 * 1024


def _seen(i0, j0, s: int, window: int, tq: int, tk: int):
    """``[tk, tq]``: which of the keys ``j0 ..`` (down) each of the
    queries ``i0 ..`` (along) reaches, a query past the end none."""
    at = i0 + lax.broadcasted_iota(jnp.int32, (1, tq), 1)
    key = j0 + lax.broadcasted_iota(jnp.int32, (tk, 1), 0)
    seen = jnp.logical_and(key <= at, at < s)
    if window:
        seen = jnp.logical_and(seen, key > at - window)
    return seen


def _whole(i0, j0, s: int, window: int, tq: int, tk: int):
    """Every key of the tile in every query's reach, and every query
    inside the sequence: the tile takes no mask."""
    whole = jnp.logical_and(j0 + tk - 1 <= i0, i0 + tq <= s)
    if window:
        whole = jnp.logical_and(whole, j0 > i0 + tq - 1 - window)
    return whole


def _tile_grads(q, do, keys, values, lse, delta, seen, scale):
    """One head's part of a tile pair, scores transposed ``[tk, tq]``:
    the probabilities and the scores' gradient, in the operands' dtype
    for the products that follow. ``seen`` None: no mask."""
    dims = (((1,), (1,)), ((), ()))
    st = lax.dot_general(keys, q, dims,
                         preferred_element_type=jnp.float32) * scale
    pt = jnp.exp(st - lse)
    dpt = lax.dot_general(values, do, dims,
                          preferred_element_type=jnp.float32)
    dst = pt * (dpt - delta)
    if seen is not None:
        pt = jnp.where(seen, pt, 0.0)
        dst = jnp.where(seen, dst, 0.0)
    return pt.astype(values.dtype), dst.astype(keys.dtype)


def _bwd_dq(q, k, v, do, lse, delta, window: int, tq: int, tk: int,
            interpret: bool):
    """``dq [B, S, kv x rep x hd]``: the forward's grid."""
    b, s, nkv, rep, hd = q.shape
    scale = hd ** -0.5
    first, last, _, _ = reach(s, window, tq, tk)
    steps = int(np.max(last - first + 1))
    ragged = s % tk != 0

    def run_of(qi):
        return _run(qi * tq, s, window, tq, tk, jnp)

    def body(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
             acc_ref):
        qi, step = pl.program_id(2), pl.program_id(3)
        lo, hi = run_of(qi)
        kt = lo + step
        i0, j0 = qi * tq, kt * tk

        @pl.when(step == 0)
        def _start():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        def tile(masked: bool):
            keys, values = k_ref[...], v_ref[...]
            seen = _seen(i0, j0, s, window, tq, tk) if masked else None
            if masked and ragged:
                # what lies past the end is summed over: zero it
                held = j0 + lax.broadcasted_iota(
                    jnp.int32, (tk, 1), 0) < s
                keys = jnp.where(held, keys, jnp.zeros((), keys.dtype))
                values = jnp.where(held, values,
                                   jnp.zeros((), values.dtype))
            for r in range(rep):
                head = slice(r * hd, (r + 1) * hd)
                _, dst = _tile_grads(
                    q_ref[:, head], do_ref[:, head], keys, values,
                    lse_ref[r:r + 1, :], delta_ref[r:r + 1, :], seen,
                    scale)
                acc_ref[r] += lax.dot_general(
                    dst, keys, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

        whole = _whole(i0, j0, s, window, tq, tk)
        live = kt <= hi
        pl.when(jnp.logical_and(live, whole))(lambda: tile(False))
        pl.when(jnp.logical_and(live, jnp.logical_not(whole)))(
            lambda: tile(True))

        @pl.when(step == steps - 1)
        def _finish():
            for r in range(rep):
                dq_ref[:, r * hd:(r + 1) * hd] = (
                    acc_ref[r] * scale).astype(dq_ref.dtype)

    def q_index(bi, g, qi, step):
        return bi, qi, g

    def kv_index(bi, g, qi, step):
        lo, hi = run_of(qi)
        return bi, jnp.minimum(lo + step, hi), g

    def stat_index(bi, g, qi, step):
        return bi, g, 0, qi

    facts = {"b": b, "s": s, "kv": nkv, "rep": rep, "head": hd,
             "window": window, "q_tile": tq, "k_tile": tk,
             **tile_counts(s, window, tq, tk)}
    wide = pl.BlockSpec((None, tq, rep * hd), q_index)
    stat = pl.BlockSpec((None, None, rep, tq), stat_index)
    return kernel_call(
        body, kernel="gqa_attn_bwd_dq", facts=facts,
        out_shape=jax.ShapeDtypeStruct((b, s, nkv * rep * hd), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            in_specs=[wide, pl.BlockSpec((None, tk, hd), kv_index),
                      pl.BlockSpec((None, tk, hd), kv_index), wide,
                      stat, stat],
            out_specs=wide,
            grid=(b, nkv, len(first), steps),
            scratch_shapes=[pltpu.VMEM((rep, tq, hd), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_BWD_VMEM),
        interpret=interpret,
    )(q.reshape(b, s, nkv * rep * hd), k.reshape(b, s, nkv * hd),
      v.reshape(b, s, nkv * hd), do.reshape(b, s, nkv * rep * hd), lse,
      delta)


def _bwd_dkv(q, k, v, do, lse, delta, window: int, tq: int, tk: int,
             interpret: bool):
    """``dk, dv [B, S, kv x hd]``: grid (row, key/value head, key tile,
    query tile), the query tile innermost."""
    b, s, nkv, rep, hd = q.shape
    scale = hd ** -0.5
    first, last = reach_of_keys(s, window, tq, tk)
    steps = int(np.max(last - first + 1))
    ragged = s % tq != 0

    def run_of(kt):
        return _key_run(kt * tk, s, window, tq, tk, jnp)

    def body(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
             dv_ref, dk_acc, dv_acc):
        kt, step = pl.program_id(2), pl.program_id(3)
        lo, hi = run_of(kt)
        qi = lo + step
        i0, j0 = qi * tq, kt * tk

        @pl.when(step == 0)
        def _start():
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

        def tile(masked: bool):
            keys, values = k_ref[...], v_ref[...]
            seen = _seen(i0, j0, s, window, tq, tk) if masked else None
            for r in range(rep):
                head = slice(r * hd, (r + 1) * hd)
                q_r, do_r = q_ref[:, head], do_ref[:, head]
                if masked and ragged:
                    # the queries past the end are summed over: zero
                    held = i0 + lax.broadcasted_iota(
                        jnp.int32, (tq, 1), 0) < s
                    q_r = jnp.where(held, q_r, jnp.zeros((), q_r.dtype))
                    do_r = jnp.where(held, do_r,
                                     jnp.zeros((), do_r.dtype))
                pt, dst = _tile_grads(
                    q_r, do_r, keys, values, lse_ref[r:r + 1, :],
                    delta_ref[r:r + 1, :], seen, scale)
                dv_acc[...] += jnp.dot(
                    pt, do_r, preferred_element_type=jnp.float32)
                dk_acc[...] += jnp.dot(
                    dst, q_r, preferred_element_type=jnp.float32)

        whole = _whole(i0, j0, s, window, tq, tk)
        live = qi <= hi
        pl.when(jnp.logical_and(live, whole))(lambda: tile(False))
        pl.when(jnp.logical_and(live, jnp.logical_not(whole)))(
            lambda: tile(True))

        @pl.when(step == steps - 1)
        def _finish():
            dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
            dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    def at(kt, step):
        lo, hi = run_of(kt)
        return jnp.minimum(lo + step, hi)

    def q_index(bi, g, kt, step):
        return bi, at(kt, step), g

    def kv_index(bi, g, kt, step):
        return bi, kt, g

    def stat_index(bi, g, kt, step):
        return bi, g, 0, at(kt, step)

    # the forward's tile pairs, seen from the keys: the guard is exact
    pairs = int(np.sum(last - first + 1))
    facts = {"b": b, "s": s, "kv": nkv, "rep": rep, "head": hd,
             "window": window, "q_tile": tq, "k_tile": tk,
             "key_tiles": pairs, "key_tiles_in_reach": pairs}
    wide = pl.BlockSpec((None, tq, rep * hd), q_index)
    stat = pl.BlockSpec((None, None, rep, tq), stat_index)
    narrow = pl.BlockSpec((None, tk, hd), kv_index)
    return kernel_call(
        body, kernel="gqa_attn_bwd_dkv", facts=facts,
        out_shape=(jax.ShapeDtypeStruct((b, s, nkv * hd), k.dtype),
                   jax.ShapeDtypeStruct((b, s, nkv * hd), v.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            in_specs=[wide, narrow, narrow, wide, stat, stat],
            out_specs=(narrow, narrow),
            grid=(b, nkv, len(first), steps),
            scratch_shapes=[pltpu.VMEM((tk, hd), jnp.float32),
                            pltpu.VMEM((tk, hd), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_BWD_VMEM),
        interpret=interpret,
    )(q.reshape(b, s, nkv * rep * hd), k.reshape(b, s, nkv * hd),
      v.reshape(b, s, nkv * hd), do.reshape(b, s, nkv * rep * hd), lse,
      delta)


def _laid_out(out, shape):
    """The forward kernel's result as the sequence form lays it out."""
    b, s, nkv, rep, hd = shape
    # The barrier holds the transposition to this dtype and place: left
    # free, XLA converts the kernel's result to the float32 of the
    # layer's gate first and moves twice the bytes (a whole layer 30.7
    # ms against 29.2 on the chip, PERF.md section 6, PR 42).
    flat = lax.optimization_barrier(
        out.transpose(0, 3, 1, 2, 4).reshape(b, s, nkv * rep * hd))
    return flat.reshape(shape)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def gqa_attention(q, k, v, window: int, q_tile: int = Q_TILE,
                  k_tile: int = K_TILE, interpret: bool = False):
    """The kernel's result as the sequence form lays it out,
    ``[B, S, kv, rep, hd]``."""
    return _laid_out(_fwd(q, k, v, window, q_tile, k_tile, interpret),
                     q.shape)


def _attention_fwd(q, k, v, window, q_tile, k_tile, interpret):
    out, lse = _fwd(q, k, v, window, q_tile, k_tile, interpret,
                    with_lse=True)
    # A layer that rematerialises may keep these two (models/lfm2.py):
    # its backward pass then runs the projections again, not this call.
    out = checkpoint_name(_laid_out(out, q.shape), "attn_out")
    return out, (q, k, v, out, checkpoint_name(lse, "attn_lse"))


def _attention_bwd(window, q_tile, k_tile, interpret, res, grad):
    q, k, v, out, lse = res
    with jax.named_scope(
            "gqa_attn_" + ("window" if window else "global")):
        grad = grad.astype(q.dtype)
        delta = jnp.sum(grad.astype(jnp.float32)
                        * out.astype(jnp.float32), axis=-1)
        delta = delta.transpose(0, 2, 3, 1)          # [B, kv, rep, S]
        args = (q, k, v, grad, lse, delta, window, q_tile, k_tile,
                interpret)
        dk, dv = _bwd_dkv(*args)
        return (_bwd_dq(*args).reshape(q.shape), dk.reshape(k.shape),
                dv.reshape(v.shape))


gqa_attention.defvjp(_attention_fwd, _attention_bwd)


# Cache rows a grid step of the decode kernel fetches (PERF.md section
# 6, PR 43 has the sweep).
ROW_TILE = 512


def decode_run(pos, live, rows: int, window: int, rt: int, xp=jnp):
    """First and last row tile of ``rt`` slots that the decode kernel
    visits for a stream at position ``pos`` (its new row written) in a
    cache of ``rows`` slots, slot ``p mod rows`` holding position p:
    the tiles between them are those that hold a slot in reach by
    ``models/lfm2.ring_positions``' rule (a position held, and inside
    the window where there is one). A cache that has not wrapped holds
    them in the slots ``max(pos - window + 1, 0) .. pos``; one that has
    wrapped holds one in every slot unless the window is shorter than
    the cache, where every tile is visited and the mask decides. A
    stream that is not ``live`` visits none (last < first)."""
    wrapped = pos >= rows
    lo = xp.maximum(pos - window + 1, 0) if window else 0 * pos
    first = xp.where(wrapped, 0, lo) // rt
    last = xp.where(wrapped, rows - 1, pos) // rt
    return first, xp.where(live, last, first - 1)


def rows_fetched(pos, live, rows: int, window: int, rt: int = 0, xp=jnp):
    """Cache rows (of keys; as many of values) the decode kernel moves
    for the streams ``pos, live [B]`` at row tiles of ``rt`` (0: the
    module's): its visited tiles' rows, the last tile cut at the
    cache's end."""
    rt = rt or ROW_TILE
    first, last = decode_run(pos, live, rows, window, rt, xp)
    return xp.sum(xp.where(
        live, xp.minimum((last + 1) * rt, rows) - first * rt, 0))


def gqa_decode(q, keys, values, pos, live, window: int,
               row_tile: int = 0, interpret: bool = False):
    """The DECODE form as ONE Pallas kernel, ``gqa_attn_decode``: one
    query position a stream, ``q [B, kv, rep, hd]``, against the
    stream's cache ``keys, values [B, R, kv, hd]`` (the new row
    written) at position ``pos [B]``; returns ``[B, kv, rep, hd]``,
    zeros for a stream that is not ``live [B]``.

    The grid is (stream, row tile), the row tile innermost. The cache
    is read where it lies, as ``[B, R x kv, hd]`` (a row's key/value
    heads are neighbouring rows of the array, so a tile is one
    contiguous piece of HBM), fetched ONCE for all ``kv x rep`` query
    heads: ``q [kv x rep, hd]`` against the tile's ``rt x kv`` rows is
    ONE product, of which a query head keeps the columns of its own
    key/value head (the others are masked like slots out of reach, so
    their probabilities are exact zeros in the second product, ``p
    [kv x rep, rt x kv] . V [rt x kv, hd]``). The MXU loads each row
    of K and V once either way; the masked columns cost exponents, not
    bandwidth. Arithmetic and precisions are ``gqa_attn_fwd``'s.

    A stream visits the tiles :func:`decode_run` gives; a step past
    them fetches nothing (its block index stays) and computes nothing,
    and a stream that is not live holds the block of the live stream
    before it, so it fetches nothing at all (with none before it, the
    grid's first block, fetched once a call). Tiles wholly in reach
    skip the reach mask. The last tile may hang over the cache's end:
    its overhang is masked and its values zeroed."""
    b, nkv, rep, hd = q.shape
    rt = row_tile or ROW_TILE
    rows, heads, cols = keys.shape[1], nkv * rep, rt * nkv
    tiles = -(-rows // rt)
    ragged = rows % rt != 0
    # a window shorter than the cache cuts a wrapped cache's reach
    binds = bool(window) and window < rows
    scale = hd ** -0.5
    lanes = 128 if cols % 128 == 0 and hd % 128 == 0 else 1
    pos = pos.astype(jnp.int32)
    first, last = decode_run(pos, live, rows, window, rt)
    # The live stream at or before each stream (its own number for a
    # live one) and that stream's last tile: the block a stream that
    # is not live holds. Before the first live stream it is the grid's
    # first block, which the pipeline fetches whatever it is.
    stream = jnp.arange(b)
    before = jnp.max(jnp.where(
        (stream[None, :] <= stream[:, None]) & live[None, :],
        stream[None, :], -1), axis=1)
    its_last = jnp.sum(jnp.where(
        stream[None, :] == before[:, None], last[None, :], 0), axis=1)
    bounds = jnp.stack([
        pos, first, last, jnp.maximum(before, 0),
        jnp.where(before >= 0, its_last, first[0])]).astype(jnp.int32)

    def body(bounds_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref):
        bi, step = pl.program_id(0), pl.program_id(1)
        at, lo, hi = bounds_ref[0, bi], bounds_ref[1, bi], bounds_ref[2, bi]
        j0 = (lo + step) * rt
        wrapped = at >= rows

        @pl.when(step == 0)
        def _start():
            m_ref[...] = jnp.full_like(m_ref, _MASKED)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        def tile(masked: bool):
            values = v_ref[...]
            scores = lax.dot_general(
                q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            # column c is slot c // kv of the tile, key/value head
            # c % kv; row h is a query head of key/value head h // rep
            col = lax.broadcasted_iota(jnp.int32, (1, cols), 1)
            row = lax.broadcasted_iota(jnp.int32, (heads, 1), 0)
            own = col % nkv == row // rep
            if masked:
                slot = j0 + col // nkv
                seen = jnp.logical_or(wrapped, slot <= at)
                if window:
                    newest = at % rows
                    age = jnp.where(slot <= newest, newest - slot,
                                    newest - slot + rows)
                    seen = jnp.logical_and(seen, age < window)
                if ragged:
                    seen = jnp.logical_and(seen, slot < rows)
                    inside = j0 + lax.broadcasted_iota(
                        jnp.int32, (cols, 1), 0) // nkv < rows
                    values = jnp.where(inside, values,
                                       jnp.zeros((), values.dtype))
                own = jnp.logical_and(own, seen)
            scores = jnp.where(own, scores * scale, _MASKED)
            m_prev = m_ref[...]
            m_next = jnp.maximum(
                m_prev, jnp.max(scores, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_next)
            p = jnp.exp(scores - _wide(m_next, cols))
            l_ref[...] = alpha * l_ref[...] + jnp.sum(
                p, axis=1, keepdims=True)
            m_ref[...] = m_next
            acc_ref[...] = _wide(alpha, hd) * acc_ref[...] + jnp.dot(
                p.astype(values.dtype), values,
                preferred_element_type=jnp.float32)

        whole = jnp.where(
            wrapped, j0 + rt <= (0 if binds else rows),
            jnp.logical_and(j0 + rt - 1 <= at,
                            j0 > at - window if window else True))
        run = lo + step <= hi
        pl.when(jnp.logical_and(run, whole))(lambda: tile(False))
        pl.when(jnp.logical_and(run, jnp.logical_not(whole)))(
            lambda: tile(True))

        @pl.when(step == tiles - 1)
        def _finish():
            # a stream that is not live has summed nothing
            out = jnp.where(hi >= lo, acc_ref[...] / _wide(l_ref[...], hd),
                            0.0).astype(o_ref.dtype)
            for g in range(nkv):
                o_ref[g] = out[g * rep:(g + 1) * rep]

    def q_index(bi, step, bounds_ref):
        return bi, 0, 0

    def kv_index(bi, step, bounds_ref):
        lo, hi = bounds_ref[1, bi], bounds_ref[2, bi]
        return (bounds_ref[3, bi],
                jnp.where(hi >= lo, jnp.minimum(lo + step, hi),
                          bounds_ref[4, bi]), 0)

    def out_index(bi, step, bounds_ref):
        return bi, 0, 0, 0

    facts = {"b": b, "rows": rows, "kv": nkv, "rep": rep, "head": hd,
             "window": window, "row_tile": rt, "row_tiles": tiles}
    return kernel_call(
        body, kernel="gqa_attn_decode", facts=facts,
        # what XLA's scheduler may count on when it places the loop's
        # other copies about the call: every row of every stream
        cost_estimate=pl.CostEstimate(
            flops=4 * b * heads * rows * nkv * hd,
            transcendentals=b * heads * rows * nkv,
            bytes_accessed=(2 * b * rows * nkv * hd + 2 * b * heads * hd)
            * q.dtype.itemsize),
        out_shape=jax.ShapeDtypeStruct((b, nkv, rep, hd), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((None, heads, hd), q_index),
                      pl.BlockSpec((None, cols, hd), kv_index),
                      pl.BlockSpec((None, cols, hd), kv_index)],
            out_specs=pl.BlockSpec((None, nkv, rep, hd), out_index),
            grid=(b, tiles),
            scratch_shapes=[pltpu.VMEM((heads, lanes), jnp.float32),
                            pltpu.VMEM((heads, lanes), jnp.float32),
                            pltpu.VMEM((heads, hd), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(bounds, q.reshape(b, heads, hd), keys.reshape(b, rows * nkv, hd),
      values.reshape(b, rows * nkv, hd))


def fits(head: int) -> bool:
    """Whether Mosaic takes the kernel's blocks: a head is whole lane
    tiles (K and V are read a head's columns at a time)."""
    return head % 128 == 0


# -- attention under a block selection ---------------------------------------
#
# A layer whose queries read a SELECTION of the cache's blocks of
# ``block`` rows (``models/lfm2.select_mask``: one selection a (query,
# key/value head)). Two kernels, arithmetic and precisions
# ``gqa_attn_fwd``'s:
#
# ``gqa_attn_select_decode``  one query a stream; the selected blocks
#     ONLY are fetched: the local window's rows as one run, the others
#     by a scalar-prefetched index list, ``per_step`` blocks a grid step
#     (each its own operand of the one cache array, so the pipeline
#     moves them side by side); the cache is head-major, ``[B, kv, R,
#     hd]``, so that a head's block is one contiguous piece of HBM;
# ``gqa_attn_select_fwd``     a whole sequence: ``gqa_attn_fwd``'s
#     tiles below the diagonal, each under its queries' own selection
#     (a ``[queries, blocks]`` map of 0 / 1 spread over the tile's keys
#     by one small product), so that no ``[S, S]`` array exists and no
#     query's selection is coarsened to its tile's.

# Selected blocks a grid step of the decode kernel fetches, each an
# operand of its own: 4, 8 and 16 read 1.22, 0.98 and 0.79 ms a step of
# 32 streams on the chip (PERF.md section 6, PR 54).
SELECT_PER_STEP = 16


def select_list(mask, length: int, first_local, per_step: int =
                SELECT_PER_STEP):
    """The decode kernel's index list from a selection ``mask [B, kv,
    NB]`` (bool): the selected blocks BEFORE the local window (block
    ``first_local [B]`` and after are read as one run of rows) in
    ascending order, ``[B, kv, length]`` int32, and how many they are
    ``[B, kv]``. An entry past the count repeats the entry ``per_step``
    before it (the same operand of the grid step before: nothing is
    fetched for it). No sort: a block's rank is the count of selected
    blocks before it."""
    nb = mask.shape[-1]
    block = jnp.arange(nb)
    mask = mask & (block < first_local[:, None, None])
    rank = jnp.cumsum(mask, axis=-1) - 1
    count = jnp.minimum(jnp.sum(mask, axis=-1), length).astype(jnp.int32)
    at = (rank[..., None, :] == jnp.arange(length)[:, None]) \
        & mask[..., None, :]                               # [B, kv, L, NB]
    order = jnp.sum(jnp.where(at, block, 0), axis=-1).astype(jnp.int32)
    p = jnp.arange(length)
    back = per_step * ((p - count[..., None]) // per_step + 1)
    src = jnp.maximum(jnp.where(p < count[..., None], p, p - back), 0)
    return jnp.take_along_axis(order, src, axis=-1), count


def gqa_select_decode(q, keys, values, idx, count, pos, first_row, live,
                      block: int, window: int,
                      per_step: int = SELECT_PER_STEP,
                      interpret: bool = False):
    """The DECODE form under a selection as ONE Pallas kernel,
    ``gqa_attn_select_decode``: ``q [B, kv, rep, hd]`` at row ``pos
    [B]`` against the stream's cache ``keys, values [B, kv, R, hd]``
    (head-major: a head's block of rows is one contiguous piece of HBM;
    the new row written): the rows ``first_row .. pos`` (the local
    window: at most ``window`` rows, fetched as ONE run) and the blocks
    ``idx [B, kv, L]`` before them (``count [B, kv]`` of them valid,
    :func:`select_list`), fetched by index. Returns ``[B, kv, rep,
    hd]``, zeros for a stream that is not ``live [B]``.

    The grid is (key/value head, stream, list step), the list
    innermost. The first step of a (head, stream) takes the window's
    rows (an element-offset block: the run starts at any row); every
    step takes ``per_step`` list entries, each a block ``[block, hd]``
    of K and of V through an operand of its own, in one product of the
    head's ``rep`` queries against their ``per_step x block`` rows. A
    step past the count fetches nothing and computes nothing; a stream
    that is not live holds the blocks of the live stream before it."""
    b, nkv, rep, hd = q.shape
    rows, length, n = keys.shape[2], idx.shape[-1], per_step
    if length % n or rows % block:
        raise ValueError(
            f"a list of {length} is not whole steps of {n}, or {rows} "
            f"cache rows are not whole blocks of {block}")
    steps, cols = length // n, n * block
    wide = min(window, rows)                   # the run's rows, as fetched
    scale = hd ** -0.5
    lanes = 128 if cols % 128 == 0 and wide % 128 == 0 \
        and hd % 128 == 0 else 1
    pos = pos.astype(jnp.int32)
    # Where the run is fetched from: its first row, or earlier where it
    # would hang over the cache's end (the mask knows the rows).
    start = jnp.clip(first_row, 0, rows - wide).astype(jnp.int32)
    # The live stream at or before each stream: what one that is not
    # live holds, so that it moves nothing (before the first live one,
    # the grid's first blocks, fetched whatever they are).
    stream = jnp.arange(b)
    before = jnp.max(jnp.where(
        (stream[None, :] <= stream[:, None]) & live[None, :],
        stream[None, :], -1), axis=1)
    held = jnp.maximum(before, 0)
    # ... and of its list the entries its operands held last
    last = jnp.tile(idx[..., -n:], (1, 1, steps))
    idx = jnp.where(live[:, None, None], idx, last[held])
    count = jnp.where(live[:, None], count, 0)
    idx = jnp.moveaxis(idx, 1, 0).reshape(nkv * b, length).astype(jnp.int32)
    bounds = jnp.stack([
        jnp.moveaxis(count, 1, 0).reshape(nkv * b),
        jnp.tile(pos, nkv), jnp.tile(first_row.astype(jnp.int32), nkv),
        jnp.tile(start[held], nkv), jnp.tile(held, nkv),
        jnp.tile(live.astype(jnp.int32), nkv)]).astype(jnp.int32)

    def body(idx_ref, bounds_ref, q_ref, kw_ref, vw_ref, *rest):
        k_refs, v_refs = rest[:n], rest[n:2 * n]
        o_ref, m_ref, l_ref, acc_ref = rest[2 * n:]
        at = pl.program_id(0) * b + pl.program_id(1)
        step = pl.program_id(2)
        held_, t = bounds_ref[0, at], bounds_ref[1, at]
        is_live = bounds_ref[5, at] == 1

        def update(scores, seen, v):
            width = scores.shape[1]
            scores = jnp.where(seen, scores * scale, _MASKED)
            m_prev = m_ref[...]
            m_next = jnp.maximum(
                m_prev, jnp.max(scores, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_next)
            p = jnp.where(seen, jnp.exp(scores - _wide(m_next, width)), 0.0)
            l_ref[...] = alpha * l_ref[...] + jnp.sum(
                p, axis=1, keepdims=True)
            m_ref[...] = m_next
            acc_ref[...] = _wide(alpha, hd) * acc_ref[...] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)

        @pl.when(step == 0)
        def _start():
            m_ref[...] = jnp.full_like(m_ref, _MASKED)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(jnp.logical_and(step == 0, is_live))
        def _window():
            row = bounds_ref[3, at] + lax.broadcasted_iota(
                jnp.int32, (1, wide), 1)
            seen = jnp.logical_and(row >= bounds_ref[2, at], row <= t)
            update(lax.dot_general(
                q_ref[...], kw_ref[0, 0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32), seen, vw_ref[0, 0])

        @pl.when(step * n < held_)
        def _blocks():
            k = jnp.concatenate([r[...] for r in k_refs], axis=0)
            v = jnp.concatenate([r[...] for r in v_refs], axis=0)
            # column c is row c % block of the list's entry c // block
            col = lax.broadcasted_iota(jnp.int32, (1, cols), 1)
            entry = col // block
            first = jnp.zeros((1, cols), jnp.int32)
            for i in range(n):
                first = jnp.where(entry == i,
                                  idx_ref[at, step * n + i] * block, first)
            seen = jnp.logical_and(step * n + entry < held_,
                                   first + col % block <= t)
            update(lax.dot_general(
                q_ref[...], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32), seen, v)

        @pl.when(step == steps - 1)
        def _finish():
            o_ref[...] = jnp.where(
                is_live, acc_ref[...] / _wide(
                    jnp.maximum(l_ref[...], 1e-30), hd),
                0.0).astype(o_ref.dtype)

    def q_index(g, bi, step, idx_ref, bounds_ref):
        return bi, g, 0, 0

    def window_index(g, bi, step, idx_ref, bounds_ref):
        at = g * b + bi
        # the run starts at a block's first row (the cache is whole
        # blocks): Mosaic has to know it lies on a tile of rows
        return (bounds_ref[4, at], g,
                pl.multiple_of(bounds_ref[3, at], block), 0)

    def block_index(i):
        def index(g, bi, step, idx_ref, bounds_ref):
            at = g * b + bi
            return (bounds_ref[4, at], g, idx_ref[at, step * n + i], 0, 0)
        return index

    # (Mosaic takes element offsets in every dimension of a block or
    # in none: the run's block is [1, 1, wide, hd] at (stream, head,
    # first row, 0))
    run = pl.BlockSpec((pl.Element(1), pl.Element(1), pl.Element(wide),
                        pl.Element(hd)), window_index)
    blocks = [pl.BlockSpec((None, None, None, block, hd), block_index(i))
              for i in range(n)]
    facts = {"b": b, "rows": rows, "kv": nkv, "rep": rep, "head": hd,
             "block": block, "window": wide, "list": length, "per_step": n}
    tiled = tuple(c.reshape(b, nkv, rows // block, block, hd)
                  for c in (keys, values))
    return kernel_call(
        body, kernel="gqa_attn_select_decode", facts=facts,
        cost_estimate=pl.CostEstimate(
            flops=4 * b * nkv * rep * (length * block + wide) * hd,
            transcendentals=b * nkv * rep * (length * block + wide),
            bytes_accessed=(2 * b * nkv * (length * block + wide) * hd
                            + 2 * b * nkv * rep * hd) * q.dtype.itemsize),
        out_shape=jax.ShapeDtypeStruct((b, nkv, rep, hd), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[pl.BlockSpec((None, None, rep, hd), q_index),
                      run, run] + blocks + blocks,
            out_specs=pl.BlockSpec((None, None, rep, hd), q_index),
            grid=(nkv, b, steps),
            scratch_shapes=[pltpu.VMEM((rep, lanes), jnp.float32),
                            pltpu.VMEM((rep, lanes), jnp.float32),
                            pltpu.VMEM((rep, hd), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(idx, bounds, q, keys, values, *([tiled[0]] * n), *([tiled[1]] * n))


# Queries of one head and keys a tile of the sequence form under a
# selection: a key tile is ``K_TILE / block`` blocks.
SELECT_Q_TILE = 256
_SELECT_VMEM = 64 * 1024 * 1024


def gqa_select_attention(q, k, v, sel, block: int,
                         q_tile: int = SELECT_Q_TILE, k_tile: int = K_TILE,
                         interpret: bool = False):
    """The SEQUENCE form under a selection as ONE Pallas kernel,
    ``gqa_attn_select_fwd``: ``q [B, S, kv, rep, hd]``, ``k, v [B, S,
    kv, hd]``, ``sel [B, kv, S, NB]`` (1 where query i of key/value
    head g reads block j of ``block`` rows, else 0; block 0 is read by
    every query); query i attends to the keys ``j <= i`` of its
    selected blocks. Returns ``[B, S, kv, rep, hd]``.

    ``gqa_attn_fwd``'s grid and tiles without a window: (row, key/value
    head, query tile, key tile). A tile's mask is its queries' rows of
    ``sel`` at the tile's ``k_tile / block`` blocks, spread over the
    keys by a product with a 0 / 1 matrix (``[q_tile, 128] x [128,
    k_tile]``: a few per cent of the tile's work), and the causal rule.
    Every tile at or below the diagonal is computed: a later change may
    skip the tiles no query of which selects a block."""
    b, s, nkv, rep, hd = q.shape
    tq, tk = q_tile, k_tile
    per = tk // block                       # blocks a key tile
    if tk % block or 128 % per:
        raise ValueError(f"key tiles of {tk} are not whole blocks of "
                         f"{block}, {per} of which must divide 128")
    nb = sel.shape[-1]
    sel = jnp.pad(sel.astype(q.dtype),
                  [(0, 0)] * 3 + [(0, -nb % 128)])
    scale = hd ** -0.5
    first, last, _, _ = reach(s, 0, tq, tk)
    steps = int(np.max(last - first + 1))
    ragged = s % tk != 0
    lanes = 128 if tk % 128 == 0 and hd % 128 == 0 else 1

    def last_of(qi):
        return (jnp.minimum(qi * tq + tq, s) - 1) // tk

    def body(q_ref, k_ref, v_ref, sel_ref, o_ref, m_ref, l_ref, acc_ref):
        qi, step = pl.program_id(2), pl.program_id(3)
        i0, j0 = qi * tq, step * tk

        @pl.when(step == 0)
        def _start():
            m_ref[...] = jnp.full_like(m_ref, _MASKED)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(step <= last_of(qi))
        def _tile():
            keys, values = k_ref[...], v_ref[...]
            at = i0 + lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
            col = lax.broadcasted_iota(jnp.int32, (1, tk), 1)
            # the tile's blocks among the 128 the map's tile holds
            spread = lax.broadcasted_iota(jnp.int32, (128, 1), 0) \
                == (step * per) % 128 + col // block
            chosen = jnp.dot(sel_ref[...], spread.astype(sel_ref.dtype),
                             preferred_element_type=jnp.float32)
            seen = jnp.logical_and(chosen > 0.5, j0 + col <= at)
            if ragged:
                held = j0 + lax.broadcasted_iota(
                    jnp.int32, (tk, 1), 0) < s
                values = jnp.where(held, values,
                                   jnp.zeros((), values.dtype))
            for r in range(rep):
                scores = lax.dot_general(
                    q_ref[:, r * hd:(r + 1) * hd], keys,
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                scores = jnp.where(seen, scores, _MASKED)
                m_prev = m_ref[r]
                m_next = jnp.maximum(
                    m_prev, jnp.max(scores, axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_next)
                p = jnp.where(seen, jnp.exp(scores - _wide(m_next, tk)),
                              0.0)
                l_ref[r] = alpha * l_ref[r] + jnp.sum(
                    p, axis=1, keepdims=True)
                m_ref[r] = m_next
                acc_ref[r] = _wide(alpha, hd) * acc_ref[r] + jnp.dot(
                    p.astype(values.dtype), values,
                    preferred_element_type=jnp.float32)

        @pl.when(step == steps - 1)
        def _finish():
            for r in range(rep):
                o_ref[r] = (acc_ref[r] / _wide(
                    jnp.maximum(l_ref[r], 1e-30), hd)).astype(o_ref.dtype)

    def q_index(bi, g, qi, step):
        return bi, qi, g

    def kv_index(bi, g, qi, step):
        return bi, jnp.minimum(step, last_of(qi)), g

    def sel_index(bi, g, qi, step):
        return bi, g, qi, jnp.minimum(step, last_of(qi)) * per // 128

    def out_index(bi, g, qi, step):
        return bi, g, 0, qi, 0

    facts = {"b": b, "s": s, "kv": nkv, "rep": rep, "head": hd,
             "block": block, "q_tile": tq, "k_tile": tk,
             "key_tiles": int(np.sum(last - first + 1))}
    out = kernel_call(
        body, kernel="gqa_attn_select_fwd", facts=facts,
        out_shape=jax.ShapeDtypeStruct((b, nkv, rep, s, hd), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            in_specs=[pl.BlockSpec((None, tq, rep * hd), q_index),
                      pl.BlockSpec((None, tk, hd), kv_index),
                      pl.BlockSpec((None, tk, hd), kv_index),
                      pl.BlockSpec((None, None, tq, 128), sel_index)],
            out_specs=pl.BlockSpec((None, None, rep, tq, hd), out_index),
            grid=(b, nkv, len(first), steps),
            scratch_shapes=[pltpu.VMEM((rep, tq, lanes), jnp.float32),
                            pltpu.VMEM((rep, tq, lanes), jnp.float32),
                            pltpu.VMEM((rep, tq, hd), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_SELECT_VMEM),
        interpret=interpret,
    )(q.reshape(b, s, nkv * rep * hd), k.reshape(b, s, nkv * hd),
      v.reshape(b, s, nkv * hd), sel)
    return _laid_out(out, q.shape)
