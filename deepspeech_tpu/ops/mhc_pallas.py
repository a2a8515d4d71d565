"""The passes of a sub-layer's hyper-connection over the residual
streams (``models/mhc.py``) as two Pallas kernels.

The streams of N positions are ``X [N, n, D]``; a kernel sees them as
``[N, n * D]``, a tile of positions a grid step: positions down the
sublanes, stream i the lanes ``i * D .. (i + 1) * D`` (D is whole lane
tiles, :func:`fits`), so a tile in VMEM is as large as it is in HBM.

``mhc_read``   fetches a tile ONCE and gives, in float32 as
    ``HyperConnection`` does: the mean square over the ``n * D``
    values; ``raw = (gain * phi)^T @ x^T`` with the POSITIONS ON THE
    LANES of the result, ``[n * (n + 2), tile]``, where the sigmoids
    and every Sinkhorn round then run whole vector registers at a time
    (the rounds are :func:`models.mhc.sinkhorn_round`'s, divisions
    exact); and, from the same resident tile, the sub-layer's input
    ``sum_i H_pre[i] X[i]``. Results: the coefficients ``[N, n * (n +
    2)]`` float32 (``H_pre``, ``H_post``, ``H_res`` row by row: the
    columns of ``phi``) and the mix ``[N, D]`` in the streams' dtype.
    The product keeps float32's precision: bfloat16 streams are exact
    operands, so ``gain * phi`` goes in as three bfloat16 parts (the
    high, middle and low eight bits of its mantissa), stacked as the
    rows of ONE matrix, one pass of the matrix unit, and the three
    results are summed in float32 from the smallest up; float32
    streams multiply at ``Precision.HIGHEST``.
``mhc_write``  fetches the tile, the sub-layer's output ``y [N, D]``
    and the tile's coefficients and writes ``H_res @ X + H_post (outer)
    y``, accumulated in float32 in :func:`models.mhc.write`'s order,
    ONCE, in the streams' dtype.

No float32 array of the streams' size leaves either kernel. Inside, a
tile is worked a sublane tile of positions and one lane tile at a time,
so that the chain from the loaded streams to the stored result stays in
vector registers; ``mhc_write``'s 20 coefficients a position are spread
along the lanes ONCE a tile into a VMEM table and fetched from there
like the streams (held in registers across a group of positions they
are 40 of the 64, and the compiler's spills took the one store slot:
1,108 bundles a group of 16 positions for 685, in its own schedule).

A build lies in its facts (``ops/kernel_id.py``); positions a tile and
the scoped VMEM a call asks for follow from its own shapes
(:func:`tile_rows`, :func:`_vmem_limit`).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.impl import on_tpu
from .kernel_id import kernel_call

# Positions a tile come in whole lane tiles: the coefficient path runs
# with them on the lanes.
_POSITIONS = 128
# Bytes of streams a grid step fetches, about: at xing4_29b_a4b's four
# bfloat16 streams of 3,584 that is 128 positions, 3.67 MB (the pipeline
# holds it twice, and ``mhc_write`` its result twice more).
_TILE_BYTES = 4 << 20


def fits(n: int, d: int) -> bool:
    """Whether Mosaic takes the kernels' blocks: several streams, each
    whole lane tiles, their coefficients one lane tile at most."""
    return n > 1 and n * (n + 2) <= 128 and d % 128 == 0


def in_kernels(n: int, d: int) -> bool:
    """Whether the hyper-connections run as the kernels: on a TPU, at
    sizes their blocks take."""
    return on_tpu() and fits(n, d)


def tile_rows(rows: int, n: int, d: int, itemsize: int) -> int:
    """Positions a grid step of either kernel takes for ``rows``
    positions of ``n`` streams of ``d``: whole lane tiles of them,
    about :data:`_TILE_BYTES` of streams, and no more tiles than hold a
    position."""
    room = max(1, _TILE_BYTES // (n * d * itemsize * _POSITIONS))
    return _POSITIONS * min(room, pl.cdiv(rows, _POSITIONS))


def _vmem_limit(blocks: int, temporaries: int) -> int:
    """The scoped VMEM a call asks for: its blocks twice (the pipeline
    double-buffers them), its scratches and the float32 values a group
    of positions is worked in, a quarter on top, in whole MiB and never
    under Mosaic's default of 16."""
    need = 5 * (2 * blocks + temporaries) // 4
    return max(16 << 20, pl.cdiv(need, 1 << 20) << 20)


def _group(dtype) -> int:
    """Positions a sublane tile of ``dtype`` holds."""
    return 32 // jnp.dtype(dtype).itemsize


def _rows(a, width: int, short: int):
    """``a [..., width]`` as rows ``[positions + short, width]``."""
    a = a.reshape(-1, width)
    return jnp.pad(a, ((0, short), (0, 0))) if short else a


def _in_hbm(streams, interpret: bool):
    """The streams as a kernel's operand, held to HBM. Left to XLA's
    memory-space assignment a decode step's streams (14.7 MB at
    xing4_29b_a4b's 512 positions) go into VMEM, and the loop's latent
    attention, whose operands lay there, runs 1.1 ms a step slower for
    the 0.5 ms the kernels gain by it (PERF.md section 6, PR 52). The
    CPU's interpreter knows no memory spaces."""
    return streams if interpret else pltpu.with_memory_space_constraint(
        streams, pltpu.HBM)


def _tiles(x):
    """For streams ``x [..., n, D]``: their positions, the positions a
    grid step takes and how many rows short of ONE tile the positions
    fall (they are then padded up to it, a few rows; a ragged last tile
    of several is the pipeline's)."""
    n, d = x.shape[-2:]
    if not fits(n, d):
        raise ValueError(
            f"the hyper-connection kernels take several streams of "
            f"whole lane tiles, not {n} of {d}")
    rows = x.size // (n * d)
    tile = tile_rows(rows, n, d, x.dtype.itemsize)
    return rows, tile, max(tile - rows, 0)


def _high_bits(a):
    """float32 ``a`` with the low 16 bits of its pattern cleared: the
    part of it a bfloat16 holds exactly. Cut in the bit pattern, not by
    a cast to bfloat16 and back: XLA may keep excess precision through
    such a pair of casts (on a TPU it does), and the three parts would
    then be one rounded part and two zeros."""
    bits = lax.bitcast_convert_type(a, jnp.uint32) & jnp.uint32(0xFFFF0000)
    return lax.bitcast_convert_type(bits, jnp.float32)


def _phi_rows(gain, phi, dtype):
    """``gain * phi`` transposed, ``[rows, n * D]`` with the
    coefficients' count padded to whole float32 sublane tiles (the
    product's result is cut there), as the matrix unit takes it beside
    streams of ``dtype``, and how many stacked parts that is: bfloat16
    streams are exact operands, so the float32 matrix goes in as three
    bfloat16 parts, the high, middle and low eight bits of its
    mantissa, whose sum it is exactly (72 rows at four streams: the
    matrix unit's time follows them); any other dtype multiplies in
    float32."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    folded = (gain.astype(f32)[:, None] * phi.astype(f32)).T
    folded = jnp.pad(folded, ((0, -folded.shape[0] % 8), (0, 0)))
    if dtype != bf16:
        return folded, 1
    high = _high_bits(folded)
    middle = _high_bits(folded - high)
    low = (folded - high) - middle
    return jnp.concatenate([high, middle, low]).astype(bf16), 3


# Both are jitted so that a program's 16 sub-layers, whose calls have
# one set of shapes, are traced and lowered as ONE function.
@partial(jax.jit, static_argnames=("norm_eps", "clamp", "iters", "eps",
                                   "interpret"))
def read(x, gain, phi, alpha, bias, *, norm_eps: float, clamp, iters: int,
         eps: float, interpret: bool = False):
    """The coefficients of ``HyperConnection`` and the read mix, one
    pass over the streams ``x [..., n, D]``: ``(coefficients [..., n *
    (n + 2)] float32, mix [..., D])``. ``gain [n * D]``, ``phi [n * D,
    n * (n + 2)]``, ``alpha [3]`` and ``bias [n * (n + 2)]`` are the
    module's parameters."""
    n, d = x.shape[-2:]
    lead = x.shape[:-2]
    k, count = n * d, n * (n + 2)
    f32 = jnp.float32
    rows, tile, short = _tiles(x)
    flat = _in_hbm(_rows(x, k, short), interpret)
    weights, parts = _phi_rows(gain, phi, x.dtype)
    padded = weights.shape[0] // parts
    scale = alpha.astype(f32)[np.repeat(np.arange(3), [n, n, n * n])]
    affine = jnp.pad(jnp.stack([scale, bias.astype(f32)], axis=1),
                     ((0, padded - count), (0, 0)))
    group = _group(x.dtype)
    lo, hi = clamp
    precision = None if parts == 3 else lax.Precision.HIGHEST

    def body(x_ref, w_ref, affine_ref, coef_ref, mix_ref, sq_ref, flip_ref,
             cols_ref):
        # raw [padded, tile]: positions on the lanes
        raw = None
        for i in range(n):
            lanes = slice(i * d, (i + 1) * d)
            part = lax.dot_general(
                w_ref[:, lanes], x_ref[:, lanes], (((1,), (1,)), ((), ())),
                precision=precision, preferred_element_type=f32)
            raw = part if raw is None else raw + part
        if parts == 3:  # from the smallest part up
            raw = (raw[2 * padded:] + raw[padded:2 * padded]) + raw[:padded]

        # The loops over a group's lane tiles are unrolled where they
        # are LOWERED (``unroll=True``: constant offsets, the schedule
        # of straight-line code) and not where they are traced: a body
        # a lane tile keeps the kernel's jaxpr at some hundred
        # equations for 4,000, and a program traces 16 sub-layers.

        # the squares, lane tile onto lane tile: [tile, 128] partial
        # sums, a chain of additions a stream (independent: they overlap)
        def squares(g, _):
            at = pl.ds(pl.multiple_of(g * group, group), group)

            def lane_tile(c, sums):
                out = []
                for i, total in enumerate(sums):
                    v = x_ref[at, pl.ds(i * d + c * 128, 128)].astype(f32)
                    out.append(total + v * v)
                return tuple(out)

            sums = lax.fori_loop(
                0, d // 128, lane_tile,
                (jnp.zeros((group, 128), f32),) * n, unroll=True)
            sq_ref[at, :] = sum(sums[1:], sums[0])
            return _

        lax.fori_loop(0, tile // group, squares, None)
        mean = jnp.sum(sq_ref[...].T, axis=0, keepdims=True) / k
        inv = lax.rsqrt(mean + norm_eps)                     # [1, tile]
        logits = (raw * inv) * affine_ref[:, 0:1] + affine_ref[:, 1:2]
        flip_ref[0:padded, :] = logits
        pre = jax.nn.sigmoid(flip_ref[0:n, :])
        post = 2.0 * jax.nn.sigmoid(flip_ref[n:2 * n, :])
        # H_res: row i is [n, tile], its columns down the sublanes
        res = [jnp.exp(jnp.clip(
            flip_ref[2 * n + i * n:2 * n + (i + 1) * n, :], lo, hi))
            for i in range(n)]

        def sinkhorn_round(_, m):
            m = list(m)
            for i in range(n):
                total = m[i][0:1]
                for j in range(1, n):
                    total = total + m[i][j:j + 1]
                m[i] = m[i] / (total + eps)
            total = m[0]
            for i in range(1, n):
                total = total + m[i]
            total = total + eps
            return tuple(r / total for r in m)

        res = lax.fori_loop(0, iters, sinkhorn_round, tuple(res))
        flip_ref[0:n, :] = pre
        flip_ref[n:2 * n, :] = post
        for i in range(n):
            flip_ref[2 * n + i * n:2 * n + (i + 1) * n, :] = res[i]
        # positions back down the sublanes: [tile, 128]
        cols_ref[...] = flip_ref[...].T
        coef_ref[...] = cols_ref[:, 0:count]

        def mix(g, _):
            at = pl.ds(pl.multiple_of(g * group, group), group)
            pre = [jnp.broadcast_to(cols_ref[at, i:i + 1], (group, 128))
                   for i in range(n)]

            def lane_tile(c, _):
                total = None
                for i in range(n):
                    v = pre[i] * x_ref[
                        at, pl.ds(i * d + c * 128, 128)].astype(f32)
                    total = v if total is None else total + v
                mix_ref[at, pl.ds(c * 128, 128)] = total.astype(
                    mix_ref.dtype)
                return _

            return lax.fori_loop(0, d // 128, lane_tile, None, unroll=True)

        lax.fori_loop(0, tile // group, mix, None)

    size = x.dtype.itemsize
    blocks = (tile * k * size + weights.size * weights.dtype.itemsize
              + tile * d * size + 2 * tile * 128 * 4)
    scratches = 3 * tile * 128 * 4
    facts = {"n": n, "d": d, "rows": rows, "tile": tile,
             "dtype": x.dtype.name}
    coef, mixed = kernel_call(
        body, kernel="mhc_read", facts=facts,
        # what XLA's scheduler may count on when it places the other
        # copies of a program about the call
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * k * (parts * padded + 2),
            transcendentals=rows * (count + 2 * n),
            bytes_accessed=(rows * (k + d) * size + rows * count * 4
                            + weights.size * weights.dtype.itemsize)),
        out_shape=(jax.ShapeDtypeStruct((rows + short, count), f32),
                   jax.ShapeDtypeStruct((rows + short, d), x.dtype)),
        grid=(pl.cdiv(rows + short, tile),),
        in_specs=[pl.BlockSpec((tile, k), lambda t: (t, 0)),
                  pl.BlockSpec(weights.shape, lambda t: (0, 0)),
                  pl.BlockSpec(affine.shape, lambda t: (0, 0))],
        out_specs=(pl.BlockSpec((tile, count), lambda t: (t, 0)),
                   pl.BlockSpec((tile, d), lambda t: (t, 0))),
        scratch_shapes=[pltpu.VMEM((tile, 128), f32),
                        pltpu.VMEM((128, tile), f32),
                        pltpu.VMEM((tile, 128), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_vmem_limit(
                blocks, scratches + 4 * parts * padded * tile * 4)),
        interpret=interpret,
    )(flat, weights, affine)
    if short:
        coef, mixed = coef[:rows], mixed[:rows]
    return coef.reshape(lead + (count,)), mixed.reshape(lead + (d,))


@partial(jax.jit, static_argnames=("interpret",))
def write(x, y, coef, *, interpret: bool = False):
    """``H_res @ X + H_post (outer) y``: the streams ``x [..., n, D]``
    after a sub-layer whose output is ``y [..., D]``, by the
    coefficients ``coef [..., n * (n + 2)]`` :func:`read` gave."""
    n, d = x.shape[-2:]
    k, count = n * d, n * (n + 2)
    f32 = jnp.float32
    rows, tile, short = _tiles(x)
    flat = _in_hbm(_rows(x, k, short), interpret)
    group = _group(x.dtype)

    def body(x_ref, y_ref, coef_ref, out_ref, wide_ref):
        # every coefficient of the tile spread along the lanes, once:
        # the chains below fetch them as they fetch the streams
        for c in range(n, count):
            wide_ref[c - n] = jnp.broadcast_to(coef_ref[:, c:c + 1],
                                               (tile, 128))

        def rows_of(g, _):
            at = pl.ds(pl.multiple_of(g * group, group), group)

            def lane_tile(c, _):
                xs = [x_ref[at, pl.ds(j * d + c * 128, 128)].astype(f32)
                      for j in range(n)]
                out = y_ref[at, pl.ds(c * 128, 128)].astype(f32)
                for i in range(n):
                    res = [wide_ref[n + i * n + j, at, :] for j in range(n)]
                    total = res[0] * xs[0]
                    for j in range(1, n):
                        total = total + res[j] * xs[j]
                    out_ref[at, pl.ds(i * d + c * 128, 128)] = (
                        total + wide_ref[i, at, :] * out
                    ).astype(out_ref.dtype)
                return _

            return lax.fori_loop(0, d // 128, lane_tile, None, unroll=True)

        lax.fori_loop(0, tile // group, rows_of, None)

    size = x.dtype.itemsize
    facts = {"n": n, "d": d, "rows": rows, "tile": tile,
             "dtype": x.dtype.name}
    out = kernel_call(
        body, kernel="mhc_write", facts=facts,
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * k * (n + 1), transcendentals=0,
            bytes_accessed=rows * (2 * k + d) * size + rows * count * 4),
        out_shape=jax.ShapeDtypeStruct(flat.shape, x.dtype),
        grid=(pl.cdiv(flat.shape[0], tile),),
        in_specs=[pl.BlockSpec((tile, k), lambda t: (t, 0)),
                  pl.BlockSpec((tile, d), lambda t: (t, 0)),
                  pl.BlockSpec((tile, count), lambda t: (t, 0))],
        out_specs=pl.BlockSpec((tile, k), lambda t: (t, 0)),
        scratch_shapes=[pltpu.VMEM((count - n, tile, 128), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_vmem_limit(
                2 * tile * k * size + tile * d * size + tile * 128 * 4,
                (count - n) * tile * 128 * 4)),
        interpret=interpret,
    )(flat, _rows(y, d, short), _rows(coef, count, short))
    return (out[:rows] if short else out).reshape(x.shape)
