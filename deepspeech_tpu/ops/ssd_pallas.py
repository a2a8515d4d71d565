"""The state-space recurrence of a Mamba-2 mixer (state-space duality,
arXiv:2405.21060), as two Pallas kernels and their plain oracles.

Head h of a stream holds a state ``h_t [N, P]`` (N the state size, P
the head's size), float32, zero before position 0:

    h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T        y_t = C_t h_t + D x_t

with ``x_t [P]`` the head's input, ``dt_t > 0`` its step, ``A < 0`` and
``D`` one number a head, and ``B_t, C_t [N]`` shared by the heads of a
group (head h reads group ``h // (heads / groups)``). The state is kept
``[N, P]``, the transpose of the equations' ``[P, N]``: both kernels
then read ``x`` along the lanes and B and C down the sublanes, their
outputs ``[., P]`` are whole lane rows, and the chunk's carry is a
plain product ``C h``.

``ssd_chunk_scan``  the SEQUENCE form (prefill): chunks of ``chunk``
    positions; inside a chunk the outputs are matrix products (``(C
    B^T o L) x`` with L the decays between two positions of the chunk),
    the state is carried across the chunks of a (stream, head) in VMEM
    and given out after the last one. A padded position has ``dt = 0``:
    it leaves the state alone, so the state given out is the one after
    the stream's last VALID position. The grid is (stream, head,
    chunk), the chunk innermost.
``ssd_state_step``  the DECODE form: one position a stream; the
    stream's state is read once and written once IN PLACE
    (``input_output_aliases``), a group's heads a grid step. A stream
    that is not live moves nothing: it holds the block of the live
    stream before it (as ``gqa_attn_decode`` does) and its output is
    zeros.

A LINEAR-ATTENTION layer with a constant decay a head is the same
recurrence (``x = v``, ``B = k``, ``C = q / sqrt(head)``, ``dt = 1``,
``A = -slope``, no skip: ``d`` None) with as many groups as heads; its
step then takes all groups in one grid step (``group_block``,
:func:`state_step`), a group's block being one head's 64 KB.

Off the TPU both run as the plain forms below them (``lax.scan`` over
the positions; one update), which are also the kernels' oracles. A
build lies in its facts (``ops/kernel_id.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.impl import on_tpu
from .kernel_id import kernel_call

def fits(head: int, state: int) -> bool:
    """Whether Mosaic takes the kernels' blocks: a head is whole lane
    tiles and the state whole sublane tiles."""
    return head % 128 == 0 and state % 8 == 0


def in_kernels(head: int, state: int) -> bool:
    """Whether the recurrence runs as the kernels: on a TPU, at sizes
    their blocks take."""
    return on_tpu() and fits(head, state)


def _column(row):
    """A row ``[1, n]`` as a column ``[n, 1]`` without a relayout: the
    diagonal of its broadcast, summed along the lanes."""
    n = row.shape[1]
    eye = lax.broadcasted_iota(jnp.int32, (n, n), 0) \
        == lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.sum(jnp.where(eye, jnp.broadcast_to(row, (n, n)), 0.0),
                   axis=1, keepdims=True)


# -- the sequence form -------------------------------------------------------

def scan_oracle(x, dt, a, bm, cm, d, valid):
    """The recurrence position by position (``lax.scan``), float32:
    ``x [B, S, H, P]``, ``dt [B, S, H]``, ``a, d [H]`` (``d`` None: no
    skip), ``bm, cm [B, S, G, N]``, ``valid [B, S]``. Returns ``y [B, S, H, P]`` in ``x``'s
    dtype and the state after each stream's last valid position ``[B,
    H, N, P]`` float32."""
    b, s, h, p = x.shape
    g, n = bm.shape[2:]
    f32 = jnp.float32
    dt = jnp.where(valid[..., None], dt.astype(f32), 0.0)
    bm = jnp.repeat(bm.astype(f32), h // g, axis=2)       # [B, S, H, N]
    cm = jnp.repeat(cm.astype(f32), h // g, axis=2)

    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * b_t)[..., :, None] * x_t[..., None, :]
        return state, jnp.sum(state * c_t[..., :, None], axis=-2)

    state, y = lax.scan(
        step, jnp.zeros((b, h, n, p), f32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (x.astype(f32), dt, bm, cm)))
    y = jnp.moveaxis(y, 0, 1)
    if d is not None:
        y = y + d[:, None] * x.astype(f32)
    return y.astype(x.dtype), state


def chunk_scan(x, dt, a, bm, cm, d, valid, chunk: int = 128,
               interpret: bool = False):
    """:func:`scan_oracle` as the kernel ``ssd_chunk_scan``, ``chunk``
    positions a chunk."""
    b, s, h, p = x.shape
    g, n = bm.shape[2:]
    q = chunk
    chunks = -(-s // q)
    pad = chunks * q - s
    f32 = jnp.float32
    dt = jnp.where(valid[..., None], dt.astype(f32), 0.0)
    if pad:
        x, dt, bm, cm = (jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (
            v.ndim - 2)) for v in (x, dt, bm, cm))
    # Per (stream, head, chunk) three rows over the chunk's positions:
    # the decay's logarithm summed up to and with each position, dt,
    # and the decay from each position to the chunk's end; and the
    # whole chunk's decay, along a head's lanes.
    upto = jnp.cumsum((dt * a).reshape(b, chunks, q, h), axis=2)
    rows = jnp.stack([upto, dt.reshape(b, chunks, q, h),
                      jnp.exp(upto[:, :, -1:] - upto)], axis=2)
    rows = jnp.transpose(rows, (0, 4, 1, 2, 3))          # [B, H, C, 3, Q]
    whole = jnp.broadcast_to(jnp.exp(jnp.transpose(
        upto[:, :, -1], (0, 2, 1)))[..., None, None], (b, h, chunks, 1, p))

    def body(rows_ref, whole_ref, x_ref, b_ref, c_ref, y_ref, out_ref,
             state_ref):
        step = pl.program_id(2)

        @pl.when(step == 0)
        def _start():
            state_ref[...] = jnp.zeros_like(state_ref)

        xs, keys, reads = x_ref[...], b_ref[...], c_ref[...]
        upto, dts = rows_ref[0:1, :], rows_ref[1:2, :]    # [1, Q] over s
        upto_col = _column(upto)                          # [Q, 1] over t
        # inside the chunk: position t reads s <= t through the decays
        # between them
        seen = lax.broadcasted_iota(jnp.int32, (q, q), 0) \
            >= lax.broadcasted_iota(jnp.int32, (q, q), 1)
        between = jnp.exp(jnp.where(seen, upto_col - upto, -1e30))
        scores = lax.dot_general(
            reads, keys, (((1,), (1,)), ((), ())),
            preferred_element_type=f32) * between * dts
        y = jnp.dot(scores.astype(xs.dtype), xs,
                    preferred_element_type=f32)
        # what the chunks before it left
        state = state_ref[...]
        y += jnp.exp(upto_col) * jnp.dot(
            reads, state.astype(reads.dtype), preferred_element_type=f32)
        y_ref[...] = y.astype(y_ref.dtype)
        # the state after the chunk's last position
        left = _column(rows_ref[2:3, :] * dts)            # [Q, 1]
        weighed = xs.astype(f32) * left
        state = whole_ref[...] * state
        # What enters the float32 state is not rounded to the operands'
        # dtype on its way: below float32 the weighed inputs go through
        # the product as two terms, the rounded value and what it lost.
        terms = [weighed.astype(xs.dtype)]
        if xs.dtype != f32:
            terms.append((weighed - terms[0].astype(f32)).astype(xs.dtype))
        for term in terms:
            state += lax.dot_general(
                keys, term, (((0,), (0,)), ((), ())),
                preferred_element_type=f32)
        state_ref[...] = state

        @pl.when(step == chunks - 1)
        def _finish():
            out_ref[...] = state

    facts = {"b": b, "s": s, "heads": h, "head": p, "state": n,
             "groups": g, "chunk": q, "chunks": chunks}
    per = h // g
    y, state = kernel_call(
        body, kernel="ssd_chunk_scan", facts=facts,
        cost_estimate=pl.CostEstimate(
            flops=2 * b * h * chunks * q * (q * p + 2 * n * p)
            + 2 * b * g * chunks * q * q * n,
            transcendentals=b * h * chunks * q * (q + 2),
            bytes_accessed=2 * x.size * x.dtype.itemsize
            + 2 * bm.size * bm.dtype.itemsize + 4 * b * h * n * p),
        out_shape=(jax.ShapeDtypeStruct((b, chunks * q, h * p), x.dtype),
                   jax.ShapeDtypeStruct((b, h, n, p), f32)),
        grid=(b, h, chunks),
        in_specs=[
            pl.BlockSpec((None, None, None, 3, q),
                         lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((None, None, None, 1, p),
                         lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((None, q, p), lambda bi, hi, ci: (bi, ci, hi)),
            pl.BlockSpec((None, q, n),
                         lambda bi, hi, ci: (bi, ci, hi // per)),
            pl.BlockSpec((None, q, n),
                         lambda bi, hi, ci: (bi, ci, hi // per))],
        out_specs=(
            pl.BlockSpec((None, q, p), lambda bi, hi, ci: (bi, ci, hi)),
            pl.BlockSpec((None, None, n, p),
                         lambda bi, hi, ci: (bi, hi, 0, 0))),
        scratch_shapes=[pltpu.VMEM((n, p), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(rows, whole, x.reshape(b, chunks * q, h * p),
      bm.reshape(b, chunks * q, g * n), cm.reshape(b, chunks * q, g * n))
    y = y.reshape(b, chunks * q, h, p)[:, :s]
    if d is None:
        return y, state
    skip = d[:, None] * x[:, :s].astype(f32)
    return (y.astype(f32) + skip).astype(x.dtype), state


def ssd_scan(x, dt, a, bm, cm, d, valid, chunk: int = 128):
    """The sequence form: the kernel where :func:`in_kernels` holds,
    else the plain scan."""
    with jax.named_scope("ssd_scan"):
        if in_kernels(x.shape[-1], bm.shape[-1]):
            return chunk_scan(x, dt, a, bm, cm, d, valid, chunk)
        return scan_oracle(x, dt, a, bm, cm, d, valid)


# -- the decode form ---------------------------------------------------------

def step_oracle(state, x, dt, a, bm, cm, d, live):
    """One position a stream: ``state [B, H, N, P]``, ``x [B, H, P]``,
    ``dt [B, H]``, ``bm, cm [B, G, N]``, ``live [B]``. Returns ``y [B,
    H, P]`` (zeros for a stream that is not live) and the state, in the
    dtype it came in, untouched where the stream is not live. The
    arithmetic is float32."""
    h, g = x.shape[1], bm.shape[1]
    f32 = jnp.float32
    x32, dt = x.astype(f32), dt.astype(f32)
    bm = jnp.repeat(bm.astype(f32), h // g, axis=1)       # [B, H, N]
    cm = jnp.repeat(cm.astype(f32), h // g, axis=1)
    new = jnp.exp(dt * a)[..., None, None] * state.astype(f32) \
        + (dt[..., None] * bm)[..., :, None] * x32[..., None, :]
    new = new.astype(state.dtype)
    y = jnp.sum(new.astype(f32) * cm[..., :, None], axis=-2)
    if d is not None:
        y = y + d[:, None] * x32
    at = live[:, None, None]
    return (jnp.where(at, y, 0.0).astype(x.dtype),
            jnp.where(at[..., None], new, state))


def state_step(state, x, dt, a, bm, cm, d, live, interpret: bool = False,
               group_block: int = 1):
    """:func:`step_oracle` as the kernel ``ssd_state_step``; the state
    is float32. A grid step takes ``group_block`` groups (their heads'
    states one block): 1 where a group's heads are many; where a group
    is one head of 64 KB, all of them (or whole lane tiles of them)."""
    b, h, n, p = state.shape
    g = bm.shape[1]
    per, gb = h // g, group_block
    if g % gb or (gb > 1 and gb != g and gb % 128):
        raise ValueError(f"{g} groups are not whole blocks of {gb} (several "
                         f"groups a step lie along the lanes: all of them, "
                         f"or whole lane tiles)")
    held_heads = gb * per
    f32 = jnp.float32
    x32, dt = x.astype(f32), dt.astype(f32)
    # Along the lanes, a row a head: the decay, and dt x.
    decay = jnp.broadcast_to(jnp.exp(dt * a)[..., None], (b, h, p))
    xdt = dt[..., None] * x32
    # The live stream at or before each stream (its own number where it
    # is live; with none before it, the first live stream): the blocks
    # a stream that is not live holds, so that it moves nothing.
    stream = jnp.arange(b)
    before = jnp.max(jnp.where(
        (stream[None, :] <= stream[:, None]) & live[None, :],
        stream[None, :], -1), axis=1)
    first = jnp.argmax(live)
    held = jnp.where(before >= 0, before, first)
    # Such a stream holds the last group of a stream before it, the
    # first group of one after it: the block the grid is at.
    group = jnp.where(before >= 0, g // gb - 1, 0)
    bounds = jnp.stack([live.astype(jnp.int32), held, group,
                        jnp.broadcast_to(jnp.any(live), (b,))]
                       ).astype(jnp.int32)

    def body(bounds_ref, state_ref, decay_ref, xdt_ref, b_ref, c_ref,
             y_ref, out_ref):
        bi = pl.program_id(0)

        @pl.when(bounds_ref[0, bi] == 1)
        def _update():
            for j in range(gb):
                # B and C of a group as columns: made from the row by
                # its diagonal, or (several groups a step) handed in
                # with the groups along the lanes, a column a group
                key, read = (_column(r[...]) if gb == 1 else r[:, j:j + 1]
                             for r in (b_ref, c_ref))
                for i in range(j * per, (j + 1) * per):
                    new = state_ref[i] * decay_ref[i:i + 1, :] \
                        + key * xdt_ref[i:i + 1, :]
                    out_ref[i] = new
                    y_ref[i:i + 1, :] = jnp.sum(new * read, axis=0,
                                                keepdims=True)

        @pl.when(bounds_ref[0, bi] == 0)
        def _idle():
            y_ref[...] = jnp.zeros_like(y_ref)

        # With no live stream at all every step holds the grid's first
        # block, which is written back once: as it came.
        @pl.when(bounds_ref[3, bi] == 0)
        def _keep():
            out_ref[...] = state_ref[...]

    def state_index(bi, gi, bounds_ref):
        idle = bounds_ref[0, bi] == 0
        return (bounds_ref[1, bi],
                jnp.where(idle, bounds_ref[2, bi], gi), 0, 0)

    def own(bi, gi, bounds_ref):
        return bi, gi, 0

    def own_group(bi, gi, bounds_ref):
        return bi, gi, 0, 0

    facts = {"b": b, "heads": h, "head": p, "state": n, "groups": g}
    if gb > 1:
        facts["group_block"] = gb
    if gb == 1:
        shared = pl.BlockSpec((None, None, 1, n), own_group)
        keys, reads = (v.astype(f32)[:, :, None, :] for v in (bm, cm))
    else:       # [B, N, G]: the state's rows down, a group a lane
        shared = pl.BlockSpec((None, n, gb), lambda bi, gi, bounds_ref:
                              (bi, 0, gi))
        keys, reads = (jnp.swapaxes(v.astype(f32), 1, 2) for v in (bm, cm))
    y, state = kernel_call(
        body, kernel="ssd_state_step", facts=facts,
        cost_estimate=pl.CostEstimate(
            flops=5 * state.size, transcendentals=0,
            bytes_accessed=8 * state.size),
        out_shape=(jax.ShapeDtypeStruct((b, h, p), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((None, held_heads, n, p), state_index),
                      pl.BlockSpec((None, held_heads, p), own),
                      pl.BlockSpec((None, held_heads, p), own),
                      shared, shared],
            out_specs=(pl.BlockSpec((None, held_heads, p), own),
                       pl.BlockSpec((None, held_heads, n, p), state_index)),
            grid=(b, g // gb)),
        # the state is updated where it lies: argument 1 (after the
        # prefetched scalars) is result 1
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=4 * 4 * held_heads * n * p + (16 << 20)),
        interpret=interpret,
    )(bounds, state, decay, xdt, keys, reads)
    if d is not None:
        y = y + d[:, None] * x32
    return jnp.where(live[:, None, None], y, 0.0).astype(x.dtype), state


def head_group_block(groups: int) -> int:
    """Groups a grid step of ``ssd_state_step`` where a group is ONE
    head (a linear-attention layer): all of them (32 heads of [128, 128]
    float32 are 2 MB a block, a hybrid layer's group of 16 heads of
    [256, 128]); B and C then come with the groups along the lanes
    (PERF.md section 6, PR 54 has the sweep)."""
    return groups


def ssd_step(state, x, dt, a, bm, cm, d, live):
    """The decode form: the kernel where :func:`in_kernels` holds and
    the state is float32, else the plain update."""
    with jax.named_scope("ssd_step"):
        if in_kernels(x.shape[-1], bm.shape[-1]) \
                and state.dtype == jnp.float32:
            heads, groups = x.shape[1], bm.shape[1]
            gb = head_group_block(groups) if heads == groups > 1 else 1
            return state_step(state, x, dt, a, bm, cm, d, live,
                              group_block=gb)
        return step_oracle(state, x, dt, a, bm, cm, d, live)
