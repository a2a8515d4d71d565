"""Grouped matrix products over ragged row groups, as Pallas kernels.

Adapted from ``jax.experimental.pallas.ops.tpu.megablox`` (``gmm.py``
of the installed jax 0.9.0, Apache 2.0): the group metadata, the three
index maps and the two kernel bodies are that file's. What differs:

- every ``pallas_call`` is built through ``ops/kernel_id.kernel_call``
  under the names ``moe_gmm`` and ``moe_tgmm`` with the facts ``m``
  (row capacity), ``k``, ``n``, ``groups`` (and ``transpose_rhs``), so
  the device trace names each call;
- the groups need not fill the rows: ``sum(group_sizes) <= m``. Rows
  past the last group belong to no expert (the dispatch's capacity is
  static, the routed rows are not); the grid is as long as the tiles
  the groups touch, so a tile past the last routed row is never
  visited, and ``gmm`` returns zeros there;
- no sharded groups (``group_offset``), no ``existing_out``: the layer
  that calls these holds exactly the experts it passes.

``gmm(lhs [m,k], rhs [g,k,n], sizes [g]) -> [m,n]``: rows of group i
times ``rhs[i]``. Differentiable in ``lhs`` and ``rhs``: the gradient
to the rows is ``gmm`` against the transposed ``rhs``, the gradient to
``rhs`` is ``tgmm``, the per-group product ``lhs[group].T @
grad[group]``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernel_id import kernel_call

# Row tile of a call whose groups fill it; a call with fewer rows a
# group takes a smaller one (``row_tile``).
TILE_M = 512
_TILE_M_LEAST = 128
_TILE = 512          # column tile of an output, and of tgmm's k
_VMEM_LIMIT = 48 * 1024 * 1024


def row_tile(rows: int, groups: int) -> int:
    """Rows of one tile for a call of ``rows`` static rows over
    ``groups`` groups: the power of two that holds a group's even
    share of the rows, between 128 and ``TILE_M``. A tile is multiplied
    whole for every group that has a row in it, so where the groups
    have a dozen rows each (a decode step's few hundred pairs) tiles of
    512 would be 512-row products for a dozen rows, and the call would
    be bound by the MXU and not by its weights' bytes; training's and
    prefill's calls (thousands of rows a group) keep ``TILE_M``."""
    tm = _TILE_M_LEAST
    while tm < TILE_M and tm * groups < rows:
        tm *= 2
    return tm


def row_capacity(rows: int, groups: int) -> int:
    """``rows`` rounded up to whole row tiles of its call."""
    tm = row_tile(rows, groups)
    return -(-max(int(rows), 1) // tm) * tm


def _row_tile_of(m: int, groups: int, what: str) -> int:
    """The tile of a call whose static rows are ``m`` (a capacity that
    ``row_capacity`` made, or any multiple of ``TILE_M``)."""
    tm = row_tile(m, groups)
    while m % tm and tm > _TILE_M_LEAST:
        tm //= 2
    if m % tm:
        raise ValueError(f"{what}: {m} rows are not whole tiles of {tm}")
    return tm


def _tile(x: int, want: int) -> int:
    """The largest tile <= ``want`` that divides ``x`` and keeps the
    lane width (a multiple of 128), else the whole of ``x``."""
    for t in range(min(want, x), 127, -128):
        if x % t == 0 and t % 128 == 0:
            return t
    return x


def make_group_metadata(group_sizes, m: int, tm: int,
                        visit_empty_groups: bool):
    """megablox's ``make_group_metadata`` for all groups of one shard:
    ``(group_offsets [g+1], group_ids, m_tile_ids)`` for each grid index
    along the row-tile axis, and the number of tiles to run. A tile is
    visited once by every group that has rows in it; with
    ``visit_empty_groups`` an empty group still gets one visit (tgmm
    must zero its output)."""
    num_groups = group_sizes.shape[0]
    tiles_m = m // tm
    group_ends = jnp.cumsum(group_sizes)
    group_offsets = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), group_ends]).astype(jnp.int32)
    rounded_ends = ((group_ends + tm - 1) // tm * tm).astype(jnp.int32)
    rounded_starts = group_offsets[:-1] // tm * tm
    group_tiles = jnp.where(group_sizes == 0, 0,
                            (rounded_ends - rounded_starts) // tm)
    if visit_empty_groups:
        group_tiles = jnp.where(group_sizes == 0, 1, group_tiles)
    length = tiles_m + num_groups - 1
    group_ids = jnp.repeat(jnp.arange(num_groups, dtype=jnp.int32),
                           group_tiles, total_repeat_length=length)
    # A tile is visited once by the group that owns its first row, and
    # once more by every group that starts inside it.
    starts_on_tile = jnp.logical_or(group_offsets[:-1] % tm == 0,
                                    group_sizes == 0)
    if visit_empty_groups:
        starts_on_tile = jnp.where(group_sizes == 0, False,
                                   starts_on_tile)
    partial_tile_ids = jnp.where(starts_on_tile, tiles_m,
                                 group_offsets[:-1] // tm)
    tile_visits = jnp.histogram(
        partial_tile_ids, bins=tiles_m, range=(0, tiles_m - 1))[0] + 1
    m_tile_ids = jnp.repeat(jnp.arange(tiles_m, dtype=jnp.int32),
                            tile_visits.astype(jnp.int32),
                            total_repeat_length=length)
    return (group_offsets, group_ids, m_tile_ids), group_tiles.sum()


def _row_mask(grid_id, group_metadata, tm: int, tn: int):
    """Rows of the current tile that belong to the current group."""
    group_offsets, group_ids, m_tile_ids = group_metadata
    group = group_ids[grid_id]
    rows = lax.broadcasted_iota(jnp.int32, (tm, tn), 0) \
        + m_tile_ids[grid_id] * tm
    return jnp.logical_and(rows >= group_offsets[group],
                           rows < group_offsets[group + 1])


def _gmm_call(lhs, rhs, group_sizes, out_dtype, transpose_rhs: bool,
              interpret: bool):
    m, k = lhs.shape
    groups = rhs.shape[0]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tn = _row_tile_of(m, groups, "gmm"), _tile(n, _TILE)
    tiles_n = n // tn
    metadata, num_tiles = make_group_metadata(
        group_sizes, m, tm, visit_empty_groups=False)

    # The contraction is one block (k = 1536 to 7168 here): the
    # weight block's index then changes only with the group, and the
    # pipeline does not fetch it again for the group's next row tile.
    def kernel(metadata, lhs_ref, rhs_ref, out_ref):
        grid_id = pl.program_id(1)
        dims = (((1,), (1 if transpose_rhs else 0,)), ((), ()))
        acc = lax.dot_general(lhs_ref[...], rhs_ref[...], dims,
                              preferred_element_type=jnp.float32)
        mask = _row_mask(grid_id, metadata, tm, tn)
        out_ref[...] = lax.select(
            mask, acc, out_ref[...].astype(jnp.float32)
        ).astype(out_ref.dtype)

    def lhs_index(n_i, grid_id, metadata):
        return metadata[2][grid_id], 0

    def rhs_index(n_i, grid_id, metadata):
        if transpose_rhs:
            return metadata[1][grid_id], n_i, 0
        return metadata[1][grid_id], 0, n_i

    def out_index(n_i, grid_id, metadata):
        return metadata[2][grid_id], n_i

    rhs_block = (None, tn, k) if transpose_rhs else (None, k, tn)
    out = kernel_call(
        kernel, kernel="moe_gmm",
        facts={"m": m, "k": k, "n": n, "groups": groups,
               "transpose_rhs": int(transpose_rhs)},
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((tm, k), lhs_index),
                      pl.BlockSpec(rhs_block, rhs_index)],
            out_specs=pl.BlockSpec((tm, tn), out_index),
            grid=(tiles_n, num_tiles)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(metadata, lhs, rhs)
    # Rows past the last group were never written.
    routed = jnp.arange(m)[:, None] < metadata[0][-1]
    return jnp.where(routed, out, jnp.zeros((), out_dtype))


def tgmm(lhs, rhs, group_sizes, out_dtype, interpret: bool = False):
    """``lhs [m,k]``, ``rhs [m,n]`` -> ``[g,k,n]``: per group,
    ``lhs[group].T @ rhs[group]`` (zeros for an empty group)."""
    m, k = lhs.shape
    n = rhs.shape[1]
    groups = group_sizes.shape[0]
    tm = _row_tile_of(m, groups, "tgmm")
    tk, tn = _tile(k, _TILE), _tile(n, _TILE)
    metadata, num_tiles = make_group_metadata(
        group_sizes, m, tm, visit_empty_groups=True)

    def kernel(metadata, lhs_ref, rhs_ref, out_ref, acc_ref):
        grid_id = pl.program_id(2)
        group_offsets, group_ids, _ = metadata
        group = group_ids[grid_id]
        prev = group_ids[jnp.where(grid_id > 0, grid_id - 1, 0)]

        @pl.when(jnp.logical_or(grid_id == 0, prev != group))
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(group_offsets[group + 1] > group_offsets[group])
        def _accumulate():
            a = jnp.where(_row_mask(grid_id, metadata, tm, tk),
                          lhs_ref[...], jnp.zeros((), lhs_ref.dtype))
            b = jnp.where(_row_mask(grid_id, metadata, tm, tn),
                          rhs_ref[...], jnp.zeros((), rhs_ref.dtype))
            acc_ref[...] += lax.dot_general(
                a, b, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        last = grid_id == pl.num_programs(2) - 1
        nxt = group_ids[jnp.where(last, grid_id, grid_id + 1)]

        @pl.when(jnp.logical_or(last, nxt != group))
        def _store():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    def lhs_index(n_i, k_i, grid_id, metadata):
        return metadata[2][grid_id], k_i

    def rhs_index(n_i, k_i, grid_id, metadata):
        return metadata[2][grid_id], n_i

    def out_index(n_i, k_i, grid_id, metadata):
        return metadata[1][grid_id], k_i, n_i

    return kernel_call(
        kernel, kernel="moe_tgmm",
        facts={"m": m, "k": k, "n": n, "groups": groups},
        out_shape=jax.ShapeDtypeStruct((groups, k, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((tm, tk), lhs_index),
                      pl.BlockSpec((tm, tn), rhs_index)],
            out_specs=pl.BlockSpec((None, tk, tn), out_index),
            grid=(n // tn, k // tk, num_tiles),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(metadata, lhs, rhs)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def gmm(lhs, rhs, group_sizes, out_dtype, interpret: bool = False):
    """Rows of group i of ``lhs [m,k]`` times ``rhs[i]`` of ``[g,k,n]``;
    zeros in the rows past ``sum(group_sizes)``."""
    return _gmm_call(lhs, rhs, group_sizes, out_dtype, False, interpret)


def _gmm_fwd(lhs, rhs, group_sizes, out_dtype, interpret):
    out = _gmm_call(lhs, rhs, group_sizes, out_dtype, False, interpret)
    return out, (lhs, rhs, group_sizes)


def _gmm_bwd(out_dtype, interpret, res, grad):
    lhs, rhs, group_sizes = res
    grad = grad.astype(lhs.dtype)
    d_lhs = _gmm_call(grad, rhs, group_sizes, lhs.dtype, True, interpret)
    d_rhs = tgmm(lhs, grad, group_sizes, rhs.dtype, interpret)
    return d_lhs, d_rhs, None


gmm.defvjp(_gmm_fwd, _gmm_bwd)

