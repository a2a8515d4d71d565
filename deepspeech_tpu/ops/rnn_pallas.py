"""Fused Pallas GRU cell (SURVEY.md §2 component 6).

The TPU-native answer to cuDNN's fused RNN kernels, by where the
recurrent matrix lives:

**Resident** (small/medium H): the ``[H, 3H]`` recurrent matrix is a
VMEM block with a constant index map, so Pallas fetches it once and it
stays resident for the whole sequential time grid — each step is one
MXU matmul + fused VPU gate math, with no per-step weight traffic.
cuDNN's "persistent RNN" equivalent. Budget: 3*H^2*bytes must fit the
~10 MB residency budget (H=800 f32 -> 7.7 MB ok; bf16 doubles reach
to H~1280).

**Past the residency budget** (big H, e.g. the ds2_full flagship
H=1760, whose weights are 37 MB f32 / 18.6 MB bf16: past the budget
that Mosaic's default 16 MiB scoped limit leaves, not past VMEM, of
which a v5e core has 128 MiB) a call is one of two builds, chosen from
its shapes by ``_past_budget_scan_call`` through ``_pinned_vmem_limit``:

*Copy-once* (variant ``pinned``; the call's need stays under
``_PINNED_VMEM_CAP``: H=1760 in bf16 at every batch the presets run).
The ``[H, 3H]`` operand is taken as it is in ``pl.ANY``; ONE DMA at the
first grid step copies it whole into a VMEM scratch; the grid is
``(T,)``, one step per time step, and each step is the resident
kernels' step with the scratch for its matrix: one ``[b, H] x [H, 3H]``
matmul for the gates and the element-wise update straight after it
(``_gru_kernel_pinned`` = the copy + ``_gru_kernel``), backward also one
``dgates x W^T`` contraction (``_gru_bwd_kernel_pinned`` = the copy +
``_gru_bwd_kernel``). The call raises its own scoped limit from its
shapes. Where XLA's memory-space assignment left the operand does not
matter: from HBM the one copy is 18.6 MB once a call, and nothing moves
the matrix again. What this build got rid of, each measured on the chip
(PERF.md section 6): the weights crossing HBM at every step (six of 14
backward calls until PR 27: 29.5 us a step against 17.3); the BlockSpec
pipeline's VMEM-to-VMEM copy of every column block of a matrix XLA had
already placed in VMEM (3.0 us of a 17.3 us backward step, 3.2 of a
9.2 us forward step: PR 27, PR 29); and the column grid itself, 11 grid
steps, 11 small matmuls and 11 partial stores at each of 850 dependent
time steps (PR 31; padding the scratch's columns to the lane width
reads the same to 0.002 ms a call, so it is not padded).

*Streamed* (variant ``blocked``; past the cap: a float32 model at
H=1760 beyond a few rows, wider layers; run by no preset). The weight
columns are consumed in ``[H, C]`` blocks of ``_BLOCK_COLS`` over a
``(T, G)`` grid, moved by the BlockSpec pipeline from wherever XLA left
the operand: from HBM that is the whole matrix every step, the honest
cost of a matrix that cannot live in VMEM. The column grid is there for
THIS build alone, because a pipelined operand is double-buffered: the
whole matrix as one block would cost twice its size where two 1.8 MB
column blocks do. Each step's matmul runs as G block matmuls whose
partials land in a VMEM scratch, the GRU elementwise update firing on
the last block (``_gru_kernel_blocked``); the backward kernel
(``_gru_bwd_kernel_blocked``) needs the blocks once per step: it
pipelines the ``dgates @ W^T`` contraction one step behind the gate
recompute (SURVEY.md §7 hard-parts #2). The copy-once backward step
does not: with the matrix whole in its scratch a second pass costs
nothing, and the resident body's order measured faster on the chip.

**int8 resident / int8 blocked streaming** (weight-only PTQ serving):
``gru_scan_pallas_q`` keeps the QUANTIZED matrix resident — int8
quadruples the residency reach over f32, so the flagship H=1760
(9.3 MB) needs no blocked grid at all; scales apply to
the gates via column-scale associativity (see the section comment
below). Past even the 1-byte budget (GRU H>1869; LSTM's 4-gate
layout already at H=1620) the q path switches to
``_gru_kernel_blocked_q``: the SAME ``(T, G)`` column-streaming grid
as the fp streamed build, but the moving ``[H, C]`` tile is s8 and
the dequant (upcast next to the sliced per-output-channel scale
columns) happens in VMEM — per-step HBM weight traffic is the int8
bytes, 4× less than the f32 stream.

Contract matches ``models.rnn.gru_scan`` (the XLA-scan oracle):
``(xproj [B,T,3H] incl. b_x, mask [B,T], w_h [H,3H], b_h [3H],
reverse) -> ys [B,T,H] float32``. Direction is implemented purely in
the BlockSpec index maps (the reversed scan reads/writes rows
T-1-t), so no operand flipping is materialized. ``dot_dtype``
("bfloat16" for bf16 models) sets the MXU operand precision of the
recurrent matmuls — accumulation stays f32, matching the oracle's
``dot_dtype`` semantics — and halves both the residency budget and
the weights' bytes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernel_id import kernel_call, scan_facts

# Leave headroom for xproj/mask/out rows + double buffering.
_VMEM_WEIGHT_BUDGET = 10 * 1024 * 1024
# Weight-block width (lane-aligned); G = ceil(3H / this).
_BLOCK_COLS = 512
# The scoped VMEM a copy-once call must stay under (a call whose limit
# would reach it streams instead). A v5e core has 128 MiB; 16 MiB is
# only the default scoped limit (BASELINE.md:111). The rest stays with
# XLA, which places the neighbouring calls' operands.
_PINNED_VMEM_CAP = 48 * 1024 * 1024


def fits_vmem(hidden: int, dtype_bytes: int = 4, n_gates: int = 3) -> bool:
    return n_gates * hidden * hidden * dtype_bytes <= _VMEM_WEIGHT_BUDGET


def _dot_jnp_dtype(dot_dtype: Optional[str]):
    if dot_dtype is None or dot_dtype == "float32":
        return jnp.float32
    if dot_dtype == "bfloat16":
        return jnp.bfloat16
    # Fail loudly rather than silently computing in a different
    # precision than the XLA path would.
    raise ValueError(f"unsupported pallas dot_dtype {dot_dtype!r}; "
                     "use None/'float32'/'bfloat16'")


def recurrent_dw(h_prev, dgates, dot):
    """``dW_h = sum over T*B of h_prev^T dgates``: the recurrent weight
    gradient as one MXU contraction of two float32 ``[T, B, .]``
    sequences outside the time loop, at the precision the scan's dot
    type states. The sum is cancellation-heavy (T*B = 27,200 products
    an entry at ds2_full's cell), so it never rounds an operand to
    8 bits (``DEFAULT``, one bf16 pass: 3.6e-2 off the float32 truth
    at toy size, tests/test_pallas.py
    test_gru_bf16_dw_closer_to_truth_than_oracle).

    float32 dots: ``HIGHEST``, six bf16 passes, 24 bits of each operand
    (a float32 model states float32 compute). bfloat16 dots: ``HIGH``,
    three passes (``hi*hi + hi*mid + mid*hi``), 16 bits of each
    operand: both operands come out of T steps of bf16 matmuls, whose
    noise puts dW_h 3.2e-4 from the all-float32 program's, and three
    passes are 1.4e-5 from the float64 sum, 23 times under it (the
    chip at the cell's shape, ``chip_smoke.py dw_h_precision``; limits
    and readings: PERF.md section 6, PR 37). The last 8 bits that
    ``HIGHEST`` would carry are bits of that noise, at twice the MXU
    time: 14 such contractions were 41% of ds2_full's step."""
    precision = (jax.lax.Precision.HIGH if dot == jnp.bfloat16
                 else jax.lax.Precision.HIGHEST)
    return jnp.einsum("tbh,tbg->hg", h_prev, dgates, precision=precision)


# ---------------------------------------------------------------------------
# Resident-weight kernels (weights live in VMEM across the whole scan).
# ---------------------------------------------------------------------------

def _gru_kernel(xp_ref, mask_ref, wh_ref, bh_ref, *refs):
    # refs = (out_ref, h_c) for the training path (h0 = 0), or
    # (h0_ref[in], out_ref, hfin_ref, h_c) for the streaming path that
    # carries hidden state across chunks and emits the final carry.
    if len(refs) == 2:
        (out_ref, h_c), h0_ref, hfin_ref = refs, None, None
    else:
        h0_ref, out_ref, hfin_ref, h_c = refs
    t = pl.program_id(0)
    b, h3 = xp_ref.shape[1], xp_ref.shape[2]
    h = h3 // 3

    @pl.when(t == 0)
    def _():
        h_c[:] = (jnp.zeros_like(h_c) if h0_ref is None else h0_ref[:])

    hprev = h_c[:]
    gates = jnp.dot(hprev.astype(wh_ref.dtype), wh_ref[:],
                    preferred_element_type=jnp.float32) + bh_ref[:]
    hnew = _gru_elt(xp_ref[0], gates, hprev, mask_ref[0], h)
    h_c[:] = hnew
    out_ref[0] = hnew
    if hfin_ref is not None:
        @pl.when(t == pl.num_programs(0) - 1)
        def _():
            hfin_ref[:] = hnew


def _gru_bwd_kernel(xp_ref, mask_ref, ys_prev_ref, dy_ref, wh_ref,
                    bh_ref, dxp_ref, dgates_ref, dh_c):
    """One reverse-time BPTT step (flash-style gate recompute).

    Carries dh across steps; recomputes r/z/n from (h_prev, xp, W)
    rather than storing them in the forward pass. Streams per-step
    dxp and dgates out; dW/db are formed outside as one einsum over
    the streamed dgates (a single large MXU contraction beats a
    [H,3H] VMEM accumulator, which would not leave room for W).
    """
    ti = pl.program_id(0)  # 0.. T-1, processing t = T-1-ti in scan order
    h3 = xp_ref.shape[2]
    h = h3 // 3

    @pl.when(ti == 0)
    def _():
        dh_c[:] = jnp.zeros_like(dh_c)

    hprev = jnp.where(ti == pl.num_programs(0) - 1,
                      jnp.zeros_like(ys_prev_ref[0]), ys_prev_ref[0])
    gates = jnp.dot(hprev.astype(wh_ref.dtype), wh_ref[:],
                    preferred_element_type=jnp.float32) + bh_ref[:]
    dxp, dgates, dh_elt = _gru_bwd_elt(
        xp_ref[0], gates, hprev, mask_ref[0], dh_c[:] + dy_ref[0], h)
    dxp_ref[0] = dxp
    dgates_ref[0] = dgates
    # dh_prev = elementwise terms + through-gates (dgates @ W^T).
    dh_c[:] = dh_elt + jax.lax.dot_general(
        dgates.astype(wh_ref.dtype), wh_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def _gru_elt(xp, gates, hprev, m, h):
    """Shared GRU elementwise update: (xp [B,3H], gates [B,3H] f32,
    hprev [B,H], mask [B,1]) -> new hidden [B,H]."""
    r = jax.nn.sigmoid(xp[:, :h] + gates[:, :h])
    z = jax.nn.sigmoid(xp[:, h:2 * h] + gates[:, h:2 * h])
    n = jnp.tanh(xp[:, 2 * h:] + r * gates[:, 2 * h:])
    hnew = (1.0 - z) * n + z * hprev
    return m * hnew + (1.0 - m) * hprev


def _bigru_kernel(xpf_ref, mf_ref, whf_ref, bhf_ref,
                  xpb_ref, mb_ref, whb_ref, bhb_ref,
                  outf_ref, outb_ref, hf_c, hb_c):
    """BOTH directions of a resident-weight BiGRU in one time grid.

    Two serialized single-direction kernels leave the MXU idle during
    each step's VPU gate math (and vice versa); interleaving two
    INDEPENDENT recurrences per grid step lets Mosaic overlap one
    direction's matmul with the other's elementwise tail. Grid step t:
    forward direction processes data row t, backward direction data
    row T-1-t (purely via BlockSpec index maps; the same xproj/mask
    arrays are passed twice with mirrored maps).
    """
    t = pl.program_id(0)
    h = whf_ref.shape[0]

    @pl.when(t == 0)
    def _():
        hf_c[:] = jnp.zeros_like(hf_c)
        hb_c[:] = jnp.zeros_like(hb_c)

    hf, hb = hf_c[:], hb_c[:]
    gf = jnp.dot(hf.astype(whf_ref.dtype), whf_ref[:],
                 preferred_element_type=jnp.float32) + bhf_ref[:]
    gb = jnp.dot(hb.astype(whb_ref.dtype), whb_ref[:],
                 preferred_element_type=jnp.float32) + bhb_ref[:]
    hf_new = _gru_elt(xpf_ref[0], gf, hf, mf_ref[0], h)
    hb_new = _gru_elt(xpb_ref[0], gb, hb, mb_ref[0], h)
    hf_c[:] = hf_new
    hb_c[:] = hb_new
    outf_ref[0] = hf_new
    outb_ref[0] = hb_new


def _gru_bwd_elt(xp, gates, hprev, m, dh, h):
    """Shared one-step GRU BPTT math. Returns (dxp, dgates,
    dh_prev_elementwise) — the ``dgates @ W^T`` term is the caller's
    (it differs between resident and fused-bidir layouts)."""
    g_n = gates[:, 2 * h:]
    r = jax.nn.sigmoid(xp[:, :h] + gates[:, :h])
    z = jax.nn.sigmoid(xp[:, h:2 * h] + gates[:, h:2 * h])
    n = jnp.tanh(xp[:, 2 * h:] + r * g_n)
    dh_mid = m * dh
    dn = dh_mid * (1.0 - z)
    dz = dh_mid * (hprev - n)
    da_n = dn * (1.0 - n * n)
    dr = da_n * g_n
    dg_n = da_n * r
    da_z = dz * z * (1.0 - z)
    da_r = dr * r * (1.0 - r)
    dgates = jnp.concatenate([da_r, da_z, dg_n], axis=1)
    dxp = jnp.concatenate([da_r, da_z, da_n], axis=1)
    dh_elt = dh_mid * z + (1.0 - m) * dh
    return dxp, dgates, dh_elt


def _bigru_bwd_kernel(xpf_ref, xpb_ref, mf_ref, mb_ref,
                      ysf_prev_ref, ysb_prev_ref, dyf_ref, dyb_ref,
                      whf_ref, whb_ref, bhf_ref, bhb_ref,
                      dxpf_ref, dgf_ref, dxpb_ref, dgb_ref,
                      dhf_c, dhb_c):
    """Fused BPTT for both directions (flash-style gate recompute).

    Grid step i runs the forward direction's BPTT at data row T-1-i
    and the backward direction's at data row i — each direction's own
    reverse-scan order, both recurrence starts landing on the same
    boundary i == T-1 (where h_prev is the zero initial state).
    """
    i = pl.program_id(0)
    h = whf_ref.shape[0]

    @pl.when(i == 0)
    def _():
        dhf_c[:] = jnp.zeros_like(dhf_c)
        dhb_c[:] = jnp.zeros_like(dhb_c)

    first = i == pl.num_programs(0) - 1
    hf_prev = jnp.where(first, jnp.zeros_like(ysf_prev_ref[0]),
                        ysf_prev_ref[0])
    hb_prev = jnp.where(first, jnp.zeros_like(ysb_prev_ref[0]),
                        ysb_prev_ref[0])
    gf = jnp.dot(hf_prev.astype(whf_ref.dtype), whf_ref[:],
                 preferred_element_type=jnp.float32) + bhf_ref[:]
    gb = jnp.dot(hb_prev.astype(whb_ref.dtype), whb_ref[:],
                 preferred_element_type=jnp.float32) + bhb_ref[:]
    dxpf, dgf, dhf_elt = _gru_bwd_elt(
        xpf_ref[0], gf, hf_prev, mf_ref[0], dhf_c[:] + dyf_ref[0], h)
    dxpb, dgb, dhb_elt = _gru_bwd_elt(
        xpb_ref[0], gb, hb_prev, mb_ref[0], dhb_c[:] + dyb_ref[0], h)
    dxpf_ref[0] = dxpf
    dgf_ref[0] = dgf
    dxpb_ref[0] = dxpb
    dgb_ref[0] = dgb
    dhf_c[:] = dhf_elt + jax.lax.dot_general(
        dgf.astype(whf_ref.dtype), whf_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    dhb_c[:] = dhb_elt + jax.lax.dot_general(
        dgb.astype(whb_ref.dtype), whb_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Weights past the residency budget (flagship H=1760): the copy-once
# steps (the resident bodies over a scratch, one grid step per time
# step) and the streamed (blocked) bodies.
# ---------------------------------------------------------------------------

def _copy_weights_once(wh_ref, w_scr, sem):
    """The copy-once build's one DMA: ``wh_ref`` is the whole
    matrix wherever XLA left it (``pl.ANY``), copied into the VMEM
    scratch ``w_scr`` at the call's first grid step; every later step
    reads the scratch."""
    @pl.when(pl.program_id(0) == 0)
    def _():
        copy = pltpu.make_async_copy(wh_ref, w_scr, sem)
        copy.start()
        copy.wait()


def _gru_kernel_pinned(xp_ref, mask_ref, wh_ref, bh_ref, out_ref,
                       h_c, w_scr, sem):
    """Copy-once forward step: the resident step, its matrix read from
    the scratch."""
    _copy_weights_once(wh_ref, w_scr, sem)
    _gru_kernel(xp_ref, mask_ref, w_scr, bh_ref, out_ref, h_c)


def _gru_kernel_blocked(xp_ref, mask_ref, wh_ref, bh_ref, out_ref,
                        h_c, gates_buf, *, h: int, n_blocks: int, c: int):
    t = pl.program_id(0)
    g = pl.program_id(1)

    @pl.when((t == 0) & (g == 0))
    def _():
        h_c[:] = jnp.zeros_like(h_c)

    hprev = h_c[:]
    blk = jnp.dot(hprev.astype(wh_ref.dtype), wh_ref[:],
                  preferred_element_type=jnp.float32) + bh_ref[:]
    gates_buf[:, pl.ds(g * c, c)] = blk

    @pl.when(g == n_blocks - 1)
    def _():
        hnew = _gru_elt(xp_ref[0], gates_buf[:, :3 * h], hprev,
                        mask_ref[0], h)
        h_c[:] = hnew
        out_ref[0] = hnew


def _gru_kernel_blocked_q(xp_ref, mask_ref, wq_ref, sc_ref, bh_ref,
                          out_ref, h_c, gates_buf, *,
                          h: int, n_blocks: int, c: int, dot):
    """_gru_kernel_blocked with int8 weight tiles: the moving [H, C]
    block is s8 (4× less HBM stream per step than f32), upcast to the
    MXU operand dtype in VMEM; the matching [1, C] scale columns ride
    the same block-grid axis, so each partial is exactly the resident
    q-kernel's gates restricted to this column range (matmul columns
    are independent). The outputs agree with the resident kernel's to
    a few ulp, not to the bit: the elementwise update after the gates
    is compiled apart in the two programs."""
    t = pl.program_id(0)
    g = pl.program_id(1)

    @pl.when((t == 0) & (g == 0))
    def _():
        h_c[:] = jnp.zeros_like(h_c)

    hprev = h_c[:]
    blk = jnp.dot(hprev.astype(dot), wq_ref[:].astype(dot),
                  preferred_element_type=jnp.float32) \
        * sc_ref[:] + bh_ref[:]
    gates_buf[:, pl.ds(g * c, c)] = blk

    @pl.when(g == n_blocks - 1)
    def _():
        hnew = _gru_elt(xp_ref[0], gates_buf[:, :3 * h], hprev,
                        mask_ref[0], h)
        h_c[:] = hnew
        out_ref[0] = hnew


def _gru_bwd_kernel_pinned(xp_ref, mask_ref, ys_prev_ref, dy_ref, wh_ref,
                           bh_ref, dxp_ref, dgates_ref, dh_c, w_scr, sem):
    """Copy-once BPTT step: the resident step (gate recompute,
    element-wise, ``dgates @ W^T`` into the carried dh), its matrix
    read from the scratch. On the chip this order reads 8.24 ms a call
    at ds2_full's shape against 8.36 with the contraction one step
    behind, as the streamed body has it (PERF.md section 6, PR 31).
    The resident body reads its matrix once per matmul, stores between
    them: one read feeding both makes Mosaic hold the matrix a second
    time (42 MiB of scoped VMEM for 24 at b=32)."""
    _copy_weights_once(wh_ref, w_scr, sem)
    _gru_bwd_kernel(xp_ref, mask_ref, ys_prev_ref, dy_ref, w_scr, bh_ref,
                    dxp_ref, dgates_ref, dh_c)


def _gru_bwd_kernel_blocked(xp_ref, mask_ref, ys_prev_ref, dy_ref, wh_ref,
                            bh_ref, dxp_ref, dgates_ref,
                            dh_c, dh_acc, gates_buf, dg_prev, *,
                            h: int, n_blocks: int, c: int):
    """Blocked BPTT step: ONE pass over the weight blocks per time step.

    The ``dgates @ W^T`` contribution to dh uses the *previous* step's
    dgates (held in ``dg_prev``), so it rides the same pass over the
    weight blocks as the current step's gate recompute — no second pass.
    ``dh_c`` therefore carries only the elementwise part of dh_prev;
    the full dh assembles at the last block as dh_c + dh_acc + dy.
    """
    ti = pl.program_id(0)
    g = pl.program_id(1)

    @pl.when((ti == 0) & (g == 0))
    def _():
        dh_c[:] = jnp.zeros_like(dh_c)
        dg_prev[:] = jnp.zeros_like(dg_prev)

    @pl.when(g == 0)
    def _():
        dh_acc[:] = jnp.zeros_like(dh_acc)

    hprev = jnp.where(ti == pl.num_programs(0) - 1,
                      jnp.zeros_like(ys_prev_ref[0]), ys_prev_ref[0])
    blk = jnp.dot(hprev.astype(wh_ref.dtype), wh_ref[:],
                  preferred_element_type=jnp.float32) + bh_ref[:]
    gates_buf[:, pl.ds(g * c, c)] = blk

    dgp = dg_prev[:, pl.ds(g * c, c)]
    dh_acc[:] += jax.lax.dot_general(
        dgp.astype(wh_ref.dtype), wh_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(g == n_blocks - 1)
    def _():
        dxp, dgates, dh_elt = _gru_bwd_elt(
            xp_ref[0], gates_buf[:, :3 * h], hprev, mask_ref[0],
            dh_c[:] + dh_acc[:] + dy_ref[0], h)
        dxp_ref[0] = dxp
        dgates_ref[0] = dgates
        dg_prev[:, :3 * h] = dgates
        # Elementwise part of dh_prev; the dgates @ W^T part rides the
        # next step's pass over the weight blocks into dh_acc.
        dh_c[:] = dh_elt


# ---------------------------------------------------------------------------
# Host-side wiring.
# ---------------------------------------------------------------------------

def _time_index_maps(t_max: int, reverse: bool, blocked: bool):
    """(row, mask-row) index maps in *scan order*.

    For the reversed direction the scan runs t = T-1 .. 0, so scan step
    i touches row T-1-i and its 'previous' state lives at row T-i.
    Blocked kernels have a trailing block-grid axis that row maps ignore.
    """
    if reverse:
        row = lambda t: t_max - 1 - t
    else:
        row = lambda t: t
    if blocked:
        idx = lambda t, g: (row(t), 0, 0)
        midx = lambda t, g: (row(t), 0, 0)
    else:
        idx = lambda t: (row(t), 0, 0)
        midx = lambda t: (row(t), 0, 0)
    return idx, midx


def _block_layout(h3: int):
    """(n_blocks, block_cols) for the streamed weight-column grid."""
    c = min(_BLOCK_COLS, pl.cdiv(h3, 128) * 128)
    return pl.cdiv(h3, c), c


def _pad_cols(x, cols: int):
    pad = cols - x.shape[-1]
    return x if pad == 0 else jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _time_major(xproj, mask):
    """(xp_t [T,B,G], mask_t [T,B,1]) kernel operands.

    xproj keeps its incoming dtype: a bf16 model hands bf16 xproj in,
    and storing it unwidened halves the dominant per-step VMEM stream
    (kernel adds promote to f32 — identical math to upcasting here).
    The mask's trailing singleton keeps the per-step block's last two
    dims equal to the array dims, which real-TPU lowering requires
    (a (1, B) block over a (T, B) array has an unaligned sublane dim).
    """
    return (jnp.moveaxis(xproj, 1, 0),
            jnp.moveaxis(mask.astype(jnp.float32), 1, 0)[..., None])


def _resident_in_specs(b: int, h: int, h3: int, idx, midx):
    """Input BlockSpecs shared by the resident-weight fwd kernels:
    per-step xproj row, per-step [B,1] mask row, whole-[H,3H] weights
    (constant index map = VMEM-resident), bias. Single source of truth
    for the training and streaming paths."""
    return [
        pl.BlockSpec((1, b, h3), idx, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, b, 1), midx, memory_space=pltpu.VMEM),
        pl.BlockSpec((h, h3), lambda t: (0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((1, h3), lambda t: (0, 0), memory_space=pltpu.VMEM),
    ]


def _resident_q_in_specs(b: int, h: int, hn: int, idx, midx):
    """Input BlockSpecs for the int8-resident fwd kernels, in OPERAND
    order (xp, mask, w_q, scale, bias). Single source of truth for the
    GRU (hn=3H) and LSTM (hn=4H) quantized variants — the scale and
    bias specs are coincidentally identical (1,hn) consts, so building
    them in one place is what keeps a future layout change from
    silently misbinding operands (ADVICE r4)."""
    const = lambda shape: pl.BlockSpec(shape, lambda t: (0, 0),
                                       memory_space=pltpu.VMEM)
    return [
        pl.BlockSpec((1, b, hn), idx, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, b, 1), midx, memory_space=pltpu.VMEM),
        const((h, hn)), const((1, hn)), const((1, hn)),
    ]


def _blocked_q_in_specs(b: int, h: int, hn: int, c: int, idx, midx):
    """Input BlockSpecs for the int8 blocked-streaming fwd kernels, in
    OPERAND order (xp, mask, w_q, scale, bias) — the q analogue of the
    fp blocked layout. The s8 [H, C] weight tile moves along the
    block-grid axis (Pallas double-buffers the fetch behind the
    previous block's matmul); the [1, C] scale and bias columns ride
    the same axis so the in-VMEM dequant only ever sees its own
    block's output channels."""
    col = lambda shape: pl.BlockSpec(shape, lambda t, g: (0, g),
                                     memory_space=pltpu.VMEM)
    return [
        pl.BlockSpec((1, b, hn), idx, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, b, 1), midx, memory_space=pltpu.VMEM),
        col((h, c)), col((1, c)), col((1, c)),
    ]


def _use_blocked(h: int, dot, n_gates: int = 3,
                 weight_bytes: Optional[int] = None) -> bool:
    """Regime selector: past the residency budget (the float kernels'
    copy-once or streamed build, the q kernels' blocked streaming) iff
    the matrix misses that budget at its STORED width. ``weight_bytes``
    is the per-element size of the array that actually sits in / streams
    from HBM — 1 for the int8 q kernels (the s8 tree is the jit input);
    defaults to the MXU operand size (the fp kernels pre-cast W to the
    dot dtype, so stored width == operand width there)."""
    wb = jnp.dtype(dot).itemsize if weight_bytes is None else weight_bytes
    return not fits_vmem(h, wb, n_gates)


def _pinned_vmem_limit(weight_bytes: int, row_bytes: int,
                       scratch_bytes: int) -> Optional[int]:
    """The scoped-VMEM limit a copy-once call asks for, or None when it
    would reach :data:`_PINNED_VMEM_CAP` (the call then streams its
    weights in column blocks). What the call holds: ONE copy of the
    ``[H, 3H]`` matrix (its rows as wide as VMEM's lanes make them),
    its per-step rows twice (the pipeline double-buffers them) and its
    float32 scratches, among which the caller counts the step's gate
    value ``[b, 3H]`` (live whole, since one matmul makes it); a
    quarter on top for the gate math's other temporaries, rounded up to
    4 MiB and never under Mosaic's default of 16 MiB. ds2_full (H=1760,
    bf16, 18.6 MB of weights) at b=32 / 64: forward 28 / 28 MiB,
    backward 32 / 36 MiB."""
    step = 4 * 1024 * 1024
    need = weight_bytes + 2 * row_bytes + scratch_bytes
    limit = max(16 * 1024 * 1024, pl.cdiv(need * 5 // 4, step) * step)
    return limit if limit < _PINNED_VMEM_CAP else None


# The two builds of each float scan kernel whose matrix is past the
# residency budget: the body and the widths of its float32 ``[b, n]``
# scratches (``cols``: the matrix's columns as the build pads them).
_PAST_BUDGET_BUILDS = {
    "gru_scan_fwd": {
        "pinned": (_gru_kernel_pinned, lambda h, cols: [h]),
        "blocked": (_gru_kernel_blocked, lambda h, cols: [h, cols])},
    "gru_scan_bwd": {
        "pinned": (_gru_bwd_kernel_pinned, lambda h, cols: [h]),
        "blocked": (_gru_bwd_kernel_blocked,
                    lambda h, cols: [h, h, cols, cols])},
}


def _past_budget_scan_call(kernel: str, reverse: bool, rows, w, bias,
                           out_map, out_widths, interpret: bool):
    """The scan call of either direction for a matrix past the
    residency budget, in one of two builds chosen here from the shapes.

    ``rows``: the per-step operands as ``(array [T, b, X], time index
    map)`` pairs in the kernel's order; ``w [H, 3H]`` (dot type) and
    ``bias [1, 3H]`` follow them. The outputs are float32
    ``[T, b, width]`` rows through ``out_map`` (one array for one
    width, else a list).

    Copy-once (``pinned``) when :func:`_pinned_vmem_limit` stays under
    the cap: grid ``(T,)``, the matrix taken as it is in ``pl.ANY`` and
    copied by the kernel into a scratch at the first step, each step
    the resident kernels' step over all of it, under the call's own
    scoped limit. Else the BlockSpec pipeline streams it (``blocked``):
    grid ``(T, G)`` over ``_BLOCK_COLS``-wide column blocks of the
    matrix padded to whole blocks, because a pipelined operand is
    double-buffered and two 1.8 MB blocks fit where two whole matrices
    do not.
    """
    t_max, b = rows[0][0].shape[:2]
    h, h3 = w.shape
    lanes = pl.cdiv(h3, 128) * 128  # what VMEM holds of a 3H-wide row
    row_bytes = (sum(b * max(x.shape[2], 128) * x.dtype.itemsize
                     for x, _ in rows)
                 + 8 * lanes * 4 + sum(b * n * 4 for n in out_widths))
    body, scratch_widths = _PAST_BUDGET_BUILDS[kernel]["pinned"]
    limit = _pinned_vmem_limit(
        h * lanes * w.dtype.itemsize, row_bytes,
        sum(b * n * 4 for n in scratch_widths(h, lanes) + [lanes]))
    if limit is None:
        variant = "blocked"
        body, scratch_widths = _PAST_BUDGET_BUILDS[kernel][variant]
        n_blocks, c = _block_layout(h3)
        cols, grid = n_blocks * c, (t_max, n_blocks)
        body = functools.partial(body, h=h, n_blocks=n_blocks, c=c)
        on_grid = lambda imap: lambda t, g: imap(t)
        w_spec = pl.BlockSpec((h, c), lambda t, g: (0, g),
                              memory_space=pltpu.VMEM)
        bias_spec = pl.BlockSpec((1, c), lambda t, g: (0, g),
                                 memory_space=pltpu.VMEM)
        pin_scratch, pin = [], {}
    else:
        variant, cols, grid = "pinned", h3, (t_max,)
        on_grid = lambda imap: imap
        w_spec = pl.BlockSpec(memory_space=pl.ANY)
        bias_spec = pl.BlockSpec((1, h3), lambda t: (0, 0),
                                 memory_space=pltpu.VMEM)
        pin_scratch = [pltpu.VMEM((h, h3), w.dtype),
                       pltpu.SemaphoreType.DMA(())]
        pin = {"compiler_params":
               pltpu.CompilerParams(vmem_limit_bytes=limit)}
    outs = [(pl.BlockSpec((1, b, n), on_grid(out_map),
                          memory_space=pltpu.VMEM),
             jax.ShapeDtypeStruct((t_max, b, n), jnp.float32))
            for n in out_widths]
    out_specs, out_shape = outs[0] if len(outs) == 1 else zip(*outs)
    return kernel_call(
        body, kernel=kernel,
        facts=scan_facts(variant, reverse, t_max, b, h, 3),
        grid=grid,
        in_specs=[pl.BlockSpec((1, b, x.shape[2]), on_grid(imap),
                               memory_space=pltpu.VMEM)
                  for x, imap in rows] + [w_spec, bias_spec],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((b, n), jnp.float32)
                        for n in scratch_widths(h, cols)] + pin_scratch,
        interpret=interpret,
        **pin,
    )(*[x for x, _ in rows], _pad_cols(w, cols), _pad_cols(bias, cols))


def _gru_pallas_raw(xproj, mask, w_h, b_h, reverse: bool, interpret: bool,
                    dot_dtype: Optional[str]):
    b, t_max, h3 = xproj.shape
    h = h3 // 3
    dot = _dot_jnp_dtype(dot_dtype)
    xp_t, mask_t = _time_major(xproj, mask)
    bh2 = b_h.astype(jnp.float32).reshape(1, h3)
    w = w_h.astype(dot)

    idx, midx = _time_index_maps(t_max, reverse, blocked=False)
    if not _use_blocked(h, dot):
        ys = kernel_call(
            _gru_kernel, kernel="gru_scan_fwd",
            facts=scan_facts("resident", reverse, t_max, b, h, 3),
            grid=(t_max,),
            in_specs=_resident_in_specs(b, h, h3, idx, midx),
            out_specs=pl.BlockSpec((1, b, h), idx, memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((t_max, b, h), jnp.float32),
            scratch_shapes=[pltpu.VMEM((b, h), jnp.float32)],
            interpret=interpret,
        )(xp_t, mask_t, w, bh2)
        return ys, xp_t, mask_t, bh2

    ys = _past_budget_scan_call(
        "gru_scan_fwd", reverse, [(xp_t, idx), (mask_t, midx)], w, bh2,
        idx, [h], interpret)
    return ys, xp_t, mask_t, bh2


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def gru_scan_pallas(xproj: jnp.ndarray, mask: jnp.ndarray,
                    w_h: jnp.ndarray, b_h: jnp.ndarray,
                    reverse: bool = False,
                    interpret: bool = False,
                    dot_dtype: Optional[str] = None) -> jnp.ndarray:
    """Fused GRU recurrence. See module docstring for the contract."""
    ys, _, _, _ = _gru_pallas_raw(xproj, mask, w_h, b_h, reverse, interpret,
                                  dot_dtype)
    return jnp.moveaxis(ys, 0, 1)  # [B, T, H]


def gru_scan_pallas_stream(xproj: jnp.ndarray, mask: jnp.ndarray,
                           w_h: jnp.ndarray, b_h: jnp.ndarray,
                           h0: jnp.ndarray, interpret: bool = False,
                           dot_dtype: Optional[str] = None):
    """Forward-only fused GRU with carried state, for chunked streaming
    inference (streaming.py): ``h0 [B, H]`` seeds the scan and the
    final carry is returned alongside the outputs, matching
    ``models.rnn.gru_scan(..., h0=h0, return_final=True)``. Causal
    (forward) direction only; VMEM-resident weights only — the
    streaming preset's H=800 fits, and callers fall back to the XLA
    scan otherwise.
    """
    b, t_max, h3 = xproj.shape
    h = h3 // 3
    dot = _dot_jnp_dtype(dot_dtype)
    if _use_blocked(h, dot):
        raise ValueError(
            f"streaming fused cell needs VMEM-resident weights; H={h} "
            f"at {jnp.dtype(dot).itemsize}-byte dots exceeds the budget")
    xp_t, mask_t = _time_major(xproj, mask)
    bh2 = b_h.astype(jnp.float32).reshape(1, h3)
    idx, midx = _time_index_maps(t_max, reverse=False, blocked=False)
    ys, hfin = kernel_call(
        _gru_kernel, kernel="gru_scan_stream",
        facts=scan_facts("resident", False, t_max, b, h, 3),
        grid=(t_max,),
        in_specs=_resident_in_specs(b, h, h3, idx, midx) + [
            pl.BlockSpec((b, h), lambda t: (0, 0),
                         memory_space=pltpu.VMEM),  # carried h0
        ],
        out_specs=[
            pl.BlockSpec((1, b, h), idx, memory_space=pltpu.VMEM),
            pl.BlockSpec((b, h), lambda t: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t_max, b, h), jnp.float32),
            jax.ShapeDtypeStruct((b, h), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((b, h), jnp.float32)],
        interpret=interpret,
    )(xp_t, mask_t, w_h.astype(dot), bh2, h0.astype(jnp.float32))
    return jnp.moveaxis(ys, 0, 1), hfin


# ---------------------------------------------------------------------------
# Weight-only int8 inference kernel (VERDICT r3 #7): the quantized
# [H, 3H] matrix lives int8 in VMEM, so the flagship H=1760 (9.3 MB)
# becomes RESIDENT — the bf16 forward is the copy-once build at that
# size, its 18.6 MB copied into a VMEM scratch by the kernel. Dequantization
# never materializes a full-precision matrix: column-scale associativity,
# (h @ Q) * scale == h @ (Q * scale), moves the per-output-channel
# scale onto the [B, 3H] gates — O(B*3H) VPU work per step instead of
# O(H*3H). Inference-only (no vjp): PTQ serves decode, training stays
# on the full-precision kernels.
# ---------------------------------------------------------------------------

def _gru_kernel_q(xp_ref, mask_ref, wq_ref, sc_ref, bh_ref, *refs,
                  dot):
    """_gru_kernel with int8 weights + per-output-channel scales.

    ``dot`` (static) is the MXU operand dtype: int8 values convert to
    it losslessly (|q| <= 127 is exact even in bf16), the product
    accumulates f32, and the f32 scale lands on the gates."""
    if len(refs) == 2:
        (out_ref, h_c), h0_ref, hfin_ref = refs, None, None
    else:
        h0_ref, out_ref, hfin_ref, h_c = refs
    t = pl.program_id(0)
    b, h3 = xp_ref.shape[1], xp_ref.shape[2]
    h = h3 // 3

    @pl.when(t == 0)
    def _():
        h_c[:] = (jnp.zeros_like(h_c) if h0_ref is None else h0_ref[:])

    hprev = h_c[:]
    gates = jnp.dot(hprev.astype(dot), wq_ref[:].astype(dot),
                    preferred_element_type=jnp.float32) \
        * sc_ref[:] + bh_ref[:]
    hnew = _gru_elt(xp_ref[0], gates, hprev, mask_ref[0], h)
    h_c[:] = hnew
    out_ref[0] = hnew
    if hfin_ref is not None:
        @pl.when(t == pl.num_programs(0) - 1)
        def _():
            hfin_ref[:] = hnew


def gru_scan_pallas_q(xproj: jnp.ndarray, mask: jnp.ndarray,
                      w_q: jnp.ndarray, w_scale: jnp.ndarray,
                      b_h: jnp.ndarray, reverse: bool = False,
                      interpret: bool = False,
                      dot_dtype: Optional[str] = None,
                      h0: Optional[jnp.ndarray] = None,
                      blocked: Optional[bool] = None):
    """Fused GRU with weight-only int8 weights (inference).

    ``w_q`` int8 [H, 3H], ``w_scale`` f32 [3H] (utils/quantize.py's
    per-output-channel layout). Matches
    ``gru_scan(xproj, mask, w_q * w_scale, b_h)`` up to dot rounding.
    With ``h0`` behaves like the streaming variant and returns
    ``(ys, final_carry)``.

    Two regimes, selected by the 1-byte residency budget when
    ``blocked`` is None (True/False forces, for tests and the AOT
    traffic legs): resident int8 weights up to H=1869, s8
    column-streaming (``_gru_kernel_blocked_q``) above — the same gates,
    outputs within a few ulp where both apply
    (``tests/test_ops_quant_blocked.py``). The carried-state form (``h0``) is
    resident-only: the chunked streaming engine re-enters per chunk
    and its preset sizes are chosen to fit.
    """
    b, t_max, h3 = xproj.shape
    h = h3 // 3
    if w_q.dtype != jnp.int8:
        raise ValueError(f"w_q must be int8, got {w_q.dtype}")
    dot = _dot_jnp_dtype(dot_dtype)
    use_blocked = (_use_blocked(h, dot, weight_bytes=1)
                   if blocked is None else blocked)
    if use_blocked and h0 is not None:
        raise ValueError(
            f"int8 fused GRU with a carried state (streaming) is "
            f"resident-only; H={h} needs the blocked-q kernel, which "
            f"has no h0 variant")
    if not use_blocked and not fits_vmem(h, 1):
        raise ValueError(
            f"int8 fused GRU forced resident (blocked=False) but H={h} "
            f"exceeds the 1-byte residency budget")
    xp_t, mask_t = _time_major(xproj, mask)
    sc2 = w_scale.astype(jnp.float32).reshape(1, h3)
    bh2 = b_h.astype(jnp.float32).reshape(1, h3)
    if use_blocked:
        n_blocks, c = _block_layout(h3)
        idx, midx = _time_index_maps(t_max, reverse, blocked=True)
        ys = kernel_call(
            functools.partial(_gru_kernel_blocked_q, h=h,
                              n_blocks=n_blocks, c=c, dot=dot),
            kernel="gru_scan_q_fwd",
            facts=scan_facts("blocked_q", reverse, t_max, b, h, 3),
            grid=(t_max, n_blocks),
            in_specs=_blocked_q_in_specs(b, h, h3, c, idx, midx),
            out_specs=pl.BlockSpec((1, b, h), idx,
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((t_max, b, h), jnp.float32),
            scratch_shapes=[
                pltpu.VMEM((b, h), jnp.float32),
                pltpu.VMEM((b, n_blocks * c), jnp.float32),
            ],
            interpret=interpret,
        )(xp_t, mask_t, _pad_cols(w_q, n_blocks * c),
          _pad_cols(sc2, n_blocks * c), _pad_cols(bh2, n_blocks * c))
        return jnp.moveaxis(ys, 0, 1)
    idx, midx = _time_index_maps(t_max, reverse, blocked=False)
    const = lambda shape: pl.BlockSpec(shape, lambda t: (0, 0),
                                       memory_space=pltpu.VMEM)
    in_specs = _resident_q_in_specs(b, h, h3, idx, midx)
    kern = functools.partial(_gru_kernel_q, dot=dot)
    if h0 is None:
        ys = kernel_call(
            kern, kernel="gru_scan_q_fwd",
            facts=scan_facts("resident_q", reverse, t_max, b, h, 3),
            grid=(t_max,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, b, h), idx,
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((t_max, b, h), jnp.float32),
            scratch_shapes=[pltpu.VMEM((b, h), jnp.float32)],
            interpret=interpret,
        )(xp_t, mask_t, w_q, sc2, bh2)
        return jnp.moveaxis(ys, 0, 1)
    ys, hfin = kernel_call(
        kern, kernel="gru_scan_q_stream",
        facts=scan_facts("resident_q", reverse, t_max, b, h, 3),
        grid=(t_max,),
        in_specs=in_specs + [const((b, h))],
        out_specs=[
            pl.BlockSpec((1, b, h), idx, memory_space=pltpu.VMEM),
            const((b, h)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t_max, b, h), jnp.float32),
            jax.ShapeDtypeStruct((b, h), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((b, h), jnp.float32)],
        interpret=interpret,
    )(xp_t, mask_t, w_q, sc2, bh2, h0.astype(jnp.float32))
    return jnp.moveaxis(ys, 0, 1), hfin


def bigru_fits_vmem(hidden: int, dtype_bytes: int = 4) -> bool:
    """Both directions' [H, 3H] weight sets resident at once."""
    return fits_vmem(hidden, dtype_bytes, n_gates=6)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def bigru_scan_pallas(xproj: jnp.ndarray, mask: jnp.ndarray,
                      w_f: jnp.ndarray, b_f: jnp.ndarray,
                      w_b: jnp.ndarray, b_b: jnp.ndarray,
                      interpret: bool = False,
                      dot_dtype: Optional[str] = None) -> jnp.ndarray:
    """Fused bidirectional GRU: BOTH direction recurrences in one
    resident-weight kernel, returning the SUMMED outputs [B, T, H]
    (models/rnn.py sums directions). See _bigru_kernel for why this
    beats two serialized single-direction calls. Requires
    ``bigru_fits_vmem``; callers fall back to per-direction kernels
    otherwise."""
    ysf, ysb, _, _ = _bigru_raw(xproj, mask, w_f, b_f, w_b, b_b,
                                interpret, dot_dtype)
    return jnp.moveaxis(ysf + ysb, 0, 1)


def _bigru_raw(xproj, mask, w_f, b_f, w_b, b_b, interpret, dot_dtype):
    b, t_max, h3 = xproj.shape
    h = h3 // 3
    dot = _dot_jnp_dtype(dot_dtype)
    xp_t, mask_t = _time_major(xproj, mask)
    idx, midx = _time_index_maps(t_max, reverse=False, blocked=False)
    ridx, rmidx = _time_index_maps(t_max, reverse=True, blocked=False)
    ysf, ysb = kernel_call(
        _bigru_kernel, kernel="bigru_scan_fwd",
        facts=scan_facts("resident", "both", t_max, b, h, 3),
        grid=(t_max,),
        # The shared resident layout, once per direction (the backward
        # direction's maps mirror the time axis).
        in_specs=(_resident_in_specs(b, h, h3, idx, midx)
                  + _resident_in_specs(b, h, h3, ridx, rmidx)),
        out_specs=[
            pl.BlockSpec((1, b, h), idx, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, b, h), ridx, memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t_max, b, h), jnp.float32),
            jax.ShapeDtypeStruct((t_max, b, h), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((b, h), jnp.float32),
                        pltpu.VMEM((b, h), jnp.float32)],
        interpret=interpret,
    )(xp_t, mask_t, w_f.astype(dot),
      b_f.astype(jnp.float32).reshape(1, h3),
      xp_t, mask_t, w_b.astype(dot),
      b_b.astype(jnp.float32).reshape(1, h3))
    return ysf, ysb, xp_t, mask_t


def _bigru_fwd(xproj, mask, w_f, b_f, w_b, b_b, interpret, dot_dtype):
    ysf, ysb, xp_t, mask_t = _bigru_raw(xproj, mask, w_f, b_f, w_b, b_b,
                                        interpret, dot_dtype)
    return (jnp.moveaxis(ysf + ysb, 0, 1),
            (xp_t, mask_t, w_f, b_f, w_b, b_b, ysf, ysb))


def _bigru_bwd(interpret, dot_dtype, residuals, dy):
    xp_t, mask_t, w_f, b_f, w_b, b_b, ysf, ysb = residuals
    t_max, b, h = ysf.shape
    h3 = 3 * h
    dot = _dot_jnp_dtype(dot_dtype)
    dy_t = jnp.moveaxis(dy.astype(jnp.float32), 1, 0)  # [T, B, H]

    # Grid step i: forward direction's BPTT at data row T-1-i, backward
    # direction's at data row i (each its own reverse-scan order).
    fi = lambda i: (t_max - 1 - i, 0, 0)
    bi = lambda i: (i, 0, 0)
    # h_prev rows, clamped at each direction's recurrence start (the
    # out-of-range value is masked in-kernel at i == T-1).
    fpi = lambda i: (jnp.maximum(t_max - 2 - i, 0), 0, 0)
    bpi = lambda i: (jnp.minimum(i + 1, t_max - 1), 0, 0)
    const = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0),
                                       memory_space=pltpu.VMEM)

    dxpf, dgf, dxpb, dgb = kernel_call(
        _bigru_bwd_kernel, kernel="bigru_scan_bwd",
        facts=scan_facts("resident", "both", t_max, b, h, 3),
        grid=(t_max,),
        in_specs=[
            pl.BlockSpec((1, b, h3), fi, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, b, h3), bi, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, b, 1), fi, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, b, 1), bi, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, b, h), fpi, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, b, h), bpi, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, b, h), fi, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, b, h), bi, memory_space=pltpu.VMEM),
            const((h, h3)), const((h, h3)),
            const((1, h3)), const((1, h3)),
        ],
        out_specs=[
            pl.BlockSpec((1, b, h3), fi, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, b, h3), fi, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, b, h3), bi, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, b, h3), bi, memory_space=pltpu.VMEM),
        ],
        out_shape=[jax.ShapeDtypeStruct((t_max, b, h3), jnp.float32)
                   for _ in range(4)],
        scratch_shapes=[pltpu.VMEM((b, h), jnp.float32),
                        pltpu.VMEM((b, h), jnp.float32)],
        interpret=interpret,
    )(xp_t, xp_t, mask_t, mask_t, ysf, ysb, dy_t, dy_t,
      w_f.astype(dot), w_b.astype(dot),
      b_f.astype(jnp.float32).reshape(1, h3),
      b_b.astype(jnp.float32).reshape(1, h3))

    # h_prev sequences in data order; each direction's dW as the
    # single-direction path has it (recurrent_dw).
    hprev_f = jnp.concatenate([jnp.zeros_like(ysf[:1]), ysf[:-1]], axis=0)
    hprev_b = jnp.concatenate([ysb[1:], jnp.zeros_like(ysb[:1])], axis=0)
    dw_f = recurrent_dw(hprev_f, dgf, dot)
    dw_b = recurrent_dw(hprev_b, dgb, dot)
    dxp = jnp.moveaxis(dxpf + dxpb, 0, 1)
    return (dxp, jnp.zeros_like(mask_t[..., 0]).swapaxes(0, 1),
            dw_f.astype(w_f.dtype), jnp.sum(dgf, axis=(0, 1)).astype(
                b_f.dtype),
            dw_b.astype(w_b.dtype), jnp.sum(dgb, axis=(0, 1)).astype(
                b_b.dtype))


bigru_scan_pallas.defvjp(_bigru_fwd, _bigru_bwd)


def _gru_fwd(xproj, mask, w_h, b_h, reverse, interpret, dot_dtype):
    ys, xp_t, mask_t, _ = _gru_pallas_raw(xproj, mask, w_h, b_h, reverse,
                                          interpret, dot_dtype)
    return jnp.moveaxis(ys, 0, 1), (xp_t, mask_t, w_h, b_h, ys)


def _gru_bwd(reverse, interpret, dot_dtype, residuals, dy):
    xp_t, mask_t, w_h, b_h, ys = residuals
    t_max, b, h = ys.shape
    h3 = 3 * h
    dot = _dot_jnp_dtype(dot_dtype)
    dy_t = jnp.moveaxis(dy.astype(jnp.float32), 1, 0)  # [T, B, H]
    bh2 = b_h.astype(jnp.float32).reshape(1, h3)
    w = w_h.astype(dot)
    idx, midx = _time_index_maps(t_max, reverse, blocked=False)

    # BPTT runs opposite to the forward scan: grid step i processes
    # forward-scan step T-1-i, whose data row is idx(T-1-i).
    bidx = lambda i: idx(t_max - 1 - i)
    bmidx = lambda i: midx(t_max - 1 - i)
    # h_{t-1} of forward-scan step T-1-i lives at the row of scan
    # step T-2-i; the out-of-range value at i == T-1 (h0 = 0) is
    # masked in the kernel, so clamp the index to a valid row.
    pidx = lambda i: idx(jnp.maximum(t_max - 2 - i, 0))

    if not _use_blocked(h, dot):
        dxp_t, dgates_t = kernel_call(
            _gru_bwd_kernel, kernel="gru_scan_bwd",
            facts=scan_facts("resident", reverse, t_max, b, h, 3),
            grid=(t_max,),
            in_specs=[
                pl.BlockSpec((1, b, h3), bidx, memory_space=pltpu.VMEM),
                pl.BlockSpec((1, b, 1), bmidx, memory_space=pltpu.VMEM),
                pl.BlockSpec((1, b, h), pidx, memory_space=pltpu.VMEM),
                pl.BlockSpec((1, b, h), bidx, memory_space=pltpu.VMEM),
                pl.BlockSpec((h, h3), lambda i: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, h3), lambda i: (0, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=[pl.BlockSpec((1, b, h3), bidx,
                                    memory_space=pltpu.VMEM)] * 2,
            out_shape=[jax.ShapeDtypeStruct((t_max, b, h3),
                                            jnp.float32)] * 2,
            scratch_shapes=[pltpu.VMEM((b, h), jnp.float32)],
            interpret=interpret,
        )(xp_t, mask_t, ys, dy_t, w, bh2)
    else:
        dxp_t, dgates_t = _past_budget_scan_call(
            "gru_scan_bwd", reverse,
            [(xp_t, bidx), (mask_t, bmidx), (ys, pidx), (dy_t, bidx)],
            w, bh2, bidx, [h3, h3], interpret)

    # h_prev sequence in scan order: ys shifted by one scan step.
    if reverse:
        h_prev_seq = jnp.concatenate(
            [ys[1:], jnp.zeros_like(ys[:1])], axis=0)
    else:
        h_prev_seq = jnp.concatenate(
            [jnp.zeros_like(ys[:1]), ys[:-1]], axis=0)
    # One big MXU contraction instead of a per-step VMEM accumulator,
    # from float32 operands whatever the dot type: at dot_dtype=bf16
    # the ORACLE's dW is the noisy one (it rounds h_prev to bf16 in its
    # per-step outer products, rel err ~3e-2 vs f32 truth; tests/
    # test_pallas.py test_gru_bf16_dw_closer_to_truth_than_oracle)
    # while this contraction stays ~2e-3, which is the recurrence's own
    # bf16 noise and not the contraction's (recurrent_dw).
    dw_h = recurrent_dw(h_prev_seq, dgates_t, dot)
    db_h = jnp.sum(dgates_t, axis=(0, 1))
    dxp = jnp.moveaxis(dxp_t, 0, 1)  # [B, T, 3H]
    return (dxp, jnp.zeros_like(mask_t[..., 0]).swapaxes(0, 1),
            dw_h.astype(w_h.dtype), db_h.astype(b_h.dtype))


gru_scan_pallas.defvjp(_gru_fwd, _gru_bwd)
