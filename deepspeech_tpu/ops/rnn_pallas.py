"""Fused Pallas GRU cell (SURVEY.md §2 component 6).

The TPU-native answer to cuDNN's fused RNN kernels. This file holds the
GRU's own math (its element-wise update and BPTT step, the
both-directions step bodies) and its public functions; which build a
call runs and the one ``pallas_call`` that builds it are
``ops/scan_pallas.py``'s (``scan_route``, ``scan_call``), shared with
the LSTM cells. By where the recurrent matrix lives:

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
its shapes by ``scan_route``:

*Copy-once* (variant ``pinned``; the call's need stays under
``scan_pallas.PINNED_VMEM_CAP``: H=1760 in bf16 at every batch the
presets run). The ``[H, 3H]`` operand is taken as it is in ``pl.ANY``;
ONE DMA at the first grid step copies it whole into a VMEM scratch; the
grid is ``(T,)``, one step per time step, and each step is the resident
step with the scratch for its matrix: one ``[b, H] x [H, 3H]`` matmul
for the gates and the element-wise update straight after it, backward
also one ``dgates x W^T`` contraction. The call raises its own scoped
limit from its shapes. Where XLA's memory-space assignment left the
operand does not matter: from HBM the one copy is 18.6 MB once a call,
and nothing moves the matrix again. What this build got rid of, each
measured on the chip (PERF.md section 6): the weights crossing HBM at
every step (six of 14 backward calls until PR 27: 29.5 us a step
against 17.3); the BlockSpec pipeline's VMEM-to-VMEM copy of every
column block of a matrix XLA had already placed in VMEM (3.0 us of a
17.3 us backward step, 3.2 of a 9.2 us forward step: PR 27, PR 29); and
the column grid itself, 11 grid steps, 11 small matmuls and 11 partial
stores at each of 850 dependent time steps (PR 31; padding the
scratch's columns to the lane width reads the same to 0.002 ms a call,
so it is not padded).

*Streamed* (variant ``blocked``; past the cap: a float32 model at
H=1760 beyond a few rows, wider layers; run by no preset). The weight
columns are consumed in ``[H, C]`` blocks of ``BLOCK_COLS`` over a
``(T, G)`` grid, moved by the BlockSpec pipeline from wherever XLA left
the operand: from HBM that is the whole matrix every step, the honest
cost of a matrix that cannot live in VMEM. The column grid is there for
THIS build alone, because a pipelined operand is double-buffered: the
whole matrix as one block would cost twice its size where two 1.8 MB
column blocks do. Each step's matmul runs as G block matmuls whose
partials land in a VMEM scratch, the GRU elementwise update firing on
the last block; the backward step needs the blocks once per step: it
pipelines the ``dgates @ W^T`` contraction one step behind the gate
recompute (SURVEY.md §7 hard-parts #2). The copy-once backward step
does not: with the matrix whole in its scratch a second pass costs
nothing, and the resident body's order measured faster on the chip.

**int8 resident / int8 blocked streaming** (weight-only PTQ serving):
``gru_scan_pallas_q`` keeps the QUANTIZED matrix resident — int8
quadruples the residency reach over f32, so the flagship H=1760
(9.3 MB) needs no column grid at all (its bf16 forward is the
copy-once build); scales apply to the gates via column-scale
associativity, ``(h @ Q) * scale == h @ (Q * scale)``: O(B*3H) VPU
work per step instead of O(H*3H), and no full-precision matrix is ever
materialized. Past even the 1-byte budget (GRU H>1869; LSTM's 4-gate
layout already at H=1620) the q path is the ``blocked_q`` build: the
SAME ``(T, G)`` column-streaming grid as the fp streamed build, but the
moving ``[H, C]`` tile is s8 and the dequant (upcast next to the sliced
per-output-channel scale columns) happens in VMEM — per-step HBM weight
traffic is the int8 bytes, 4× less than the f32 stream. Its outputs
agree with the resident kernel's to a few ulp, not to the bit: the
elementwise update after the gates is compiled apart in the two
programs. Inference-only (no vjp): PTQ serves decode, training stays on
the full-precision kernels.

**Both directions of a layer** run as one function of the layer's
``xproj``. Where both matrices fit VMEM together: ONE kernel
(``bigru_scan_pallas``, below). Where they do not (the flagship):
``gru_scan_pair_pallas`` (``scan_pallas.scan_pair_vjp``), which takes
the projection's matmul and its bias apart and adds them itself, then
the two forward calls one after the other, and backward the forward
direction's call handing its float32 ``dxp`` rows to the reverse
direction's, which adds its own while they are in VMEM and writes the
ONE sum rounded to ``xproj``'s dtype, with the float32 sum's column
sums (the projection's bias gradient) beside it: no pass outside the
kernels adds, casts or reduces the two directions' ``[T, b, 3H]``
gradients.

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

from . import scan_pallas
from .scan_pallas import (ScanCell, dot_jnp_dtype, own_route, prev_sequence,
                          scan_call, scan_forward, scan_pair_vjp, scan_vjp,
                          time_index_maps, time_major)


def _gru_elt(xp, gates, states, m):
    """The GRU's elementwise update: (xp [B,3H], gates [B,3H] f32,
    (hprev [B,H],), mask [B,1]) -> (new hidden [B,H],)."""
    hprev, = states
    h = hprev.shape[-1]
    r = jax.nn.sigmoid(xp[:, :h] + gates[:, :h])
    z = jax.nn.sigmoid(xp[:, h:2 * h] + gates[:, h:2 * h])
    n = jnp.tanh(xp[:, 2 * h:] + r * gates[:, 2 * h:])
    hnew = (1.0 - z) * n + z * hprev
    return m * hnew + (1.0 - m) * hprev,


def _gru_bwd_elt(xp, gates, prevs, m, dstates, dy):
    """One-step GRU BPTT math. Returns (dxp, dgates,
    (dh_prev_elementwise,)) — the ``dgates @ W^T`` term is the caller's
    (it differs between the builds and the fused-bidir layout)."""
    hprev, = prevs
    h = hprev.shape[-1]
    dh = dstates[0] + dy
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
    return dxp, dgates, (dh_elt,)


GRU = ScanCell("gru", _gru_elt, _gru_bwd_elt)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def gru_scan_pallas(xproj: jnp.ndarray, mask: jnp.ndarray,
                    w_h: jnp.ndarray, b_h: jnp.ndarray,
                    reverse: bool = False,
                    interpret: bool = False,
                    dot_dtype: Optional[str] = None) -> jnp.ndarray:
    """Fused GRU recurrence. See module docstring for the contract."""
    (ys,), _, _ = scan_forward(GRU, xproj, mask, w_h, b_h, reverse=reverse,
                               interpret=interpret, dot_dtype=dot_dtype)
    return jnp.moveaxis(ys, 0, 1)  # [B, T, H]


gru_scan_pallas.defvjp(*scan_vjp(GRU))


# Both directions of a bidirectional GRU layer whose matrices do not
# fit VMEM together, summed [B, T, H]: gru_scan_pallas's two forward
# calls as ONE function, so that its VJP's two backward calls make
# xproj's gradient between them, the bias' too. (product, mask, b_x,
# w_f, b_f, w_b, b_b, interpret, dot_dtype); xproj = product + b_x.
gru_scan_pair_pallas = scan_pair_vjp(GRU)


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
    scan otherwise (``scan_route(..., carry=True)``).
    """
    (ys, hfin), _, _ = scan_forward(GRU, xproj, mask, w_h, b_h, h0=h0,
                                    interpret=interpret,
                                    dot_dtype=dot_dtype)
    return jnp.moveaxis(ys, 0, 1), hfin


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
    column-streaming above — the same gates, outputs within a few ulp
    where both apply (``tests/test_ops_quant_blocked.py``). The
    carried-state form (``h0``) is resident-only: the chunked streaming
    engine re-enters per chunk and its preset sizes are chosen to fit.
    """
    out, _, _ = scan_forward(GRU, xproj, mask, w_q, b_h, scale=w_scale,
                             reverse=reverse, interpret=interpret,
                             dot_dtype=dot_dtype, h0=h0, blocked=blocked)
    ys = jnp.moveaxis(out[0], 0, 1)
    return ys if h0 is None else (ys, out[1])


# ---------------------------------------------------------------------------
# Both directions of a resident-weight BiGRU in one time grid: the step
# bodies are this file's (two recurrences a grid step), the specs and
# the call scan_pallas's.
# ---------------------------------------------------------------------------

def _bigru_kernel(xpf_ref, mf_ref, xpb_ref, mb_ref,
                  whf_ref, bhf_ref, whb_ref, bhb_ref,
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

    @pl.when(t == 0)
    def _():
        hf_c[:] = jnp.zeros_like(hf_c)
        hb_c[:] = jnp.zeros_like(hb_c)

    hf, hb = hf_c[:], hb_c[:]
    gf = jnp.dot(hf.astype(whf_ref.dtype), whf_ref[:],
                 preferred_element_type=jnp.float32) + bhf_ref[:]
    gb = jnp.dot(hb.astype(whb_ref.dtype), whb_ref[:],
                 preferred_element_type=jnp.float32) + bhb_ref[:]
    hf_new, = _gru_elt(xpf_ref[0], gf, (hf,), mf_ref[0])
    hb_new, = _gru_elt(xpb_ref[0], gb, (hb,), mb_ref[0])
    hf_c[:] = hf_new
    hb_c[:] = hb_new
    outf_ref[0] = hf_new
    outb_ref[0] = hb_new


def _bigru_bwd_kernel(xpf_ref, mf_ref, ysf_prev_ref, dyf_ref,
                      xpb_ref, mb_ref, ysb_prev_ref, dyb_ref,
                      whf_ref, bhf_ref, whb_ref, bhb_ref,
                      dxpf_ref, dgf_ref, dxpb_ref, dgb_ref,
                      dhf_c, dhb_c):
    """Fused BPTT for both directions (flash-style gate recompute).

    Grid step i runs the forward direction's BPTT at data row T-1-i
    and the backward direction's at data row i — each direction's own
    reverse-scan order, both recurrence starts landing on the same
    boundary i == T-1 (where h_prev is the zero initial state).
    """
    i = pl.program_id(0)

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
    dxpf, dgf, (dhf_elt,) = _gru_bwd_elt(
        xpf_ref[0], gf, (hf_prev,), mf_ref[0], (dhf_c[:],), dyf_ref[0])
    dxpb, dgb, (dhb_elt,) = _gru_bwd_elt(
        xpb_ref[0], gb, (hb_prev,), mb_ref[0], (dhb_c[:],), dyb_ref[0])
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


def _bigru_call(body, backward, rows, w_f, b_f, w_b, b_b, outs, interpret,
                dot_dtype):
    """Either both-directions call: the per-step ``rows`` of the two
    directions, then their matrices and biases, all resident."""
    t_max, b = rows[0][0].shape[:2]
    h = w_f.shape[0]
    dot = dot_jnp_dtype(dot_dtype)
    route = own_route("both_bwd" if backward else "both_fwd", "gru",
                      rows=b, hidden=h,
                      dot_bytes=jnp.dtype(dot).itemsize, directions=2,
                      backward=backward)
    column = lambda v: v.astype(jnp.float32).reshape(1, 3 * h)
    return scan_call(
        body, route, reverse="both", hidden=h, gates=3, rows=rows,
        weights=[w_f.astype(dot), column(b_f), w_b.astype(dot),
                 column(b_b)],
        outs=outs, scratch=lambda cols: [h, h], interpret=interpret)


def _bigru_raw(xproj, mask, w_f, b_f, w_b, b_b, interpret, dot_dtype):
    h = w_f.shape[0]
    xp_t, mask_t = time_major(xproj, mask)
    at_f, _, _ = time_index_maps(xp_t.shape[0], reverse=False)
    at_b, _, _ = time_index_maps(xp_t.shape[0], reverse=True)
    ysf, ysb = _bigru_call(
        _bigru_kernel, False,
        [(xp_t, at_f), (mask_t, at_f), (xp_t, at_b), (mask_t, at_b)],
        w_f, b_f, w_b, b_b,
        [(h, jnp.float32, at_f), (h, jnp.float32, at_b)],
        interpret, dot_dtype)
    return ysf, ysb, xp_t, mask_t


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def bigru_scan_pallas(xproj: jnp.ndarray, mask: jnp.ndarray,
                      w_f: jnp.ndarray, b_f: jnp.ndarray,
                      w_b: jnp.ndarray, b_b: jnp.ndarray,
                      interpret: bool = False,
                      dot_dtype: Optional[str] = None) -> jnp.ndarray:
    """Fused bidirectional GRU: BOTH direction recurrences in one
    resident-weight kernel, returning the SUMMED outputs [B, T, H]
    (models/rnn.py sums directions). See _bigru_kernel for why this
    beats two serialized single-direction calls. Both weight sets have
    to be resident at once (``scan_route(..., directions=2)`` names this
    kernel then; callers run the directions one by one otherwise)."""
    ysf, ysb, _, _ = _bigru_raw(xproj, mask, w_f, b_f, w_b, b_b,
                                interpret, dot_dtype)
    return jnp.moveaxis(ysf + ysb, 0, 1)


@jax.named_scope("rnn_scan")
def _bigru_fwd(xproj, mask, w_f, b_f, w_b, b_b, interpret, dot_dtype):
    ysf, ysb, xp_t, mask_t = _bigru_raw(xproj, mask, w_f, b_f, w_b, b_b,
                                        interpret, dot_dtype)
    return (jnp.moveaxis(ysf + ysb, 0, 1),
            (xp_t, mask_t, w_f, b_f, w_b, b_b, ysf, ysb))


@jax.named_scope("rnn_scan")
def _bigru_bwd(interpret, dot_dtype, residuals, dy):
    xp_t, mask_t, w_f, b_f, w_b, b_b, ysf, ysb = residuals
    t_max, _, h = ysf.shape
    dot = dot_jnp_dtype(dot_dtype)
    dy_t = jnp.moveaxis(dy.astype(jnp.float32), 1, 0)  # [T, B, H]
    # Grid step i: forward direction's BPTT at data row T-1-i, backward
    # direction's at data row i (each its own reverse-scan order).
    _, f_at, f_prev = time_index_maps(t_max, reverse=False)
    _, b_at, b_prev = time_index_maps(t_max, reverse=True)
    dxpf, dgf, dxpb, dgb = _bigru_call(
        _bigru_bwd_kernel, True,
        [(xp_t, f_at), (mask_t, f_at), (ysf, f_prev), (dy_t, f_at),
         (xp_t, b_at), (mask_t, b_at), (ysb, b_prev), (dy_t, b_at)],
        w_f, b_f, w_b, b_b,
        [(3 * h, jnp.float32, f_at)] * 2 + [(3 * h, jnp.float32, b_at)] * 2,
        interpret, dot_dtype)
    # each direction's dW as the single-direction path has it
    dw_f = scan_pallas.recurrent_dw(prev_sequence(ysf, False), dgf, dot)
    dw_b = scan_pallas.recurrent_dw(prev_sequence(ysb, True), dgb, dot)
    dxp = jnp.moveaxis(dxpf + dxpb, 0, 1)
    return (dxp, jnp.zeros_like(mask_t[..., 0]).swapaxes(0, 1),
            dw_f.astype(w_f.dtype), jnp.sum(dgf, axis=(0, 1)).astype(
                b_f.dtype),
            dw_b.astype(w_b.dtype), jnp.sum(dgb, axis=(0, 1)).astype(
                b_b.dtype))


bigru_scan_pallas.defvjp(_bigru_fwd, _bigru_bwd)
