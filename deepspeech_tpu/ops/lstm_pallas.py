"""Fused Pallas LSTM cell (SURVEY.md §2 component 6, LSTM variant).

Same two regimes as the GRU cell (ops/rnn_pallas.py): VMEM-resident
``[H, 4H]`` weights for small/medium H, blocked column streaming with
automatic double buffering above that. The recurrence matches
``models.rnn.lstm_scan`` (the XLA oracle), including the +1.0
forget-gate bias trick and mask-held h/c for padded frames.

Backward is BPTT with gate recompute: the forward tapes the cell-state
sequence ``cs`` alongside the outputs ``ys`` (cuDNN does the same),
and the backward kernel recomputes the four gate activations from
(h_prev, c_prev, xproj, W) instead of storing them. The blocked
backward pipelines the ``dgates @ W^T`` contraction one step behind
the gate recompute so each weight block streams once per time step.

Gate order i, f, g, o:
  i = sigmoid(xp_i + h W_i + b_i)
  f = sigmoid(xp_f + h W_f + b_f + 1)
  g = tanh   (xp_g + h W_g + b_g)
  o = sigmoid(xp_o + h W_o + b_o)
  c' = f*c + i*g ;  h' = o * tanh(c')
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernel_id import kernel_call, scan_facts
from .rnn_pallas import (_block_layout, _blocked_q_in_specs,
                         _dot_jnp_dtype, _pad_cols,
                         _resident_in_specs, _resident_q_in_specs,
                         _time_index_maps, _time_major,
                         _use_blocked, fits_vmem, recurrent_dw)


def _lstm_elementwise_fwd(xp, gates, hprev, cprev, m):
    h = hprev.shape[-1]
    i = jax.nn.sigmoid(xp[:, :h] + gates[:, :h])
    f = jax.nn.sigmoid(xp[:, h:2 * h] + gates[:, h:2 * h] + 1.0)
    g = jnp.tanh(xp[:, 2 * h:3 * h] + gates[:, 2 * h:3 * h])
    o = jax.nn.sigmoid(xp[:, 3 * h:] + gates[:, 3 * h:])
    cnew = f * cprev + i * g
    hnew = o * jnp.tanh(cnew)
    hnew = m * hnew + (1.0 - m) * hprev
    cnew = m * cnew + (1.0 - m) * cprev
    return hnew, cnew


def _lstm_elementwise_bwd(xp, gates, hprev, cprev, m, dh_in, dc_in, dy):
    """Shared VPU math for one reverse step.

    Returns (dgates, dh_prev_local, dc_prev) where dh_prev_local still
    lacks the dgates @ W^T term (regime-specific).
    """
    h = hprev.shape[-1]
    i = jax.nn.sigmoid(xp[:, :h] + gates[:, :h])
    f = jax.nn.sigmoid(xp[:, h:2 * h] + gates[:, h:2 * h] + 1.0)
    g = jnp.tanh(xp[:, 2 * h:3 * h] + gates[:, 2 * h:3 * h])
    o = jax.nn.sigmoid(xp[:, 3 * h:] + gates[:, 3 * h:])
    cnew = f * cprev + i * g
    tc = jnp.tanh(cnew)

    dh = dh_in + dy
    dh_mid = m * dh
    do = dh_mid * tc
    dc_pre = m * dc_in + dh_mid * o * (1.0 - tc * tc)
    di = dc_pre * g
    df = dc_pre * cprev
    dg = dc_pre * i
    da_i = di * i * (1.0 - i)
    da_f = df * f * (1.0 - f)
    da_g = dg * (1.0 - g * g)
    da_o = do * o * (1.0 - o)
    dgates = jnp.concatenate([da_i, da_f, da_g, da_o], axis=1)
    dh_prev_local = (1.0 - m) * dh
    dc_prev = dc_pre * f + (1.0 - m) * dc_in
    return dgates, dh_prev_local, dc_prev


# ---------------------------------------------------------------------------
# Kernels.
# ---------------------------------------------------------------------------

def _lstm_kernel(xp_ref, mask_ref, wh_ref, bh_ref, *refs):
    # refs = (ys_ref, cs_ref, h_c, c_c) when taping the cell-state
    # sequence for BPTT, (ys_ref, h_c, c_c) on the no-grad eval path
    # (skips the [T, B, H] HBM tape write entirely).
    if len(refs) == 4:
        ys_ref, cs_ref, h_c, c_c = refs
    else:
        (ys_ref, h_c, c_c), cs_ref = refs, None
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        h_c[:] = jnp.zeros_like(h_c)
        c_c[:] = jnp.zeros_like(c_c)

    hprev, cprev = h_c[:], c_c[:]
    gates = jnp.dot(hprev.astype(wh_ref.dtype), wh_ref[:],
                    preferred_element_type=jnp.float32) + bh_ref[:]
    m = mask_ref[0]
    hnew, cnew = _lstm_elementwise_fwd(xp_ref[0], gates, hprev, cprev, m)
    h_c[:] = hnew
    c_c[:] = cnew
    ys_ref[0] = hnew
    if cs_ref is not None:
        cs_ref[0] = cnew


def _lstm_kernel_blocked(xp_ref, mask_ref, wh_ref, bh_ref, *refs,
                         h: int, n_blocks: int, c: int):
    if len(refs) == 5:
        ys_ref, cs_ref, h_c, c_c, gates_buf = refs
    else:
        (ys_ref, h_c, c_c, gates_buf), cs_ref = refs, None
    t = pl.program_id(0)
    g = pl.program_id(1)

    @pl.when((t == 0) & (g == 0))
    def _():
        h_c[:] = jnp.zeros_like(h_c)
        c_c[:] = jnp.zeros_like(c_c)

    hprev = h_c[:]
    blk = jnp.dot(hprev.astype(wh_ref.dtype), wh_ref[:],
                  preferred_element_type=jnp.float32) + bh_ref[:]
    gates_buf[:, pl.ds(g * c, c)] = blk

    @pl.when(g == n_blocks - 1)
    def _():
        m = mask_ref[0]
        hnew, cnew = _lstm_elementwise_fwd(
            xp_ref[0], gates_buf[:, :4 * h], hprev, c_c[:], m)
        h_c[:] = hnew
        c_c[:] = cnew
        ys_ref[0] = hnew
        if cs_ref is not None:
            cs_ref[0] = cnew


def _lstm_bwd_kernel(xp_ref, mask_ref, ys_prev_ref, cs_prev_ref, dy_ref,
                     wh_ref, bh_ref, dxp_ref, dgates_ref, dh_c, dc_c):
    ti = pl.program_id(0)

    @pl.when(ti == 0)
    def _():
        dh_c[:] = jnp.zeros_like(dh_c)
        dc_c[:] = jnp.zeros_like(dc_c)

    first = ti == pl.num_programs(0) - 1
    hprev = jnp.where(first, jnp.zeros_like(ys_prev_ref[0]),
                      ys_prev_ref[0])
    cprev = jnp.where(first, jnp.zeros_like(cs_prev_ref[0]),
                      cs_prev_ref[0])
    gates = jnp.dot(hprev.astype(wh_ref.dtype), wh_ref[:],
                    preferred_element_type=jnp.float32) + bh_ref[:]
    m = mask_ref[0]
    dgates, dh_local, dc_prev = _lstm_elementwise_bwd(
        xp_ref[0], gates, hprev, cprev, m, dh_c[:], dc_c[:], dy_ref[0])
    dxp_ref[0] = dgates
    dgates_ref[0] = dgates
    dh_c[:] = dh_local + jax.lax.dot_general(
        dgates.astype(wh_ref.dtype), wh_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    dc_c[:] = dc_prev


def _lstm_bwd_kernel_blocked(xp_ref, mask_ref, ys_prev_ref, cs_prev_ref,
                             dy_ref, wh_ref, bh_ref, dxp_ref, dgates_ref,
                             dh_c, dc_c, dh_acc, gates_buf, dg_prev,
                             *, h: int, n_blocks: int, c: int):
    ti = pl.program_id(0)
    g = pl.program_id(1)

    @pl.when((ti == 0) & (g == 0))
    def _():
        dh_c[:] = jnp.zeros_like(dh_c)
        dc_c[:] = jnp.zeros_like(dc_c)
        dg_prev[:] = jnp.zeros_like(dg_prev)

    @pl.when(g == 0)
    def _():
        dh_acc[:] = jnp.zeros_like(dh_acc)

    first = ti == pl.num_programs(0) - 1
    hprev = jnp.where(first, jnp.zeros_like(ys_prev_ref[0]),
                      ys_prev_ref[0])
    blk = jnp.dot(hprev.astype(wh_ref.dtype), wh_ref[:],
                  preferred_element_type=jnp.float32) + bh_ref[:]
    gates_buf[:, pl.ds(g * c, c)] = blk

    dgp = dg_prev[:, pl.ds(g * c, c)]
    dh_acc[:] += jax.lax.dot_general(
        dgp.astype(wh_ref.dtype), wh_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(g == n_blocks - 1)
    def _():
        cprev = jnp.where(first, jnp.zeros_like(cs_prev_ref[0]),
                          cs_prev_ref[0])
        m = mask_ref[0]
        dgates, dh_local, dc_prev = _lstm_elementwise_bwd(
            xp_ref[0], gates_buf[:, :4 * h], hprev, cprev, m,
            dh_c[:] + dh_acc[:], dc_c[:], dy_ref[0])
        dxp_ref[0] = dgates
        dgates_ref[0] = dgates
        dg_prev[:, :4 * h] = dgates
        # dgates @ W^T rides the NEXT step's weight stream (dh_acc).
        dh_c[:] = dh_local
        dc_c[:] = dc_prev


# ---------------------------------------------------------------------------
# Host-side wiring.
# ---------------------------------------------------------------------------

def _lstm_pallas_raw(xproj, mask, w_h, b_h, reverse, interpret, dot_dtype,
                     want_cs: bool = True):
    """want_cs=False (no-grad primal) skips the [T,B,H] cell-state tape
    write; the BPTT backward needs it, eval/infer forward does not."""
    b, t_max, h4 = xproj.shape
    h = h4 // 4
    dot = _dot_jnp_dtype(dot_dtype)
    xp_t, mask_t = _time_major(xproj, mask)
    bh2 = b_h.astype(jnp.float32).reshape(1, h4)
    w = w_h.astype(dot)
    n_out = 2 if want_cs else 1
    out_shape = [jax.ShapeDtypeStruct((t_max, b, h), jnp.float32)] * n_out

    if not _use_blocked(h, dot, n_gates=4):
        idx, midx = _time_index_maps(t_max, reverse, blocked=False)
        out = kernel_call(
            _lstm_kernel, kernel="lstm_scan_fwd",
            facts=scan_facts("resident", reverse, t_max, b, h, 4),
            grid=(t_max,),
            in_specs=_resident_in_specs(b, h, h4, idx, midx),
            out_specs=[
                pl.BlockSpec((1, b, h), idx, memory_space=pltpu.VMEM),
            ] * n_out,
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((b, h), jnp.float32)] * 2,
            interpret=interpret,
        )(xp_t, mask_t, w, bh2)
    else:
        n_blocks, c = _block_layout(h4)
        idx, midx = _time_index_maps(t_max, reverse, blocked=True)
        out = kernel_call(
            functools.partial(_lstm_kernel_blocked, h=h, n_blocks=n_blocks,
                              c=c),
            kernel="lstm_scan_fwd",
            facts=scan_facts("blocked", reverse, t_max, b, h, 4),
            grid=(t_max, n_blocks),
            in_specs=[
                pl.BlockSpec((1, b, h4), idx, memory_space=pltpu.VMEM),
                pl.BlockSpec((1, b, 1), midx, memory_space=pltpu.VMEM),
                pl.BlockSpec((h, c), lambda t, g: (0, g),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, c), lambda t, g: (0, g),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=[
                pl.BlockSpec((1, b, h), idx, memory_space=pltpu.VMEM),
            ] * n_out,
            out_shape=out_shape,
            scratch_shapes=[
                pltpu.VMEM((b, h), jnp.float32),
                pltpu.VMEM((b, h), jnp.float32),
                pltpu.VMEM((b, n_blocks * c), jnp.float32),
            ],
            interpret=interpret,
        )(xp_t, mask_t, _pad_cols(w, n_blocks * c),
          _pad_cols(bh2, n_blocks * c))
    ys, cs = out if want_cs else (out[0], None)
    return ys, cs, xp_t, mask_t


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def lstm_scan_pallas(xproj: jnp.ndarray, mask: jnp.ndarray,
                     w_h: jnp.ndarray, b_h: jnp.ndarray,
                     reverse: bool = False,
                     interpret: bool = False,
                     dot_dtype: Optional[str] = None) -> jnp.ndarray:
    """Fused LSTM recurrence; contract matches models.rnn.lstm_scan."""
    ys, _, _, _ = _lstm_pallas_raw(xproj, mask, w_h, b_h, reverse,
                                   interpret, dot_dtype, want_cs=False)
    return jnp.moveaxis(ys, 0, 1)


def _lstm_kernel_q(xp_ref, mask_ref, wq_ref, sc_ref, bh_ref, ys_ref,
                   h_c, c_c, *, dot):
    """Weight-only int8 eval kernel: gates = (h @ Q) * scale + b (the
    same column-scale-after-dot refactoring as rnn_pallas's
    _gru_kernel_q; |q| <= 127 converts to ``dot`` losslessly)."""
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        h_c[:] = jnp.zeros_like(h_c)
        c_c[:] = jnp.zeros_like(c_c)

    hprev, cprev = h_c[:], c_c[:]
    gates = jnp.dot(hprev.astype(dot), wq_ref[:].astype(dot),
                    preferred_element_type=jnp.float32) \
        * sc_ref[:] + bh_ref[:]
    hnew, cnew = _lstm_elementwise_fwd(xp_ref[0], gates, hprev, cprev,
                                       mask_ref[0])
    h_c[:] = hnew
    c_c[:] = cnew
    ys_ref[0] = hnew


def _lstm_kernel_blocked_q(xp_ref, mask_ref, wq_ref, sc_ref, bh_ref,
                           ys_ref, h_c, c_c, gates_buf, *,
                           h: int, n_blocks: int, c: int, dot):
    """_lstm_kernel_blocked with int8 weight tiles (see rnn_pallas's
    _gru_kernel_blocked_q): the streamed [H, C] block is s8, upcast in
    VMEM next to its sliced scale columns, so per-step HBM weight
    traffic is the quantized bytes. No cell-state tape (eval-only)."""
    t = pl.program_id(0)
    g = pl.program_id(1)

    @pl.when((t == 0) & (g == 0))
    def _():
        h_c[:] = jnp.zeros_like(h_c)
        c_c[:] = jnp.zeros_like(c_c)

    hprev = h_c[:]
    blk = jnp.dot(hprev.astype(dot), wq_ref[:].astype(dot),
                  preferred_element_type=jnp.float32) \
        * sc_ref[:] + bh_ref[:]
    gates_buf[:, pl.ds(g * c, c)] = blk

    @pl.when(g == n_blocks - 1)
    def _():
        hnew, cnew = _lstm_elementwise_fwd(
            xp_ref[0], gates_buf[:, :4 * h], hprev, c_c[:], mask_ref[0])
        h_c[:] = hnew
        c_c[:] = cnew
        ys_ref[0] = hnew


def lstm_scan_pallas_q(xproj: jnp.ndarray, mask: jnp.ndarray,
                       w_q: jnp.ndarray, w_scale: jnp.ndarray,
                       b_h: jnp.ndarray, reverse: bool = False,
                       interpret: bool = False,
                       dot_dtype: Optional[str] = None,
                       blocked: Optional[bool] = None) -> jnp.ndarray:
    """Fused LSTM with weight-only int8 weights (inference).

    ``w_q`` int8 [H, 4H], ``w_scale`` f32 [4H] per-output-channel;
    matches ``lstm_scan(xproj, mask, w_q * w_scale, b_h)`` up to dot
    rounding. Same two regimes as ``gru_scan_pallas_q`` (``blocked``
    None = auto by the 1-byte budget): resident int8 up to H=1619,
    s8 column-streaming above — which covers the flagship H=1760,
    whose 4-gate 12.4 MB int8 matrix misses residency. No cell-state
    tape in either regime (eval has no BPTT).
    """
    b, t_max, h4 = xproj.shape
    h = h4 // 4
    if w_q.dtype != jnp.int8:
        raise ValueError(f"w_q must be int8, got {w_q.dtype}")
    dot = _dot_jnp_dtype(dot_dtype)
    use_blocked = (_use_blocked(h, dot, n_gates=4, weight_bytes=1)
                   if blocked is None else blocked)
    if not use_blocked and not fits_vmem(h, 1, n_gates=4):
        raise ValueError(
            f"int8 fused LSTM forced resident (blocked=False) but H={h} "
            f"exceeds the 1-byte residency budget")
    xp_t, mask_t = _time_major(xproj, mask)
    sc2 = w_scale.astype(jnp.float32).reshape(1, h4)
    bh2 = b_h.astype(jnp.float32).reshape(1, h4)
    if use_blocked:
        n_blocks, c = _block_layout(h4)
        idx, midx = _time_index_maps(t_max, reverse, blocked=True)
        ys = kernel_call(
            functools.partial(_lstm_kernel_blocked_q, h=h,
                              n_blocks=n_blocks, c=c, dot=dot),
            kernel="lstm_scan_q_fwd",
            facts=scan_facts("blocked_q", reverse, t_max, b, h, 4),
            grid=(t_max, n_blocks),
            in_specs=_blocked_q_in_specs(b, h, h4, c, idx, midx),
            out_specs=pl.BlockSpec((1, b, h), idx,
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((t_max, b, h), jnp.float32),
            scratch_shapes=[
                pltpu.VMEM((b, h), jnp.float32),
                pltpu.VMEM((b, h), jnp.float32),
                pltpu.VMEM((b, n_blocks * c), jnp.float32),
            ],
            interpret=interpret,
        )(xp_t, mask_t, _pad_cols(w_q, n_blocks * c),
          _pad_cols(sc2, n_blocks * c), _pad_cols(bh2, n_blocks * c))
        return jnp.moveaxis(ys, 0, 1)
    idx, midx = _time_index_maps(t_max, reverse, blocked=False)
    ys = kernel_call(
        functools.partial(_lstm_kernel_q, dot=dot),
        kernel="lstm_scan_q_fwd",
        facts=scan_facts("resident_q", reverse, t_max, b, h, 4),
        grid=(t_max,),
        # Shared with gru_scan_pallas_q: specs in OPERAND order
        # (xp, mask, w_q, scale, bias) from one constructor (ADVICE r4).
        in_specs=_resident_q_in_specs(b, h, h4, idx, midx),
        out_specs=pl.BlockSpec((1, b, h), idx, memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((t_max, b, h), jnp.float32),
        scratch_shapes=[pltpu.VMEM((b, h), jnp.float32)] * 2,
        interpret=interpret,
    )(xp_t, mask_t, w_q, sc2, bh2)
    return jnp.moveaxis(ys, 0, 1)


def _lstm_fwd(xproj, mask, w_h, b_h, reverse, interpret, dot_dtype):
    ys, cs, xp_t, mask_t = _lstm_pallas_raw(xproj, mask, w_h, b_h, reverse,
                                            interpret, dot_dtype)
    return jnp.moveaxis(ys, 0, 1), (xp_t, mask_t, w_h, b_h, ys, cs)


def _lstm_bwd(reverse, interpret, dot_dtype, residuals, dy):
    xp_t, mask_t, w_h, b_h, ys, cs = residuals
    t_max, b, h = ys.shape
    h4 = 4 * h
    dot = _dot_jnp_dtype(dot_dtype)
    dy_t = jnp.moveaxis(dy.astype(jnp.float32), 1, 0)
    bh2 = b_h.astype(jnp.float32).reshape(1, h4)
    w = w_h.astype(dot)
    blocked = _use_blocked(h, dot, n_gates=4)
    idx, midx = _time_index_maps(t_max, reverse, blocked=blocked)

    if blocked:
        bidx = lambda i, g: idx(t_max - 1 - i, g)
        bmidx = lambda i, g: midx(t_max - 1 - i, g)
        pidx = lambda i, g: idx(jnp.maximum(t_max - 2 - i, 0), g)
    else:
        bidx = lambda i: idx(t_max - 1 - i)
        bmidx = lambda i: midx(t_max - 1 - i)
        pidx = lambda i: idx(jnp.maximum(t_max - 2 - i, 0))

    out_specs = [
        pl.BlockSpec((1, b, h4), bidx, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, b, h4), bidx, memory_space=pltpu.VMEM),
    ]
    out_shape = [jax.ShapeDtypeStruct((t_max, b, h4), jnp.float32)] * 2

    if not blocked:
        dxp_t, dgates_t = kernel_call(
            _lstm_bwd_kernel, kernel="lstm_scan_bwd",
            facts=scan_facts("resident", reverse, t_max, b, h, 4),
            grid=(t_max,),
            in_specs=[
                pl.BlockSpec((1, b, h4), bidx, memory_space=pltpu.VMEM),
                pl.BlockSpec((1, b, 1), bmidx, memory_space=pltpu.VMEM),
                pl.BlockSpec((1, b, h), pidx, memory_space=pltpu.VMEM),
                pl.BlockSpec((1, b, h), pidx, memory_space=pltpu.VMEM),
                pl.BlockSpec((1, b, h), bidx, memory_space=pltpu.VMEM),
                pl.BlockSpec((h, h4), lambda i: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, h4), lambda i: (0, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((b, h), jnp.float32)] * 2,
            interpret=interpret,
        )(xp_t, mask_t, ys, cs, dy_t, w, bh2)
    else:
        n_blocks, c = _block_layout(h4)
        dxp_t, dgates_t = kernel_call(
            functools.partial(_lstm_bwd_kernel_blocked, h=h,
                              n_blocks=n_blocks, c=c),
            kernel="lstm_scan_bwd",
            facts=scan_facts("blocked", reverse, t_max, b, h, 4),
            grid=(t_max, n_blocks),
            in_specs=[
                pl.BlockSpec((1, b, h4), bidx, memory_space=pltpu.VMEM),
                pl.BlockSpec((1, b, 1), bmidx, memory_space=pltpu.VMEM),
                pl.BlockSpec((1, b, h), pidx, memory_space=pltpu.VMEM),
                pl.BlockSpec((1, b, h), pidx, memory_space=pltpu.VMEM),
                pl.BlockSpec((1, b, h), bidx, memory_space=pltpu.VMEM),
                pl.BlockSpec((h, c), lambda i, g: (0, g),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, c), lambda i, g: (0, g),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[
                pltpu.VMEM((b, h), jnp.float32),
                pltpu.VMEM((b, h), jnp.float32),
                pltpu.VMEM((b, h), jnp.float32),
                pltpu.VMEM((b, n_blocks * c), jnp.float32),
                pltpu.VMEM((b, n_blocks * c), jnp.float32),
            ],
            interpret=interpret,
        )(xp_t, mask_t, ys, cs, dy_t, _pad_cols(w, n_blocks * c),
          _pad_cols(bh2, n_blocks * c))

    if reverse:
        h_prev_seq = jnp.concatenate(
            [ys[1:], jnp.zeros_like(ys[:1])], axis=0)
    else:
        h_prev_seq = jnp.concatenate(
            [jnp.zeros_like(ys[:1]), ys[:-1]], axis=0)
    # float32 operands, never rounded to 8 bits, at the precision the
    # dot type states: the GRU's rule (rnn_pallas.recurrent_dw).
    dw_h = recurrent_dw(h_prev_seq, dgates_t, dot)
    db_h = jnp.sum(dgates_t, axis=(0, 1))
    dxp = jnp.moveaxis(dxp_t, 0, 1)
    return (dxp, jnp.zeros_like(mask_t[..., 0]).swapaxes(0, 1),
            dw_h.astype(w_h.dtype), db_h.astype(b_h.dtype))


lstm_scan_pallas.defvjp(_lstm_fwd, _lstm_bwd)


# ---------------------------------------------------------------------------
# LSTM with a recurrent projection and layer-normalised gates
# (models.rnn.lstmp_scan is the XLA oracle).
#
#   a = xp_t + r_{t-1} W_r                 [B, 4H]  (i, f, g, o)
#   pre_k = LN(a_k) * scale_k + bias_k     per gate, over its H units
#   c = sig(pre_f + 1) c + sig(pre_i) tanh(pre_g)
#   m = sig(pre_o) tanh(c);  r = m W_p     [B, P]
#
# One regime: W_r [P, 4H] and W_p [H, P] are whole-array VMEM blocks
# with a constant index map, single-buffered (``pl.Buffered(1)``: a
# block that never moves needs no second buffer), and the call raises
# Mosaic's scoped-VMEM limit to what its blocks and temporaries need
# (rnnt_he2019: 13.1 MB of bf16 weights, over the GRU/LSTM kernels'
# 10 MB residency budget under the default 16 MiB limit). A model whose
# weights do not fit :data:`LSTMP_VMEM_LIMIT` runs the XLA scan
# (``lstmp_fits_vmem``, asked by models/rnn.py), and so does the
# decoders' one-step path with its carried (c, r).
#
# Backward is BPTT with gate recompute: the forward tapes the cell
# state beside the outputs; the backward kernel recomputes the gates
# and the layer-norm statistics from (r_prev, c_prev, xp), returns the
# gradient of the pre-normalisation gates ``da`` (= the gradient of
# xproj), the recomputed cell outputs ``m`` and the masked output
# gradients ``dr`` per step, and accumulates the layer-norm gain and
# bias gradients in VMEM. The three weight-gradient contractions over
# all T*B rows run outside the time loop, as the GRU/LSTM kernels' do.
# ---------------------------------------------------------------------------

LSTMP_VMEM_LIMIT = 96 * 1024 * 1024     # of a v5e core's 128 MiB
_LN_EPS = 1e-5                          # models.rnn.LN_EPS


def _lstmp_vmem_bytes(b: int, h: int, p: int, dot_bytes: int,
                      backward: bool) -> int:
    """What a call holds in VMEM: the single-buffered weights, the
    double-buffered per-step blocks and the float32 [B, 4H]
    temporaries of the gate math (6 forward, 12 backward)."""
    weights = (p * 4 * h + h * p) * dot_bytes + 2 * 4 * h * 4
    row = b * 4 * h
    if backward:
        blocks = 2 * (2 * row * dot_bytes + (2 * b * h + 3 * b * p) * 4)
        return weights + blocks + 12 * row * 4
    blocks = 2 * (row * dot_bytes + (b * h + b * p) * 4)
    return weights + blocks + 6 * row * 4


def lstmp_fits_vmem(b: int, h: int, p: int, dot_bytes: int) -> bool:
    return _lstmp_vmem_bytes(b, h, p, dot_bytes, True) <= LSTMP_VMEM_LIMIT


def _lstmp_params(b, h, p, dot_bytes, backward):
    need = _lstmp_vmem_bytes(b, h, p, dot_bytes, backward)
    limit = min(LSTMP_VMEM_LIMIT, max(32 * 1024 * 1024, need * 5 // 4))
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                vmem_limit_bytes=limit)


def _lstmp_gates(a, scale, bias, h: int, layer_norm: bool):
    """(pre [B,4H], n [B,4H], rstd per gate) from the summed
    pre-normalisation gates; without layer norm pre = a."""
    if not layer_norm:
        return a, None, None
    ns, rstds = [], []
    for k in range(4):
        g = a[:, k * h:(k + 1) * h]
        mu = jnp.mean(g, axis=-1, keepdims=True)
        d = g - mu
        rstd = jax.lax.rsqrt(jnp.mean(d * d, axis=-1, keepdims=True)
                             + _LN_EPS)
        ns.append(d * rstd)
        rstds.append(rstd)
    n = jnp.concatenate(ns, axis=1)
    return n * scale + bias, n, rstds


def _lstmp_cell(pre, cprev, h: int):
    i = jax.nn.sigmoid(pre[:, :h])
    f = jax.nn.sigmoid(pre[:, h:2 * h] + 1.0)
    g = jnp.tanh(pre[:, 2 * h:3 * h])
    o = jax.nn.sigmoid(pre[:, 3 * h:])
    cnew = f * cprev + i * g
    tc = jnp.tanh(cnew)
    return i, f, g, o, cnew, tc


def _lstmp_kernel(xp_ref, mask_ref, wr_ref, wp_ref, sc_ref, bi_ref,
                  *refs, h: int, layer_norm: bool):
    # refs = (ys, cs, c_c, r_c) when taping the cell-state sequence
    # for BPTT, (ys, c_c, r_c) on the no-grad path.
    if len(refs) == 4:
        ys_ref, cs_ref, c_c, r_c = refs
    else:
        (ys_ref, c_c, r_c), cs_ref = refs, None
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        c_c[:] = jnp.zeros_like(c_c)
        r_c[:] = jnp.zeros_like(r_c)

    cprev, rprev = c_c[:], r_c[:]
    a = xp_ref[0].astype(jnp.float32) + jnp.dot(
        rprev.astype(wr_ref.dtype), wr_ref[:],
        preferred_element_type=jnp.float32)
    pre, _, _ = _lstmp_gates(a, sc_ref[:], bi_ref[:], h, layer_norm)
    _, _, _, o, cnew, tc = _lstmp_cell(pre, cprev, h)
    rnew = jnp.dot((o * tc).astype(wp_ref.dtype), wp_ref[:],
                   preferred_element_type=jnp.float32)
    m = mask_ref[0]
    cnew = m * cnew + (1.0 - m) * cprev
    rnew = m * rnew + (1.0 - m) * rprev
    c_c[:] = cnew
    r_c[:] = rnew
    ys_ref[0] = rnew
    if cs_ref is not None:
        cs_ref[0] = cnew


def _lstmp_bwd_kernel(xp_ref, mask_ref, rs_prev_ref, cs_prev_ref, dy_ref,
                      wr_ref, wp_ref, sc_ref, bi_ref,
                      da_ref, mo_ref, dr_ref, dsc_ref, dbi_ref,
                      dr_c, dc_c, *, h: int, layer_norm: bool):
    ti = pl.program_id(0)

    @pl.when(ti == 0)
    def _():
        dr_c[:] = jnp.zeros_like(dr_c)
        dc_c[:] = jnp.zeros_like(dc_c)
        dsc_ref[:] = jnp.zeros_like(dsc_ref)
        dbi_ref[:] = jnp.zeros_like(dbi_ref)

    first = ti == pl.num_programs(0) - 1     # scan step 0: zero carry
    rprev = jnp.where(first, jnp.zeros_like(rs_prev_ref[0]),
                      rs_prev_ref[0])
    cprev = jnp.where(first, jnp.zeros_like(cs_prev_ref[0]),
                      cs_prev_ref[0])
    a = xp_ref[0].astype(jnp.float32) + jnp.dot(
        rprev.astype(wr_ref.dtype), wr_ref[:],
        preferred_element_type=jnp.float32)
    scale = sc_ref[:]
    pre, n, rstds = _lstmp_gates(a, scale, bi_ref[:], h, layer_norm)
    i, f, g, o, _, tc = _lstmp_cell(pre, cprev, h)
    mo = o * tc

    m = mask_ref[0]
    dr_in = dr_c[:] + dy_ref[0]
    dr_new = m * dr_in
    dmo = jax.lax.dot_general(
        dr_new.astype(wp_ref.dtype), wp_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    dc_in = dc_c[:]
    dc_new = m * dc_in + dmo * o * (1.0 - tc * tc)
    dpre = jnp.concatenate([
        dc_new * g * i * (1.0 - i),
        dc_new * cprev * f * (1.0 - f),
        dc_new * i * (1.0 - g * g),
        dmo * tc * o * (1.0 - o)], axis=1)
    if layer_norm:
        dsc_ref[:] += jnp.sum(dpre * n, axis=0, keepdims=True)
        dbi_ref[:] += jnp.sum(dpre, axis=0, keepdims=True)
        dn = dpre * scale
        das = []
        for k in range(4):
            dk = dn[:, k * h:(k + 1) * h]
            nk = n[:, k * h:(k + 1) * h]
            das.append(rstds[k] * (
                dk - jnp.mean(dk, axis=-1, keepdims=True)
                - nk * jnp.mean(dk * nk, axis=-1, keepdims=True)))
        da = jnp.concatenate(das, axis=1)
    else:
        da = dpre
    da_q = da.astype(da_ref.dtype)
    da_ref[0] = da_q
    mo_ref[0] = mo.astype(mo_ref.dtype)
    dr_ref[0] = dr_new.astype(dr_ref.dtype)
    dr_c[:] = (1.0 - m) * dr_in + jax.lax.dot_general(
        da_q.astype(wr_ref.dtype), wr_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    dc_c[:] = dc_new * f + (1.0 - m) * dc_in


def _const_spec(shape):
    """A whole-array VMEM block that never moves: one buffer."""
    return pl.BlockSpec(shape, lambda t: (0,) * len(shape),
                        memory_space=pltpu.VMEM,
                        pipeline_mode=pl.Buffered(1))


def _lstmp_operands(w_r, w_p, ln_scale, ln_bias, dot):
    h4 = w_r.shape[1]
    layer_norm = ln_scale is not None
    sc = (ln_scale if layer_norm else jnp.ones((h4,))
          ).astype(jnp.float32).reshape(1, h4)
    bi = (ln_bias if layer_norm else jnp.zeros((h4,))
          ).astype(jnp.float32).reshape(1, h4)
    return w_r.astype(dot), w_p.astype(dot), sc, bi, layer_norm


def _lstmp_raw(xproj, mask, w_r, w_p, ln_scale, ln_bias, interpret,
               dot_dtype, want_cs: bool):
    b, t_max, h4 = xproj.shape
    h, p = h4 // 4, w_p.shape[1]
    dot = _dot_jnp_dtype(dot_dtype)
    xp_t, mask_t = _time_major(xproj, mask)
    wr, wp, sc, bi, layer_norm = _lstmp_operands(w_r, w_p, ln_scale,
                                                 ln_bias, dot)
    idx, midx = _time_index_maps(t_max, False, blocked=False)
    in_specs = [
        pl.BlockSpec((1, b, h4), idx, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, b, 1), midx, memory_space=pltpu.VMEM),
        _const_spec((p, h4)), _const_spec((h, p)),
        _const_spec((1, h4)), _const_spec((1, h4)),
    ]
    widths = (p, h) if want_cs else (p,)
    out = kernel_call(
        functools.partial(_lstmp_kernel, h=h, layer_norm=layer_norm),
        kernel="lstmp_scan_fwd",
        facts={**scan_facts("resident", False, t_max, b, h, 4), "p": p},
        grid=(t_max,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, b, w), idx, memory_space=pltpu.VMEM)
                   for w in widths],
        out_shape=[jax.ShapeDtypeStruct((t_max, b, w), jnp.float32)
                   for w in widths],
        scratch_shapes=[pltpu.VMEM((b, h), jnp.float32),
                        pltpu.VMEM((b, p), jnp.float32)],
        compiler_params=_lstmp_params(b, h, p, jnp.dtype(dot).itemsize,
                                      False),
        interpret=interpret,
    )(xp_t, mask_t, wr, wp, sc, bi)
    ys, cs = out if want_cs else (out[0], None)
    return ys, cs, xp_t, mask_t


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def lstmp_scan_pallas(xproj: jnp.ndarray, mask: jnp.ndarray,
                      w_r: jnp.ndarray, w_p: jnp.ndarray,
                      ln_scale: Optional[jnp.ndarray],
                      ln_bias: Optional[jnp.ndarray],
                      interpret: bool = False,
                      dot_dtype: Optional[str] = None) -> jnp.ndarray:
    """Fused LSTM-with-projection recurrence from a zero carry;
    contract matches models.rnn.lstmp_scan: r [B, T, P] float32."""
    ys, _, _, _ = _lstmp_raw(xproj, mask, w_r, w_p, ln_scale, ln_bias,
                             interpret, dot_dtype, want_cs=False)
    return jnp.moveaxis(ys, 0, 1)


def _lstmp_fwd(xproj, mask, w_r, w_p, ln_scale, ln_bias, interpret,
               dot_dtype):
    ys, cs, xp_t, mask_t = _lstmp_raw(
        xproj, mask, w_r, w_p, ln_scale, ln_bias, interpret, dot_dtype,
        want_cs=True)
    return jnp.moveaxis(ys, 0, 1), (xp_t, mask_t, w_r, w_p, ln_scale,
                                    ln_bias, ys, cs)


def _lstmp_bwd(interpret, dot_dtype, residuals, dy):
    xp_t, mask_t, w_r, w_p, ln_scale, ln_bias, ys, cs = residuals
    t_max, b, h = cs.shape
    p, h4 = w_p.shape[1], 4 * h
    dot = _dot_jnp_dtype(dot_dtype)
    wr, wp, sc, bi, layer_norm = _lstmp_operands(w_r, w_p, ln_scale,
                                                 ln_bias, dot)
    dy_t = jnp.moveaxis(dy.astype(jnp.float32), 1, 0)
    bidx = lambda i: (t_max - 1 - i, 0, 0)
    pidx = lambda i: (jnp.maximum(t_max - 2 - i, 0), 0, 0)
    step = lambda w, idx: pl.BlockSpec((1, b, w), idx,
                                       memory_space=pltpu.VMEM)
    acc = pl.BlockSpec((1, h4), lambda i: (0, 0), memory_space=pltpu.VMEM)
    da_t, mo_t, dr_t, dsc, dbi = kernel_call(
        functools.partial(_lstmp_bwd_kernel, h=h, layer_norm=layer_norm),
        kernel="lstmp_scan_bwd",
        facts={**scan_facts("resident", False, t_max, b, h, 4), "p": p},
        grid=(t_max,),
        in_specs=[step(h4, bidx), step(1, bidx), step(p, pidx),
                  step(h, pidx), step(p, bidx),
                  _const_spec((p, h4)), _const_spec((h, p)),
                  _const_spec((1, h4)), _const_spec((1, h4))],
        out_specs=[step(h4, bidx), step(h, bidx), step(p, bidx), acc, acc],
        out_shape=[jax.ShapeDtypeStruct((t_max, b, h4), dot),
                   jax.ShapeDtypeStruct((t_max, b, h), dot),
                   jax.ShapeDtypeStruct((t_max, b, p), dot),
                   jax.ShapeDtypeStruct((1, h4), jnp.float32),
                   jax.ShapeDtypeStruct((1, h4), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((b, p), jnp.float32),
                        pltpu.VMEM((b, h), jnp.float32)],
        compiler_params=_lstmp_params(b, h, p, jnp.dtype(dot).itemsize,
                                      True),
        interpret=interpret,
    )(xp_t, mask_t, ys, cs, dy_t, wr, wp, sc, bi)

    # The weight gradients over all T*B rows, outside the time loop;
    # operands in the dot type, float32 accumulation, as the oracle's
    # per-step contractions have them.
    r_prev = jnp.concatenate([jnp.zeros_like(ys[:1]), ys[:-1]], axis=0)
    dw_r = jnp.einsum("tbp,tbg->pg", r_prev.astype(dot), da_t,
                      preferred_element_type=jnp.float32)
    dw_p = jnp.einsum("tbh,tbp->hp", mo_t, dr_t,
                      preferred_element_type=jnp.float32)
    dxp = jnp.moveaxis(da_t, 0, 1).astype(xp_t.dtype)
    dmask = jnp.zeros_like(mask_t[..., 0]).swapaxes(0, 1)
    if not layer_norm:
        return (dxp, dmask, dw_r.astype(w_r.dtype), dw_p.astype(w_p.dtype),
                None, None)
    return (dxp, dmask, dw_r.astype(w_r.dtype), dw_p.astype(w_p.dtype),
            dsc.reshape(h4).astype(ln_scale.dtype),
            dbi.reshape(h4).astype(ln_bias.dtype))


lstmp_scan_pallas.defvjp(_lstmp_fwd, _lstmp_bwd)
