"""Fused Pallas LSTM cells (SURVEY.md §2 component 6, LSTM variants).

The plain LSTM is a gated cell of ``ops/scan_pallas.py`` like the GRU
(``ops/rnn_pallas.py``), four gates and two carried states: this file
holds its element-wise math and its public functions, the route and
the call are shared. Two regimes, by the route: VMEM-resident
``[H, 4H]`` weights for small/medium H, streamed column blocks
(``blocked``) above that, and the int8 pair ``resident_q`` /
``blocked_q``. The recurrence matches ``models.rnn.lstm_scan`` (the XLA
oracle), including the +1.0 forget-gate bias trick and mask-held h/c
for padded frames.

Backward is BPTT with gate recompute: the forward tapes the cell-state
sequence ``cs`` alongside the outputs ``ys`` (cuDNN does the same; the
no-grad primal skips that [T, B, H] HBM write), and the backward step
recomputes the four gate activations from (h_prev, c_prev, xproj, W)
instead of storing them.

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

from .scan_pallas import (ScanCell, dot_jnp_dtype, own_route, prev_sequence,
                          scan_call, scan_forward, scan_pair_vjp, scan_vjp,
                          time_index_maps, time_major)


def _lstm_elementwise_fwd(xp, gates, states, m):
    hprev, cprev = states
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


def _lstm_elementwise_bwd(xp, gates, prevs, m, dstates, dy):
    """VPU math of one reverse step. Returns (dxp, dgates, (dh_prev,
    dc_prev)): the gradient of xproj IS that of the gates, and dh_prev
    still lacks the dgates @ W^T term (the step body's)."""
    (hprev, cprev), (dh_in, dc_in) = prevs, dstates
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
    return dgates, dgates, (dh_prev_local, dc_prev)


LSTM = ScanCell("lstm", _lstm_elementwise_fwd, _lstm_elementwise_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def lstm_scan_pallas(xproj: jnp.ndarray, mask: jnp.ndarray,
                     w_h: jnp.ndarray, b_h: jnp.ndarray,
                     reverse: bool = False,
                     interpret: bool = False,
                     dot_dtype: Optional[str] = None) -> jnp.ndarray:
    """Fused LSTM recurrence; contract matches models.rnn.lstm_scan."""
    (ys,), _, _ = scan_forward(LSTM, xproj, mask, w_h, b_h, reverse=reverse,
                               interpret=interpret, dot_dtype=dot_dtype)
    return jnp.moveaxis(ys, 0, 1)


lstm_scan_pallas.defvjp(*scan_vjp(LSTM))


# Both directions of a bidirectional LSTM layer, summed [B, T, H], as
# rnn_pallas.gru_scan_pair_pallas.
lstm_scan_pair_pallas = scan_pair_vjp(LSTM)


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
    (ys,), _, _ = scan_forward(LSTM, xproj, mask, w_q, b_h, scale=w_scale,
                               reverse=reverse, interpret=interpret,
                               dot_dtype=dot_dtype, blocked=blocked)
    return jnp.moveaxis(ys, 0, 1)


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
# weights do not fit ``scan_pallas.LSTMP_VMEM_LIMIT`` runs the XLA scan
# (``scan_route``, asked by models/rnn.py), and so does the decoders'
# one-step path with its carried (c, r). The step is two matmuls around
# layer-normalised gates, so the step bodies are this file's; the specs
# and the call are scan_pallas's.
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

_LN_EPS = 1e-5                          # models.rnn.LN_EPS


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


def _lstmp_operands(w_r, w_p, ln_scale, ln_bias, dot):
    h4 = w_r.shape[1]
    layer_norm = ln_scale is not None
    sc = (ln_scale if layer_norm else jnp.ones((h4,))
          ).astype(jnp.float32).reshape(1, h4)
    bi = (ln_bias if layer_norm else jnp.zeros((h4,))
          ).astype(jnp.float32).reshape(1, h4)
    return w_r.astype(dot), w_p.astype(dot), sc, bi, layer_norm


def _lstmp_call(body, backward, rows, operands, outs, whole_outs,
                scratch, interpret):
    """Either lstmp call: the per-step ``rows``, then W_r, W_p, the
    layer-norm gain and bias, whole and single-buffered."""
    wr, wp, sc, bi, layer_norm = operands
    b, (h, p) = rows[0][0].shape[1], wp.shape
    route = own_route(
        "bwd" if backward else "fwd", "lstmp", rows=b, hidden=h, proj=p, dot_bytes=wr.dtype.itemsize,
        backward=backward)
    return scan_call(
        functools.partial(body, h=h, layer_norm=layer_norm), route,
        reverse=False, hidden=h, gates=4, more_facts={"p": p}, rows=rows,
        weights=[wr, wp, sc, bi], outs=outs, whole_outs=whole_outs,
        scratch=lambda cols: scratch, interpret=interpret,
        buffer_weights_once=True, dimension_semantics=("arbitrary",))


def _lstmp_raw(xproj, mask, w_r, w_p, ln_scale, ln_bias, interpret,
               dot_dtype, want_cs: bool):
    """want_cs=False (no-grad primal) skips the [T,B,H] cell-state tape
    write; the BPTT backward needs it, eval/infer forward does not."""
    h, p = w_p.shape
    xp_t, mask_t = time_major(xproj, mask)
    at, _, _ = time_index_maps(xp_t.shape[0], False)
    out = _lstmp_call(
        _lstmp_kernel, False, [(xp_t, at), (mask_t, at)],
        _lstmp_operands(w_r, w_p, ln_scale, ln_bias,
                        dot_jnp_dtype(dot_dtype)),
        [(w, jnp.float32, at) for w in ((p, h) if want_cs else (p,))],
        [], [h, p], interpret)
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


@jax.named_scope("rnn_scan")
def _lstmp_fwd(xproj, mask, w_r, w_p, ln_scale, ln_bias, interpret,
               dot_dtype):
    ys, cs, xp_t, mask_t = _lstmp_raw(
        xproj, mask, w_r, w_p, ln_scale, ln_bias, interpret, dot_dtype,
        want_cs=True)
    return jnp.moveaxis(ys, 0, 1), (xp_t, mask_t, w_r, w_p, ln_scale,
                                    ln_bias, ys, cs)


@jax.named_scope("rnn_scan")
def _lstmp_bwd(interpret, dot_dtype, residuals, dy):
    xp_t, mask_t, w_r, w_p, ln_scale, ln_bias, ys, cs = residuals
    t_max, _, h = cs.shape
    p, h4 = w_p.shape[1], 4 * h
    dot = dot_jnp_dtype(dot_dtype)
    operands = _lstmp_operands(w_r, w_p, ln_scale, ln_bias, dot)
    dy_t = jnp.moveaxis(dy.astype(jnp.float32), 1, 0)
    _, at, at_prev = time_index_maps(t_max, False)
    da_t, mo_t, dr_t, dsc, dbi = _lstmp_call(
        _lstmp_bwd_kernel, True,
        [(xp_t, at), (mask_t, at), (ys, at_prev), (cs, at_prev),
         (dy_t, at)],
        operands, [(h4, dot, at), (h, dot, at), (p, dot, at)],
        [(1, h4)] * 2, [p, h], interpret)

    # The weight gradients over all T*B rows, outside the time loop;
    # operands in the dot type, float32 accumulation, as the oracle's
    # per-step contractions have them.
    with jax.named_scope("dw_h"):
        r_prev = prev_sequence(ys, False)
        dw_r = jnp.einsum("tbp,tbg->pg", r_prev.astype(dot), da_t,
                          preferred_element_type=jnp.float32)
        dw_p = jnp.einsum("tbh,tbp->hp", mo_t, dr_t,
                          preferred_element_type=jnp.float32)
    dxp = jnp.moveaxis(da_t, 0, 1).astype(xp_t.dtype)
    dmask = jnp.zeros_like(mask_t[..., 0]).swapaxes(0, 1)
    if not operands[-1]:
        return (dxp, dmask, dw_r.astype(w_r.dtype), dw_p.astype(w_p.dtype),
                None, None)
    return (dxp, dmask, dw_r.astype(w_r.dtype), dw_p.astype(w_p.dtype),
            dsc.reshape(h4).astype(ln_scale.dtype),
            dbi.reshape(h4).astype(ln_bias.dtype))


lstmp_scan_pallas.defvjp(_lstmp_fwd, _lstmp_bwd)
