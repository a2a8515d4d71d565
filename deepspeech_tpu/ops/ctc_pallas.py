"""CTC forward/backward as Pallas TPU kernels.

The TPU-native replacement for warp-ctc (SURVEY.md §2 component 9,
recursion spec §3.3). Same math as the jnp oracle in ``ops/ctc.py``
(which remains the bit-oracle in tests); the kernels fuse the whole
time recursion so each step is one VPU pass over a resident
``[B, S]`` band instead of a dispatched XLA op.

Layout (time-major, batched bands):
- jnp wrapper: log_softmax + gather of the extended-label emissions
  ``lp_ext[T, B, S]`` (XLA fuses these), pad S to a lane multiple and
  B to a sublane multiple.
- forward kernel: sequential grid over T; carries ``alpha[B, S]`` in
  VMEM scratch across grid steps, streams each step's alpha row out to
  HBM, and latches the per-utterance log-likelihood at t = len-1.
- backward kernel: reversed sequential grid over T; carries
  ``beta[B, S]``, reads the stored alphas, and emits the occupancy
  ``gamma_ext[T, B, S] = exp(alpha + beta - loglik)``.
- jnp wrapper: sums gamma_ext into vocab bins (one-hot contraction) and forms
  ``dlogits = softmax - gamma`` (the closed-form CTC gradient).

Banded transitions (stay / step / skip) are lane-shifts: ``pltpu.roll``
along S with iota masks for the rolled-in lanes.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ctc import NEG, _transition_masks, scatter_ext_to_vocab
from .kernel_id import kernel_call

_LANE = 128
_SUBLANE = 8


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _logaddexp(a, b):
    m = jnp.maximum(a, b)
    # Guard the all-NEG case: exp(NEG - NEG) would be exp(0)=1 twice.
    return jnp.where(
        m <= NEG / 2, NEG,
        m + jnp.log(jnp.exp(a - m) + jnp.exp(b - m)))


def _shift_down(x, k, fill=NEG):
    """x[..., s] -> x[..., s-k] along lanes (band 'from the left')."""
    s = x.shape[-1]
    rolled = pltpu.roll(x, k, axis=len(x.shape) - 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, len(x.shape) - 1)
    return jnp.where(lane < k, fill, rolled)


def _shift_up(x, k, fill=NEG):
    """x[..., s] -> x[..., s+k] along lanes (circular roll by S-k)."""
    s = x.shape[-1]
    rolled = pltpu.roll(x, s - k, axis=len(x.shape) - 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, len(x.shape) - 1)
    return jnp.where(lane >= s - k, fill, rolled)


def _fwd_body(lp_ext_ref, skip_ref, valid_ref, lens_ref, slast_ref,
              ll_ref, alpha_c, alpha_out_ref):
    t = pl.program_id(0)
    lp_t = lp_ext_ref[0]          # [B, S]
    skip = skip_ref[:]            # [B, S] f32 (1 = s-2 transition legal)
    valid = valid_ref[:]          # [B, S] f32 (1 = s < 2L+1)
    lens = lens_ref[:]            # [B, 1] i32
    slast = slast_ref[:]          # [B, 1] i32
    b, s = lp_t.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (b, s), 1)

    @pl.when(t == 0)
    def _():
        # alpha_0: only s=0 (blank) and s=1 (first label, if L>0).
        init = jnp.where(
            (lane == 0) | ((lane == 1) & (slast > 0)), lp_t, NEG)
        alpha_c[:] = jnp.where(valid > 0.5, init, NEG)

    @pl.when(t > 0)
    def _():
        alpha = alpha_c[:]
        stay = alpha
        step1 = _shift_down(alpha, 1)
        step2 = jnp.where(skip > 0.5, _shift_down(alpha, 2), NEG)
        new = lp_t + _logaddexp(stay, _logaddexp(step1, step2))
        new = jnp.where(valid > 0.5, new, NEG)
        # Frames at/after this utterance's length carry alpha unchanged.
        alpha_c[:] = jnp.where(t < lens, new, alpha)

    if alpha_out_ref is not None:
        alpha_out_ref[0] = alpha_c[:]

    # Latch loglik at each utterance's final frame.
    alpha = alpha_c[:]
    final_mask = (lane == slast) | ((lane == slast - 1) & (slast > 0))
    masked = jnp.where(final_mask, alpha, NEG)
    m = jnp.max(masked, axis=1, keepdims=True)
    ll = m + jnp.log(jnp.sum(jnp.exp(masked - m), axis=1, keepdims=True))

    @pl.when(t == 0)
    def _():
        ll_ref[:] = ll

    @pl.when(t > 0)
    def _():
        ll_ref[:] = jnp.where(t == lens - 1, ll, ll_ref[:])


def _fwd_kernel(lp_ext_ref, skip_ref, valid_ref, lens_ref, slast_ref,
                alpha_out_ref, ll_ref, alpha_c):
    _fwd_body(lp_ext_ref, skip_ref, valid_ref, lens_ref, slast_ref,
              ll_ref, alpha_c, alpha_out_ref)


def _fwd_kernel_loss_only(lp_ext_ref, skip_ref, valid_ref, lens_ref,
                          slast_ref, ll_ref, alpha_c):
    """Loss without the alpha tape: eval/infer never pays the [T,B,S]
    HBM write or the beta pass (VERDICT r1 'weak' item)."""
    _fwd_body(lp_ext_ref, skip_ref, valid_ref, lens_ref, slast_ref,
              ll_ref, alpha_c, None)


def _bwd_kernel(lp_next_ref, skip_ref, valid_ref, lens_ref, slast_ref,
                alpha_ref, ll_ref, gamma_ref, beta_c):
    ti = pl.program_id(0)          # 0..T-1, processing t = T-1-ti
    n_t = pl.num_programs(0)
    t = n_t - 1 - ti
    skip = skip_ref[:]
    valid = valid_ref[:]
    lens = lens_ref[:]
    slast = slast_ref[:]
    b, s = skip.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (b, s), 1)

    terminal = jnp.where(
        (lane == slast) | ((lane == slast - 1) & (slast > 0)), 0.0, NEG)

    @pl.when(ti == 0)
    def _():
        beta_c[:] = terminal

    @pl.when(ti > 0)
    def _():
        beta = beta_c[:]
        g = lp_next_ref[0]         # lp_ext at t+1
        contrib = beta + g
        stay = contrib
        step1 = _shift_up(contrib, 1)
        # Skip legality is defined at the *destination* s+2.
        step2 = _shift_up(jnp.where(skip > 0.5, contrib, NEG), 2)
        rec = _logaddexp(stay, _logaddexp(step1, step2))
        rec = jnp.where(valid > 0.5, rec, NEG)
        # t == len-1 restarts at terminal; padded frames stay terminal.
        beta_c[:] = jnp.where(t >= lens - 1, terminal, rec)

    occ = alpha_ref[0] + beta_c[:] - ll_ref[:]
    gamma = jnp.exp(jnp.minimum(occ, 0.0))
    gamma = jnp.where((t < lens) & (valid > 0.5), gamma, 0.0)
    gamma_ref[0] = gamma


def _pallas_ctc_fwd_bwd(lp_ext, skip, valid, input_lens, s_last,
                        interpret: bool):
    """lp_ext [T, B, S] (padded) -> (loglik [B, 1], gamma_ext [T, B, S])."""
    t_max, b, s = lp_ext.shape
    lens2 = input_lens.reshape(b, 1).astype(jnp.int32)
    slast2 = s_last.reshape(b, 1).astype(jnp.int32)

    row = pl.BlockSpec((1, b, s), lambda t: (t, 0, 0),
                       memory_space=pltpu.VMEM)
    full = pl.BlockSpec((b, s), lambda t: (0, 0), memory_space=pltpu.VMEM)
    col = pl.BlockSpec((b, 1), lambda t: (0, 0), memory_space=pltpu.VMEM)

    facts = {"t": t_max, "b": b, "s": s}
    alphas, ll = kernel_call(
        _fwd_kernel, kernel="ctc_alpha", facts=facts,
        grid=(t_max,),
        in_specs=[row, full, full, col, col],
        out_specs=[row, col],
        out_shape=[
            jax.ShapeDtypeStruct((t_max, b, s), jnp.float32),
            jax.ShapeDtypeStruct((b, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((b, s), jnp.float32)],
        interpret=interpret,
    )(lp_ext, skip, valid, lens2, slast2)

    rev = pl.BlockSpec((1, b, s), lambda ti: (t_max - 1 - ti, 0, 0),
                       memory_space=pltpu.VMEM)
    # lp_ext at t+1 = T-1-ti+1; clamp at T-1 (unused when ti == 0).
    rev_next = pl.BlockSpec(
        (1, b, s), lambda ti: (jnp.minimum(t_max - ti, t_max - 1), 0, 0),
        memory_space=pltpu.VMEM)

    gamma = kernel_call(
        _bwd_kernel, kernel="ctc_gamma", facts=facts,
        grid=(t_max,),
        in_specs=[rev_next, full, full, col, col, rev, col],
        out_specs=rev,
        out_shape=jax.ShapeDtypeStruct((t_max, b, s), jnp.float32),
        scratch_shapes=[pltpu.VMEM((b, s), jnp.float32)],
        interpret=interpret,
    )(lp_ext, skip, valid, lens2, slast2, alphas, ll)

    return ll, gamma


def _prepare(logits, labels, input_lens, label_lens):
    b, t_max, v = logits.shape
    log_probs = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ext, allowed_skip, valid_s = _transition_masks(labels, label_lens)
    s = ext.shape[1]
    s_pad = _round_up(max(s, _LANE), _LANE)
    b_pad = _round_up(max(b, _SUBLANE), _SUBLANE)

    lp_ext = jnp.take_along_axis(log_probs, ext[:, None, :],
                                 axis=2)  # [B, T, S] (index broadcasts)
    lp_ext = jnp.moveaxis(lp_ext, 0, 1)  # [T, B, S]
    lp_ext = jnp.pad(lp_ext, ((0, 0), (0, b_pad - b), (0, s_pad - s)),
                     constant_values=NEG)
    skip = jnp.pad(allowed_skip.astype(jnp.float32),
                   ((0, b_pad - b), (0, s_pad - s)))
    valid = jnp.pad(valid_s.astype(jnp.float32),
                    ((0, b_pad - b), (0, s_pad - s)))
    # Padded batch rows: len 1 so the recursion stays trivially defined.
    lens_p = jnp.pad(input_lens.astype(jnp.int32), (0, b_pad - b),
                     constant_values=1)
    slast_p = jnp.pad((2 * label_lens).astype(jnp.int32), (0, b_pad - b))
    return log_probs, ext, lp_ext, skip, valid, lens_p, slast_p, s, b_pad, s_pad


def _scatter_gamma(gamma_ext, ext, b, t_max, v):
    """gamma_ext [T, B, S] + ext [B, S] -> gamma [B, T, V] per vocab bin."""
    return scatter_ext_to_vocab(jnp.moveaxis(gamma_ext, 1, 0), ext, v)


def _pallas_ctc_loss_only(lp_ext, skip, valid, input_lens, s_last,
                          interpret: bool):
    """Alpha recursion only -> loglik [B, 1]; no tape, no beta pass."""
    t_max, b, s = lp_ext.shape
    lens2 = input_lens.reshape(b, 1).astype(jnp.int32)
    slast2 = s_last.reshape(b, 1).astype(jnp.int32)
    row = pl.BlockSpec((1, b, s), lambda t: (t, 0, 0),
                       memory_space=pltpu.VMEM)
    full = pl.BlockSpec((b, s), lambda t: (0, 0), memory_space=pltpu.VMEM)
    col = pl.BlockSpec((b, 1), lambda t: (0, 0), memory_space=pltpu.VMEM)
    return kernel_call(
        _fwd_kernel_loss_only, kernel="ctc_alpha_loss",
        facts={"t": t_max, "b": b, "s": s},
        grid=(t_max,),
        in_specs=[row, full, full, col, col],
        out_specs=col,
        out_shape=jax.ShapeDtypeStruct((b, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((b, s), jnp.float32)],
        interpret=interpret,
    )(lp_ext, skip, valid, lens2, slast2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def ctc_loss_pallas(logits, labels, input_lens, label_lens,
                    interpret: bool = False):
    """Per-utterance CTC loss [B] with a Pallas fwd/bwd. blank=0.

    Same contract as ``ops.ctc.ctc_loss``. ``interpret=True`` runs the
    kernels in the Pallas interpreter (CPU CI; SURVEY.md §5 'sanitizer').
    The primal path (no grad requested — eval/infer) runs the alpha
    kernel only; the vjp fwd additionally tapes alphas and runs the
    beta kernel to form the closed-form gradient.
    """
    b = logits.shape[0]
    (_, _, lp_ext, skip, valid, lens_p, slast_p, _, _, _) = _prepare(
        logits, labels, input_lens, label_lens)
    ll = _pallas_ctc_loss_only(lp_ext, skip, valid, lens_p, slast_p,
                               interpret)
    return -ll[:b, 0]


def _ctc_pallas_fwd(logits, labels, input_lens, label_lens, interpret):
    b, t_max, v = logits.shape
    (log_probs, ext, lp_ext, skip, valid, lens_p, slast_p, s, b_pad,
     s_pad) = _prepare(logits, labels, input_lens, label_lens)
    ll, gamma_ext = _pallas_ctc_fwd_bwd(lp_ext, skip, valid, lens_p,
                                        slast_p, interpret)
    loss = -ll[:b, 0]
    gamma_ext = gamma_ext[:, :b, :s]
    gamma = _scatter_gamma(gamma_ext, ext, b, t_max, v)
    tmask = (jnp.arange(t_max)[None, :] < input_lens[:, None])
    dlogits = (jnp.exp(log_probs) * tmask[:, :, None] - gamma
               ).astype(logits.dtype)
    return loss, dlogits


def _ctc_pallas_bwd(interpret, residuals, g):
    dlogits = residuals
    return (dlogits * g[:, None, None], None, None, None)


def _ctc_pallas_fwd_vjp(logits, labels, input_lens, label_lens, interpret):
    loss, dlogits = _ctc_pallas_fwd(logits, labels, input_lens, label_lens,
                                    interpret)
    return loss, dlogits


ctc_loss_pallas.defvjp(_ctc_pallas_fwd_vjp, _ctc_pallas_bwd)
