"""CTC loss in pure JAX: log-space forward/backward over `lax.scan`.

This is the framework's replacement for warp-ctc (SURVEY.md §2
component 9; recursion spec in §3.3). Two implementations live here:

- ``ctc_loss_ref``: alpha-only forward; gradients via autodiff through
  the scan. Slow but independently correct — the test oracle.
- ``ctc_loss``: custom_vjp with explicit alpha/beta recursions and the
  closed-form gradient  dL/dlogits = softmax(logits) - gamma,  where
  gamma[t,v] = sum_{s: ext[s]=v} P(s at t | labels) — the same math the
  Pallas kernel (ops/ctc_pallas.py) implements on-chip.

Conventions (matching optax.ctc_loss so it can cross-check us):
- blank id = 0
- inputs are *logits* [B, T, V]; log_softmax happens inside
- per-utterance negative log-likelihood is returned, shape [B]
- variable lengths via ``input_lens`` [B] (frames) and ``label_lens`` [B]

Extended label sequence: ext = [blank, l1, blank, l2, ..., lL, blank],
S = 2L+1. alpha[t,s] includes the emission at t; beta[t,s] excludes it,
so P = logsumexp_s(alpha[t,s] + beta[t,s]) at every valid t.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

NEG = -1e30  # effectively log(0) without -inf NaN hazards


def _extend_labels(labels: jnp.ndarray) -> jnp.ndarray:
    """[B, L] -> ext [B, 2L+1] with blanks interleaved (blank=0)."""
    b, l = labels.shape
    ext = jnp.zeros((b, 2 * l + 1), dtype=labels.dtype)
    return ext.at[:, 1::2].set(labels)


def _transition_masks(labels: jnp.ndarray, label_lens: jnp.ndarray
                      ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(ext, allowed_skip[s], valid_s[s]) for the banded recursion.

    allowed_skip[s]: the s-2 -> s transition is legal (ext[s] is a label
    and differs from ext[s-2], i.e. not a repeated character).
    valid_s[s]: s < 2*label_len+1 for this utterance.
    """
    ext = _extend_labels(labels)
    b, s_max = ext.shape
    s_idx = jnp.arange(s_max)
    prev2 = jnp.concatenate([jnp.zeros((b, 2), ext.dtype), ext[:, :-2]],
                            axis=1)
    allowed_skip = (ext != 0) & (ext != prev2) & (s_idx[None, :] >= 2)
    valid_s = s_idx[None, :] < (2 * label_lens[:, None] + 1)
    return ext, allowed_skip, valid_s


def _shift1(x, fill=NEG):
    return jnp.concatenate(
        [jnp.full_like(x[:, :1], fill), x[:, :-1]], axis=1)


def _shift2(x, fill=NEG):
    return jnp.concatenate(
        [jnp.full_like(x[:, :2], fill), x[:, :-2]], axis=1)


def _alpha_step(alpha, lp_ext_t, allowed_skip, valid_s):
    """One banded forward-recursion step (alpha already includes t-1)."""
    stay = alpha
    step1 = _shift1(alpha)
    step2 = jnp.where(allowed_skip, _shift2(alpha), NEG)
    new = lp_ext_t + jnp.logaddexp(stay, jnp.logaddexp(step1, step2))
    return jnp.where(valid_s, new, NEG)


def forward_alphas(log_probs: jnp.ndarray, labels: jnp.ndarray,
                   input_lens: jnp.ndarray, label_lens: jnp.ndarray
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """All alpha[t] and the final per-utterance log-likelihood.

    Returns (alphas [T, B, S], loglik [B]).
    """
    b, t_max, _ = log_probs.shape
    ext, allowed_skip, valid_s = _transition_masks(labels, label_lens)
    s_max = ext.shape[1]

    lp_t = jnp.moveaxis(log_probs, 1, 0)  # [T, B, V]

    def gather_ext(lp):  # [B, V] -> [B, S]
        return jnp.take_along_axis(lp, ext, axis=1)

    alpha0 = jnp.full((b, s_max), NEG)
    alpha0 = alpha0.at[:, 0].set(gather_ext(lp_t[0])[:, 0])
    alpha0 = alpha0.at[:, 1].set(
        jnp.where(label_lens > 0, gather_ext(lp_t[0])[:, 1], NEG))
    alpha0 = jnp.where(valid_s, alpha0, NEG)

    def step(alpha, xt):
        t, lp = xt
        new = _alpha_step(alpha, gather_ext(lp), allowed_skip, valid_s)
        # Frames at/after input_len carry alpha through unchanged.
        new = jnp.where((t < input_lens)[:, None], new, alpha)
        return new, new

    ts = jnp.arange(1, t_max)
    _, alphas_rest = jax.lax.scan(step, alpha0, (ts, lp_t[1:]))
    alphas = jnp.concatenate([alpha0[None], alphas_rest], axis=0)

    final = alphas[-1]
    s_last = 2 * label_lens  # index of final blank
    a_last = jnp.take_along_axis(final, s_last[:, None], axis=1)[:, 0]
    a_prev = jnp.where(
        label_lens > 0,
        jnp.take_along_axis(final, jnp.maximum(s_last - 1, 0)[:, None],
                            axis=1)[:, 0],
        NEG)
    loglik = jnp.logaddexp(a_last, a_prev)
    return alphas, loglik


def backward_betas(log_probs: jnp.ndarray, labels: jnp.ndarray,
                   input_lens: jnp.ndarray, label_lens: jnp.ndarray
                   ) -> jnp.ndarray:
    """beta[t, b, s], emission at t excluded (see module docstring)."""
    b, t_max, _ = log_probs.shape
    ext, allowed_skip, valid_s = _transition_masks(labels, label_lens)
    s_max = ext.shape[1]
    s_idx = jnp.arange(s_max)[None, :]

    lp_t = jnp.moveaxis(log_probs, 1, 0)

    def gather_ext(lp):
        return jnp.take_along_axis(lp, ext, axis=1)

    s_last = 2 * label_lens
    terminal = jnp.where(
        (s_idx == s_last[:, None]) |
        ((s_idx == (s_last - 1)[:, None]) & (label_lens > 0)[:, None]),
        0.0, NEG)

    def shift_m1(x, fill=NEG):  # x[s+1]
        return jnp.concatenate(
            [x[:, 1:], jnp.full_like(x[:, :1], fill)], axis=1)

    def shift_m2(x, fill=NEG):
        return jnp.concatenate(
            [x[:, 2:], jnp.full_like(x[:, :2], fill)], axis=1)

    # allowed_skip describes s-2 -> s; from s the skip goes to s+2, which
    # is legal iff allowed_skip[s+2].
    allowed_fwd = shift_m2(allowed_skip.astype(jnp.float32), 0.0) > 0.5

    def step(carry, xt):
        t, lp_next = xt  # lp at t+1
        g = gather_ext(lp_next)
        stay = carry + g
        step1 = shift_m1(carry + g)
        step2 = jnp.where(allowed_fwd, shift_m2(carry + g), NEG)
        rec = jnp.logaddexp(stay, jnp.logaddexp(step1, step2))
        rec = jnp.where(valid_s, rec, NEG)
        # t == input_len-1 restarts at the terminal condition; padded
        # frames (t >= input_len) hold the terminal values.
        new = jnp.where((t >= input_lens - 1)[:, None], terminal, rec)
        return new, new

    ts = jnp.arange(t_max - 1, -1, -1)
    # At step t we look at lp[t+1]; pad one NEG frame past the end.
    lp_pad = jnp.concatenate(
        [lp_t, jnp.full_like(lp_t[:1], NEG)], axis=0)
    _, betas_rev = jax.lax.scan(step, terminal, (ts, lp_pad[ts + 1]))
    return betas_rev[::-1]  # [T, B, S]


def scatter_ext_to_vocab(vals: jnp.ndarray, ext: jnp.ndarray,
                         vocab: int) -> jnp.ndarray:
    """Sum extended-label values into vocab bins.

    vals [B, T, S], ext [B, S] -> [B, T, V] (f32):
    ``out[b, t, v] = sum of vals[b, t, s] over the s with ext[b, s] == v``.
    One batched contraction with the one-hot of ``ext`` (a TPU runs a
    scatter-add one update after another). ``HIGHEST`` keeps it exact
    in f32: the one-hot is exact in bf16, so only the order of the f32
    additions differs from a scatter-add. Shared by the alpha/beta
    gradient here and the Pallas kernel wrapper (ops/ctc_pallas.py).
    """
    one_hot = (ext[:, :, None] == jnp.arange(vocab)).astype(jnp.float32)
    return jnp.einsum("bts,bsv->btv", vals, one_hot,
                      precision=jax.lax.Precision.HIGHEST)


# Back-compat re-export: the interpreter-mode default historically
# lived here; the shared helpers now sit in utils.impl.
from ..utils.impl import interpret_default  # noqa: F401


def ctc_loss_ref(logits: jnp.ndarray, labels: jnp.ndarray,
                 input_lens: jnp.ndarray, label_lens: jnp.ndarray
                 ) -> jnp.ndarray:
    """Reference CTC loss; gradient flows by autodiff through the scan."""
    log_probs = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    _, loglik = forward_alphas(log_probs, labels, input_lens, label_lens)
    return -loglik


@functools.partial(jax.custom_vjp, nondiff_argnums=())
def ctc_loss(logits, labels, input_lens, label_lens):
    return ctc_loss_ref(logits, labels, input_lens, label_lens)


def _ctc_fwd(logits, labels, input_lens, label_lens):
    loss = ctc_loss_ref(logits, labels, input_lens, label_lens)
    return loss, (logits, labels, input_lens, label_lens)


def ctc_grad(logits: jnp.ndarray, labels: jnp.ndarray,
             input_lens: jnp.ndarray, label_lens: jnp.ndarray
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(loss [B], dloss/dlogits [B, T, V]) via explicit alpha/beta."""
    b, t_max, v = logits.shape
    logits32 = logits.astype(jnp.float32)
    log_probs = jax.nn.log_softmax(logits32, axis=-1)
    alphas, loglik = forward_alphas(log_probs, labels, input_lens, label_lens)
    betas = backward_betas(log_probs, labels, input_lens, label_lens)
    ext, _, _ = _transition_masks(labels, label_lens)

    # occupancy[t,b,s] = P(path passes s at t | labels), in log space.
    log_occ = alphas + betas - loglik[None, :, None]

    # gamma[b,t,v] = occupancy summed into vocab bins by ext[s].
    occ = jnp.exp(jnp.minimum(log_occ, 0.0))  # clip tiny numeric overshoot
    occ = jnp.moveaxis(occ, 1, 0)  # [B, T, S]
    gamma = scatter_ext_to_vocab(occ, ext, v)  # [B, T, V]
    probs = jnp.exp(log_probs)
    grad = probs - gamma
    tmask = (jnp.arange(t_max)[None, :] < input_lens[:, None])
    grad = grad * tmask[:, :, None]
    return -loglik, grad.astype(logits.dtype)


def _ctc_bwd(residuals, g):
    logits, labels, input_lens, label_lens = residuals
    _, grad = ctc_grad(logits, labels, input_lens, label_lens)
    return (grad * g[:, None, None], None, None, None)


ctc_loss.defvjp(_ctc_fwd, _ctc_bwd)


def ctc_loss_mean(logits, labels, input_lens, label_lens):
    """Batch-mean CTC loss (what the train step optimizes)."""
    per_utt = ctc_loss(logits, labels, input_lens, label_lens)
    return jnp.mean(per_utt)
