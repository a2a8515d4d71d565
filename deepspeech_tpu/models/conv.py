"""2D convolutional frontend over spectrograms (SURVEY.md §2 component 5).

XLA's ``lax.conv_general_dilated`` on the MXU, with the output
FREQUENCIES folded into the channel dimension where the layer is narrow.
A TPU lays activations out channels-minor on 128 lanes, so DS2's 32
channels left three quarters of every vector register, MXU column and
activation byte as padding: conv1 (11 x 21, 32 -> 32) ran at 8.6% of
the MXU's peak and the frontend took 119.9 ms of ``ds2_full``'s 672 ms
train step. :func:`freq_folded_conv` computes the same sums as a
convolution over groups of ``g = 128 // C_out`` output frequencies with
``g * C_out`` output channels, and the frontend takes 22.9 ms of a
572 ms step (PERF.md, PR 34); layers with 128 or more channels get the
plain call.

Explicit padding keeps the length math simple:
out_len = ceil(in_len / time_stride).
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..config import ModelConfig
from .layers import MaskedBatchNorm, clipped_relu, length_mask

LANES = 128  # a TPU vector register's minor dimension
_NHWC = ("NHWC", "HWIO", "NHWC")


def fold_factor(c_out: int) -> int:
    """Output frequencies folded into the channels: as many as fill 128
    lanes exactly, 1 (the plain convolution) for 128 channels or more
    and for widths that do not divide 128."""
    return LANES // c_out if c_out < LANES and LANES % c_out == 0 else 1


def _fold_table(kf: int, kd: int, sf: int, g: int, dtype) -> np.ndarray:
    """0/1 table ``[kf, kd, sf*g, g]``: frequency tap ``k`` of the
    kernel as written is tap ``d``, input sub-position ``s`` of the
    folded kernel for output sub-position ``r`` where
    ``k = sf*g*d + s - sf*r``."""
    d, s, r = np.ogrid[:kd, :sf * g, :g]
    k = sf * g * d + s - sf * r
    return (np.arange(kf)[:, None, None, None] == k[None]).astype(dtype)


def freq_folded_conv(x: jnp.ndarray, kernel: jnp.ndarray,
                     strides: Tuple[int, int],
                     padding: Sequence[Tuple[int, int]],
                     layer: str = "") -> jnp.ndarray:
    """``lax.conv_general_dilated(x, kernel, strides, padding,
    dimension_numbers=("NHWC", "HWIO", "NHWC"))`` with ``g =
    fold_factor(C_out)`` output frequencies folded into the channels.

    x ``[B, T, F, C_in]``, kernel ``[kt, kf, C_in, C_out]`` in the
    compute dtype, strides ``(st, sf)``, explicit padding. Output
    frequency ``f = g*q + r`` reads padded input frequencies
    ``sf*f + k = sf*g*(q + d) + s``, so the same products are summed by
    a stride-1 convolution of ``kd`` taps over the input reshaped
    ``[B, T, F_pad/(sf*g), sf*g*C_in]`` with the kernel placed at
    ``[a, d, s*C_in + ci, r*C_out + co]`` and zero elsewhere. The
    placement is a contraction with a constant 0/1 table, so its
    transpose is one too (a TPU runs a scatter-add one update after
    another). The time axis is not touched. The ``g*ceil(F_out/g) -
    F_out`` surplus positions at the top, frequencies that do not
    exist, are sliced off here.

    While jax traces, ``layer`` (if given) is recorded as the gauge
    ``conv_fold{layer, g, taps, surplus}``.
    """
    (st, sf), (pad_t, (pf_lo, pf_hi)) = strides, padding
    kt, kf, c_in, c_out = kernel.shape
    fdim = x.shape[2]
    f_out = (fdim + pf_lo + pf_hi - kf) // sf + 1
    g = fold_factor(c_out)
    groups = -(-f_out // g)
    kd = (sf * (g - 1) + kf - 1) // (sf * g) + 1 if g > 1 else kf
    if layer:
        obs.registry().gauge("conv_fold", 1, labels={
            "layer": layer, "g": g, "taps": kd,
            "surplus": g * groups - f_out})
    if g == 1:
        return jax.lax.conv_general_dilated(
            x, kernel, strides, padding, dimension_numbers=_NHWC)
    # Every folded entry is one kernel entry times 1 plus zeros: exact
    # in the compute dtype (HIGHEST keeps float32 weights whole).
    folded = jnp.einsum(
        "akio,kdsr->adsiro", kernel,
        _fold_table(kf, kd, sf, g, kernel.dtype),
        precision=jax.lax.Precision.HIGHEST).reshape(
            kt, kd, sf * g * c_in, g * c_out)
    # Left padding as given; right padding out to whole groups, which
    # covers every column the last real frequency reads (lax.pad crops
    # where the stride leaves given columns unread).
    cols = groups + kd - 1
    zero = jnp.zeros((), x.dtype)
    x = jax.lax.pad(x, zero, (
        (0, 0, 0), (0, 0, 0),
        (pf_lo, sf * g * cols - fdim - pf_lo, 0), (0, 0, 0)))
    x = x.reshape(x.shape[:2] + (cols, sf * g * c_in))
    y = jax.lax.conv_general_dilated(
        x, folded, (st, 1), (pad_t, (0, 0)), dimension_numbers=_NHWC)
    return y.reshape(y.shape[:2] + (g * groups, c_out))[:, :, :f_out]


def _flax_conv(x, kernel, strides, padding, *, layer, **plain):
    """``nn.Conv``'s hook: it owns the parameter (name, shape, dtype,
    initialisation) and hands over the operands already cast. ``plain``
    are its defaults (no dilation, one feature group, no precision)."""
    return freq_folded_conv(x, kernel, strides, padding, layer)


def freq_padding(fdim: int, kf: int, sf: int) -> Tuple[int, int]:
    """The frequency padding SAME would choose (F is static)."""
    total = (-(-fdim // sf) - 1) * sf + kf - fdim
    return total // 2, total - total // 2


def conv_out_lens(feat_lens: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    lens = feat_lens
    for (_, _, ts, _) in cfg.conv_layers:
        lens = -(-lens // ts)  # ceil div, SAME padding
    return lens


class ConvFrontend(nn.Module):
    """features [B, T, F] -> [B, T', C*F'] plus new lengths."""

    cfg: ModelConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray, feat_lens: jnp.ndarray,
                 train: bool,
                 valid_start: jnp.ndarray | None = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """``valid_start`` [B] (raw-frame units, default 0) marks frames
        before the utterance as invalid — used by the streaming engine
        (streaming.py), whose windows carry pre-stream history. Offline
        callers never pass it. Must be divisible by the total time
        stride so the per-layer start index stays exact."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        x = x.astype(dtype)[..., None]  # [B, T, F, 1]
        lens = feat_lens
        start = valid_start
        for i, ((kt, kf, st, sf), ch) in enumerate(
                zip(cfg.conv_layers, cfg.conv_channels)):
            # Explicit time padding instead of "SAME": XLA's SAME grid
            # for strided convs depends on the PARITY of the padded
            # input length (even T: pad_left=(kt-st)//2, odd T: one
            # more), which would make the sampling grid a function of
            # the bucket size and break chunked streaming. This choice
            # equals SAME for even T and is length-invariant; output
            # length stays ceil(T/st). Frequency padding is computed
            # the same way SAME would (F is static).
            pt = (kt - st) // 2
            x = nn.Conv(ch, kernel_size=(kt, kf), strides=(st, sf),
                        padding=((pt, kt - 1 - pt),
                                 freq_padding(x.shape[2], kf, sf)),
                        use_bias=False, dtype=dtype, name=f"conv{i}",
                        conv_general_dilated=functools.partial(
                            _flax_conv, layer=f"conv{i}"))(x)
            lens = -(-lens // st)
            mask = length_mask(lens, x.shape[1])
            if start is not None:
                start = start // st
                mask = mask * (jnp.arange(x.shape[1])[None, :]
                               >= start[:, None]).astype(jnp.float32)
            x = MaskedBatchNorm(name=f"bn{i}")(x, mask, train)
            x = clipped_relu(x, cfg.relu_clip)
            # Zero invalid frames so they can't leak into the next
            # layer through the conv receptive field (BN stats in
            # training, SAME-pad equivalence in streaming inference).
            x = x * mask[:, :, None, None].astype(x.dtype)
        b, t, f, c = x.shape
        return x.reshape(b, t, f * c), lens
