"""Analytic FLOP accounting for the DS2 model family (VERDICT r2 #2).

Converts a rate (``utt/s/chip``) into an absolute scale: model
flops/step -> achieved TFLOP/s -> MFU against the chip's bf16 peak.
Without this there is no way to judge "is this fast" — a kernel's
speedup over this repo's own oracle says nothing about hardware
capability (BASELINE.json:5 north-star scale clause). The benchmark
keeps its own copy of these counts (``benchmark/costs/``, PERF.md
section 3: `mfu_pct`).

Conventions (the standard MFU bookkeeping, e.g. the PaLM appendix):
- A matmul [m,k]x[k,n] counts 2*m*k*n flops.
- Backward counts 2x forward for every matmul/conv (dX and dW each cost
  one forward-sized contraction), so a train step is 3x forward.
- Elementwise work (gate nonlinearities, BN, ReLU, masking, SGD update)
  and the CTC alpha-beta recursion are excluded: they are O(B*T*H) /
  O(B*T*S) against matmul terms of O(B*T*H^2) — sub-1% at every preset
  (the CTC inner loop does no matmuls at all; see ops/ctc.py).

Model flow (models/ds2.py): conv frontend -> L x (Bi)RNN with summed
directions (layer output width H, models/rnn.py) -> optional lookahead
conv -> Dense head [H, V].
"""

from __future__ import annotations

import os
import re
from typing import Optional

from ..config import ModelConfig


def conv_frontend_flops(cfg: ModelConfig, frames: int,
                        num_features: int = 161) -> tuple[int, int, int]:
    """(flops, out_frames, out_features) of the conv stack, batch 1.

    Mirrors models/conv.py: SAME-style padding, out_len=ceil(T/stride),
    F' = ceil(F/sf) per layer; each output element costs
    2 * kt * kf * C_in flops. ``num_features`` is the spectrogram bin
    count (FeatureConfig.num_features; 161 is every preset's default).
    """
    t = frames
    f = num_features
    c_in = 1
    flops = 0
    for (kt, kf, st, sf), c_out in zip(cfg.conv_layers, cfg.conv_channels):
        t = -(-t // st)
        f = -(-f // sf)
        flops += 2 * t * f * c_out * kt * kf * c_in
        c_in = c_out
    return flops, t, f * c_in


def rnn_stack_flops(cfg: ModelConfig, t: int, d_in: int) -> int:
    """Flops of the RNN stack forward, batch 1, ``t`` post-conv frames.

    Per layer and direction: hoisted input projection [t, d] x [d, gH]
    plus the recurrent matmul [1, H] x [H, gH] per step (g=3 for GRU,
    4 for LSTM; models/rnn.py gru_scan / lstm_scan). Bidirectional
    doubles both; directions are summed so every layer after the first
    sees width H.
    """
    g = 4 if cfg.rnn_type == "lstm" else 3
    h = cfg.rnn_hidden
    ndir = 2 if cfg.bidirectional else 1
    flops = 0
    d = d_in
    for _ in range(cfg.rnn_layers):
        flops += ndir * (2 * t * d * g * h + 2 * t * h * g * h)
        d = h
    return flops


def ds2_step_flops(cfg: ModelConfig, batch: int, frames: int,
                   num_features: int = 161) -> int:
    """Total flops of ONE training step (fwd + bwd + update) at
    ``batch`` utterances of ``frames`` feature frames each."""
    conv, t, d = conv_frontend_flops(cfg, frames, num_features)
    fwd = conv + rnn_stack_flops(cfg, t, d)
    if cfg.lookahead_context > 0:
        # Depthwise lookahead conv (models/lookahead.py): [t, H] with a
        # context-tap per-channel filter.
        fwd += 2 * t * cfg.rnn_hidden * cfg.lookahead_context
    fwd += 2 * t * cfg.rnn_hidden * cfg.vocab_size  # head
    return 3 * fwd * batch


_PEAK_TFLOPS_BF16 = (
    # device_kind regex (case-insensitive) -> dense bf16 peak TFLOP/s
    # per chip, from Google's published TPU specs. "v5 lite"/"v5e"
    # is the chip the driver benches on (BASELINE.md r2 rows).
    (r"v5\s*lite|v5e", 197.0),
    (r"v5p", 459.0),
    (r"v6|trillium", 918.0),
    (r"v4", 275.0),
    (r"v3", 123.0),
    (r"v2", 46.0),
)


def peak_tflops_bf16(device_kind: str) -> Optional[float]:
    """Per-chip dense bf16 peak for a jax device_kind string; None when
    unknown. ``BENCH_PEAK_TFLOPS`` overrides (e.g. for new chips)."""
    env = os.environ.get("BENCH_PEAK_TFLOPS")
    if env:
        try:
            return float(env)
        except ValueError:
            # A typo'd override must not invalidate an already-timed
            # sweep point (bench calls this after the measurement);
            # fall through to the table.
            pass
    for pat, peak in _PEAK_TFLOPS_BF16:
        if re.search(pat, device_kind, re.IGNORECASE):
            return peak
    return None


def mfu(cfg: ModelConfig, batch: int, frames: int, steps_per_sec: float,
        device_kind: str, num_features: int = 161
        ) -> tuple[float, Optional[float]]:
    """(achieved TFLOP/s, MFU or None if the chip's peak is unknown)."""
    tflops = (ds2_step_flops(cfg, batch, frames, num_features)
              * steps_per_sec / 1e12)
    peak = peak_tflops_bf16(device_kind)
    return tflops, (tflops / peak if peak else None)
