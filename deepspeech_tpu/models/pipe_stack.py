"""Pipeline-parallel RNN middle stack (SURVEY.md §2 component 14,
parallelism beyond the reference's DP-only NCCL layout).

DS2's RNN stack is a depth-L tower whose layers 1..L-1 are HOMOGENEOUS
[B,T,H] -> [B,T,H] blocks (masked sequence BN -> input projection ->
(bi)directional recurrence). That homogeneity is what makes TPU-native
pipeline parallelism clean: stack each block's weights along a leading
layer axis, shard that axis over the mesh's ``pipe`` dimension, and run
a GPipe microbatch schedule inside one ``shard_map`` — activations hop
stage-to-stage over ICI via ``ppermute`` while every stage's matmuls
stay dense on the MXU. XLA differentiates the whole schedule (the
transpose of ``ppermute`` is the reverse hop, so the backward pass is
the reverse pipeline for free), and ``jax.checkpoint`` around each
stage bounds residual memory to one microbatch per live round.

Schedule (M microbatches, P stages, R = M + P - 1 rounds):

    round r: stage p computes microbatch (r - p) when 0 <= r - p < M;
    rank 0 injects microbatch r, rank P-1 emits microbatch r - (P-1).

Bubble fraction is (P-1)/R, the GPipe bound. Layer weights, BN stats,
and (via matching opt_state paths) optimizer momentum all shard over
``pipe`` — each device stores only its own stage, which is the point:
models whose stacked RNN weights outgrow one chip's HBM train anyway.

Semantics notes (both documented GPipe-standard):
- Train-mode BN normalizes each microbatch by its OWN batch stats
  (exactly like the gradient-accumulation path, train.py:160-183); the
  running stats absorb the mean of the per-microbatch stats once per
  step. With pipeline_microbatches == 1 this is bit-identical to the
  sequential stack.
- Eval-mode BN uses running stats, so any M matches the sequential
  stack exactly.

The sequential path (no mesh / pipe axis absent / initialization) runs
the SAME stacked parameters layer-by-layer — it is the parity oracle
for the pipelined path (tests/test_pipeline_pp.py) and what
single-device infer/serve use when restoring a pipeline-trained
checkpoint.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import ModelConfig
from .layers import BN_EPS, BN_MOMENTUM, length_mask, masked_bn_stats
from .rnn import gru_scan, lstm_scan


def _stacked_orthogonal(key, shape, dtype=jnp.float32):
    """Per-layer orthogonal init for a stacked [L, H, G*H] leaf (plain
    orthogonal on the stacked shape would orthogonalize across layers)."""
    init = nn.initializers.orthogonal()
    keys = jax.random.split(key, shape[0])
    return jnp.stack([init(k, shape[1:], dtype) for k in keys])


def _block_apply(cfg: ModelConfig, p: dict, rstats, x, mask, train: bool):
    """One homogeneous block: masked seq BN -> xproj -> (bi)RNN.

    ``p`` holds ONE layer's weights (stacked leaves already sliced).
    Returns (out [B,T,H], (batch_mean, batch_var)) — the stats are the
    batch's own when training (for the running-stat update), the running
    ones otherwise. Math mirrors models/rnn.py RNNLayer + MaskedBatchNorm
    exactly so the sequential path is a drop-in for RNNStack layers 1+.
    """
    dtype = jnp.dtype(cfg.dtype)
    if cfg.rnn_batch_norm:
        x32 = x.astype(jnp.float32)
        if train:
            mean, var = masked_bn_stats(x32, mask)
        else:
            mean, var = rstats
        y = (x32 - mean) * jax.lax.rsqrt(var + BN_EPS)
        y = (y * p["bn_scale"] + p["bn_bias"]).astype(dtype)
    else:
        # rstats still flow (zeros/ones, never applied) so the carry
        # structure is config-independent.
        mean, var = rstats
        y = x.astype(dtype)
    xproj = y @ p["wx_kernel"].astype(dtype) + p["wx_bias"].astype(dtype)
    dot_dtype = None if dtype == jnp.float32 else dtype
    scan = gru_scan if cfg.rnn_type == "gru" else lstm_scan
    out = scan(xproj, mask, p["wh_fw"], p["bh_fw"], reverse=False,
               dot_dtype=dot_dtype, remat_chunk=cfg.rnn_remat_chunk)
    if cfg.bidirectional:
        out = out + scan(xproj, mask, p["wh_bw"], p["bh_bw"], reverse=True,
                         dot_dtype=dot_dtype,
                         remat_chunk=cfg.rnn_remat_chunk)
    out = out * mask[:, :, None]
    return out.astype(dtype), (mean, var)


def _stage_apply(cfg: ModelConfig, stacked_local, rstats_local, x, mask,
                 train: bool):
    """Apply this stage's local layers sequentially; returns the stage
    output and the stacked per-layer batch stats [L_local, H]."""
    n_local = jax.tree.leaves(stacked_local)[0].shape[0]
    stats = []
    for i in range(n_local):
        pi = jax.tree.map(lambda a: a[i], stacked_local)
        ri = (rstats_local[0][i], rstats_local[1][i])
        x, st = _block_apply(cfg, pi, ri, x, mask, train)
        stats.append(st)
    return x, (jnp.stack([s[0] for s in stats]),
               jnp.stack([s[1] for s in stats]))


def _pipe_fn(cfg: ModelConfig, train: bool, n_stages: int, n_micro: int,
             pipe_axis: str, stacked_local, rstats_local, xm, maskm):
    """The SPMD pipeline body (inside shard_map, manual over ``pipe``).

    xm [M, b, T, H] / maskm [M, b, T] are replicated along pipe (their
    batch dim stays GSPMD-auto over ``data``, so BN's batch reductions
    inside each stage still see the global microbatch). stacked_local /
    rstats_local leaves are this stage's [L/P, ...] slices.
    """
    p_rank = jax.lax.axis_index(pipe_axis)
    rounds = n_micro + n_stages - 1
    # Activations cross the shard_map boundary as f32 (see caller);
    # compute in the model dtype inside.
    dtype = jnp.dtype(cfg.dtype)
    xm = xm.astype(dtype)
    stage = jax.checkpoint(
        partial(_stage_apply, cfg, stacked_local, rstats_local,
                train=train))
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def body(carry, r):
        cur, sacc = carry
        idx = jnp.clip(r - p_rank, 0, n_micro - 1)
        xin = jnp.where(p_rank == 0, xm[idx], cur)
        y, st = stage(xin, maskm[idx])
        valid = ((r - p_rank >= 0) & (r - p_rank < n_micro)).astype(
            jnp.float32)
        sacc = jax.tree.map(lambda a, s: a + valid * s, sacc, st)
        nxt = jax.lax.ppermute(y, pipe_axis, perm)
        piece = jnp.where((p_rank == n_stages - 1) & (valid > 0), y, 0.0)
        return (nxt, sacc), piece

    szero = jax.tree.map(jnp.zeros_like, rstats_local)
    (_, sacc), pieces = jax.lax.scan(
        body, (jnp.zeros(xm.shape[1:], xm.dtype), szero),
        jnp.arange(rounds))
    # Rank P-1 emitted microbatch m at round m + P - 1; other ranks'
    # pieces are zero, so a psum over pipe replicates the result set.
    # The psum (and the boundary crossing back out) runs in f32: a bf16
    # collective at this boundary check-fails XLA:CPU's
    # AllReducePromotion pass ("Invalid binary instruction opcode
    # copy"), and one cast per step is noise anyway.
    out_m = jax.lax.psum(
        pieces[n_stages - 1: n_stages - 1 + n_micro].astype(jnp.float32),
        pipe_axis)
    # Mean of each layer's per-microbatch stats (every stage saw exactly
    # n_micro valid rounds) — feeds the running-stat update only.
    stats = jax.tree.map(lambda a: a / n_micro, sacc)
    return out_m, stats


class PipelinedRNNStack(nn.Module):
    """Layers 1..rnn_layers-1 of the RNN stack, stacked + pipelined.

    Used by DeepSpeech2 when ``cfg.pipeline_stages > 1`` (layer 0 keeps
    its own width-changing RNNLayer outside). Parameter tree (all leaves
    stacked [Lp, ...], sharded over ``pipe`` by parallel/mesh.py's
    ``rnn_pipe/`` rule):

      rnn_pipe/{bn_scale, bn_bias, wx_kernel, wx_bias,
                wh_fw, bh_fw[, wh_bw, bh_bw]}
      batch_stats: rnn_pipe/{mean, var}
    """

    cfg: ModelConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray, lens: jnp.ndarray,
                 train: bool) -> jnp.ndarray:
        cfg = self.cfg
        n_layers = cfg.rnn_layers - 1
        n_stages = cfg.pipeline_stages
        if n_layers < 1 or n_layers % n_stages:
            raise ValueError(
                f"pipeline_stages={n_stages} must divide "
                f"rnn_layers-1={n_layers}")
        h = cfg.rnn_hidden
        g = (3 if cfg.rnn_type == "gru" else 4) * h
        if x.shape[-1] != h:
            raise ValueError(f"pipelined layers expect width {h}, "
                             f"got {x.shape[-1]}")

        params = {
            # lecun_normal's fan_in/out come from the trailing two dims,
            # so the stacked shape is per-layer correct as-is.
            "wx_kernel": self.param("wx_kernel",
                                    nn.initializers.lecun_normal(),
                                    (n_layers, h, g), jnp.float32),
            "wx_bias": self.param("wx_bias", nn.initializers.zeros,
                                  (n_layers, g), jnp.float32),
            "wh_fw": self.param("wh_fw", _stacked_orthogonal,
                                (n_layers, h, g), jnp.float32),
            "bh_fw": self.param("bh_fw", nn.initializers.zeros,
                                (n_layers, g), jnp.float32),
        }
        if cfg.bidirectional:
            params["wh_bw"] = self.param("wh_bw", _stacked_orthogonal,
                                         (n_layers, h, g), jnp.float32)
            params["bh_bw"] = self.param("bh_bw", nn.initializers.zeros,
                                         (n_layers, g), jnp.float32)
        if cfg.rnn_batch_norm:
            params["bn_scale"] = self.param(
                "bn_scale", nn.initializers.ones, (n_layers, h),
                jnp.float32)
            params["bn_bias"] = self.param(
                "bn_bias", nn.initializers.zeros, (n_layers, h),
                jnp.float32)
            ra_mean = self.variable("batch_stats", "mean",
                                    lambda: jnp.zeros((n_layers, h),
                                                      jnp.float32))
            ra_var = self.variable("batch_stats", "var",
                                   lambda: jnp.ones((n_layers, h),
                                                    jnp.float32))
            rstats = (ra_mean.value, ra_var.value)
        else:
            # Placeholders keep the stage carry structure uniform; the
            # BN branch never reads them.
            rstats = (jnp.zeros((n_layers, h), jnp.float32),
                      jnp.ones((n_layers, h), jnp.float32))
        mask = length_mask(lens, x.shape[1])

        pipelined = (not self.is_initializing() and self.mesh is not None
                     and "pipe" in self.mesh.axis_names
                     and self.mesh.shape["pipe"] > 1)
        if pipelined and self.mesh.shape["pipe"] != n_stages:
            raise ValueError(
                f"mesh pipe axis {self.mesh.shape['pipe']} != "
                f"pipeline_stages {n_stages}")

        if not pipelined:
            # Sequential oracle: same stacked params, same math, no
            # microbatching — used for init, single-device restore, and
            # as the parity reference in tests.
            x, stats = _stage_apply(cfg, params, rstats, x, mask, train)
        else:
            m = cfg.pipeline_microbatches or n_stages
            b = x.shape[0]
            if b % m:
                raise ValueError(f"batch {b} not divisible by "
                                 f"pipeline_microbatches {m}")
            # Strided microbatch split (row i -> microbatch i % m): each
            # data rank's contiguous row block contributes rows to every
            # microbatch, so no cross-device resharding (train.py accum
            # uses the same trick).
            mesh = self.mesh
            xm = x.reshape(b // m, m, *x.shape[1:]).swapaxes(0, 1)
            maskm = mask.reshape(b // m, m, mask.shape[1]).swapaxes(0, 1)
            xm = jax.lax.with_sharding_constraint(
                xm, NamedSharding(mesh, P(None, "data")))
            # Boundary tensors cross in f32 (cast back below): a bf16
            # cotangent psum at the shard_map boundary check-fails
            # XLA:CPU's AllReducePromotion ("opcode copy"); _pipe_fn
            # computes in the model dtype internally.
            out_m, stats = shard_map(
                partial(_pipe_fn, cfg, train, n_stages, m, "pipe"),
                mesh=mesh,
                in_specs=(jax.tree.map(lambda _: P("pipe"), params),
                          (P("pipe"), P("pipe")), P(), P()),
                out_specs=(P(), (P("pipe"), P("pipe"))),
                axis_names={"pipe"}, check_vma=False,
            )(params, rstats, xm.astype(jnp.float32), maskm)
            x = out_m.swapaxes(0, 1).reshape(
                b, *out_m.shape[2:]).astype(jnp.dtype(cfg.dtype))

        if train and cfg.rnn_batch_norm and not self.is_initializing():
            ra_mean.value = (BN_MOMENTUM * ra_mean.value
                             + (1 - BN_MOMENTUM) * stats[0])
            ra_var.value = (BN_MOMENTUM * ra_var.value
                            + (1 - BN_MOMENTUM) * stats[1])
        return x
