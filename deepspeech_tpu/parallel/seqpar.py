"""Sequence-parallel long-audio inference (SURVEY.md §2 component 14;
"long-context is first-class").

The chunked streaming engine (deepspeech_tpu/streaming.py) already
transcribes unbounded audio on one chip for the CAUSAL (lookahead)
variants. What it cannot cover is the BIDIRECTIONAL offline models —
the backward recurrence needs the whole utterance, so a long recording
(hours of audio => millions of feature frames) must be resident at
once, and one chip's HBM caps the utterance length.

This module removes that cap the TPU-native way: shard the TIME axis
over the mesh and run the whole encoder inside one ``shard_map``:

- conv frontend: halo exchange via ``ppermute`` (left halo = each
  layer's left pad, right halo = kt - stride - left), then a VALID
  conv — bit-identical sampling grid to the offline explicit-pad conv
  (models/conv.py). Edge shards receive ppermute's zero fill, which IS
  the offline zero padding.
- recurrences: inherently sequential, so the carry RELAYS across
  shards in S rounds — shard k's forward scan runs with the real
  carry at round k and hands its final state rightward; the backward
  direction relays the opposite way in the SAME rounds loop, so both
  wavefronts overlap. Wall-clock per direction stays O(T) (a scan is a
  scan), but activations and logits live [T/S] per device — the memory
  scaling that makes the length unbounded. Conv, input projections,
  and the vocab head parallelize S-ways for free.
- BN: inference reads running statistics (time-local, no collectives);
  training psums mask-weighted partial stats over the seq axis.

Surfaces: ``sp_forward``/``sp_greedy_decode`` (inference),
``sp_beam_search`` (the beam state relays too), and ``sp_loss``
(training — the CTC alpha band relays as well and gradients are
exactly the offline ones). All operate on the standard (non-pipelined)
DeepSpeech2 parameter tree; bidirectional or unidirectional GRU/LSTM
stacks without lookahead (lookahead models stream natively and don't
need this).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..config import ModelConfig
from ..models.conv import freq_folded_conv, freq_padding
from ..models.layers import BN_EPS
from ..models.rnn import gru_scan, lstm_scan
from .mesh import DATA_AXIS

# The relay needs every shard's local scan to see the same static
# shapes; callers pad T to sp_frame_multiple(cfg, n_shards).


def sp_frame_multiple(cfg: ModelConfig, n_shards: int) -> int:
    """Feature-frame count must divide by this for an SP forward: every
    shard takes an equal slice whose length divides the conv stride."""
    return n_shards * cfg.time_stride


def _conv_halo(kt: int, st: int) -> Tuple[int, int]:
    """(left, right) halo frames a conv layer needs from its neighbors
    — the SAME split _conv_sp exchanges via ppermute; shared so the
    _validate guard can't drift from the exchange arithmetic."""
    pt = (kt - st) // 2
    return pt, kt - st - pt


def sp_min_frames(cfg: ModelConfig, n_shards: int) -> int:
    """Smallest total feature-frame count an SP forward accepts on
    ``n_shards``: every shard's slice must cover each conv layer's halo
    (see _validate) and divide the stride chain. Callers that own the
    padding (infer's sp decode) zero-pad short utterances up to this —
    padding frames are masked, so outputs stay exact."""
    need = 1  # >=1 post-conv frame per shard
    for (kt, _, st, _) in reversed(cfg.conv_layers):
        need = max(need * st, max(_conv_halo(kt, st)), 1)
    stride = cfg.time_stride
    need = -(-need // stride) * stride  # align to the stride chain
    return need * n_shards


def _validate(cfg: ModelConfig, mesh, axis: str, t: int) -> int:
    """Shared entry guards; returns the shard count."""
    if cfg.lookahead_context > 0:
        raise ValueError("lookahead models stream natively "
                         "(streaming.py); sequence parallelism targets "
                         "bidirectional offline models")
    if cfg.pipeline_stages > 1:
        raise ValueError("sequence parallelism expects the standard "
                         "(non-pipelined) parameter tree")
    n_shards = int(mesh.shape[axis])
    mult = sp_frame_multiple(cfg, n_shards)
    if t % mult:
        raise ValueError(f"frames {t} must divide by {mult} "
                         f"(= shards * time_stride); zero-pad the tail")
    # The conv halo exchange reaches exactly one neighbor, so every
    # shard's local slice must cover each layer's halo. Short of that,
    # x[:, -halo:] silently yields fewer frames than the halo needs —
    # one regime fails with an opaque conv shape error, another
    # produces misaligned logits (ADVICE r3 #1). Replays _conv_sp's
    # static length arithmetic.
    tl = t // n_shards
    for i, (kt, kf, st, sf) in enumerate(cfg.conv_layers):
        halo = max(_conv_halo(kt, st))
        if tl < halo:
            raise ValueError(
                f"too many sequence shards for this utterance length: "
                f"conv layer {i} needs a {halo}-frame halo but each of "
                f"the {n_shards} shards holds only {tl} frames at that "
                f"layer; use fewer shards or longer (padded) inputs")
        tl //= st
    return n_shards


def _bn_sp(x, p, rstats, mask, train: bool, axis: str):
    """Masked BN over (batch, GLOBAL time) under the time-sharded
    layout. Eval reads running stats (time-local). Train computes the
    mask-weighted stats from local partial sums psum'd over the seq
    axis — numerically the models/layers.masked_bn_stats definition,
    with the (batch, time) reduction split across shards.

    Returns (normalized [.., C] float32, {"mean", "var"} batch stats —
    the running ones in eval, this batch's in train).
    """
    x32 = x.astype(jnp.float32)
    if not train:
        mean, var = rstats["mean"], rstats["var"]
    else:
        w = jnp.broadcast_to(
            mask.reshape(mask.shape + (1,) * (x32.ndim - 3)),
            x32.shape[:-1])
        wexp = w[..., None]
        red = tuple(range(x32.ndim - 1))
        denom = jnp.maximum(jax.lax.psum(jnp.sum(w), axis), 1.0)
        mean = jax.lax.psum(jnp.sum(x32 * wexp, axis=red), axis) / denom
        var = jax.lax.psum(
            jnp.sum(wexp * (x32 - mean) ** 2, axis=red), axis) / denom
    y = (x32 - mean) * jax.lax.rsqrt(var + BN_EPS)
    return y * p["scale"] + p["bias"], {"mean": mean, "var": var}


def _conv_sp(cfg: ModelConfig, params, stats, x, lens, axis, n_shards,
             t_off, train: bool = False):
    """models/conv.py ConvFrontend, time-sharded.

    x [B, Tl, F, 1] local slice; t_off = this shard's global frame
    offset (traced). Returns ([B, Tl', F'*C], conv lens, local offset
    in conv frames, {bn{i}: batch stats} when training).
    """
    dtype = jnp.dtype(cfg.dtype)
    x = x.astype(dtype)
    new_stats = {}
    for i, ((kt, kf, st, sf), ch) in enumerate(
            zip(cfg.conv_layers, cfg.conv_channels)):
        halo_l, halo_r = _conv_halo(kt, st)
        # Neighbors' boundary frames; edge shards get ppermute's zero
        # fill = the offline explicit zero padding.
        send_r = [(k, k + 1) for k in range(n_shards - 1)]
        send_l = [(k, k - 1) for k in range(1, n_shards)]
        left = jax.lax.ppermute(x[:, -halo_l:], axis, send_r) \
            if halo_l else x[:, :0]
        right = jax.lax.ppermute(x[:, :halo_r], axis, send_l) \
            if halo_r else x[:, :0]
        x = jnp.concatenate([left, x, right], axis=1)
        x = freq_folded_conv(
            x.astype(dtype),
            params[f"conv{i}"]["kernel"].astype(dtype), (st, sf),
            ((0, 0), freq_padding(x.shape[2], kf, sf)), f"conv{i}")
        lens = -(-lens // st)
        t_off = t_off // st
        # Global-validity mask for the local span.
        gidx = t_off + jnp.arange(x.shape[1])
        mask = (gidx[None, :] < lens[:, None]).astype(jnp.float32)
        x, st_i = _bn_sp(x, params[f"bn{i}"], stats[f"bn{i}"], mask,
                         train, axis)
        new_stats[f"bn{i}"] = st_i
        x = jnp.clip(x, 0.0, cfg.relu_clip)
        x = (x * mask[:, :, None, None]).astype(dtype)
    b, tl, f, c = x.shape
    return x.reshape(b, tl, f * c), lens, t_off, new_stats


def _relay_scan(cfg: ModelConfig, xproj, mask, w_h, b_h, reverse, axis,
                n_shards, my):
    """One direction of one RNN layer with the carry relayed across
    shards. Round r: shard r (forward) / shard S-1-r (backward) scans
    its chunk with the true incoming carry and hands its final state to
    the next shard; other shards' round work is discarded. Outputs are
    each shard's local [B, Tl, H] hidden states."""
    dtype = jnp.dtype(cfg.dtype)
    dot_dtype = None if dtype == jnp.float32 else dtype
    if reverse:
        xproj, mask = xproj[:, ::-1], mask[:, ::-1]
        # In reversed-time coordinates the relay flows S-1 -> 0.
        my = n_shards - 1 - my
        perm = [(k, k - 1) for k in range(1, n_shards)]
    else:
        perm = [(k, k + 1) for k in range(n_shards - 1)]
    b, tl, gh = xproj.shape
    h = gh // (3 if cfg.rnn_type == "gru" else 4)

    if cfg.rnn_type == "gru":
        def chunk(carry):
            return gru_scan(xproj, mask, w_h, b_h, dot_dtype=dot_dtype,
                            h0=carry, return_final=True)
        init = jnp.zeros((b, h), jnp.float32)
    else:
        def chunk(carry):
            return lstm_scan(xproj, mask, w_h, b_h, dot_dtype=dot_dtype,
                             hc0=carry, return_final=True)
        init = (jnp.zeros((b, h), jnp.float32),
                jnp.zeros((b, h), jnp.float32))

    def body(state, r):
        carry, out = state
        ys, fin = chunk(carry)
        keep = r == my
        out = jnp.where(keep, ys, out)
        # Shard r's final state, delivered to shard r+1 (relay coords);
        # adopt it only when it is really ours (end of round my-1).
        fin = jax.tree.map(lambda f: jnp.where(keep, f, 0.0), fin)
        delivered = jax.tree.map(
            lambda f: jax.lax.ppermute(f, axis, perm), fin)
        carry = jax.tree.map(
            lambda c, d: jnp.where(r + 1 == my, d, c), carry, delivered)
        return (carry, out), None

    # lax.scan (not fori_loop): the relay must be reverse-differentiable
    # for sequence-parallel TRAINING (sp_loss) — the transpose of each
    # ppermute hop is the reverse hop, so the backward pass relays the
    # cotangents the opposite way for free.
    (_, out), _ = jax.lax.scan(
        body, (init, jnp.zeros((b, tl, h), jnp.float32)),
        jnp.arange(n_shards))
    return out[:, ::-1] if reverse else out


def _forward_local(cfg: ModelConfig, params, stats, feats, lens, axis,
                   n_shards, train: bool = False):
    """Returns (logits_local f32, conv lens, new_batch_stats).

    ``new_batch_stats`` mirrors the flax ``batch_stats`` tree structure
    and holds THIS batch's statistics when training (for the caller's
    running-average update); in eval it echoes the running stats.
    """
    my = jax.lax.axis_index(axis)
    tl_raw = feats.shape[1]
    t_off = my * tl_raw
    x, clens, t_off, conv_stats = _conv_sp(
        cfg, params["conv"], stats["conv"], feats[..., None], lens,
        axis, n_shards, t_off, train)
    dtype = jnp.dtype(cfg.dtype)
    gidx = t_off + jnp.arange(x.shape[1])
    mask = (gidx[None, :] < clens[:, None]).astype(jnp.float32)
    dirs = [False, True] if cfg.bidirectional else [False]
    # Mirrors the flax batch_stats treedef exactly (an "rnn" subtree
    # exists iff the rnn layers carry BN) so out_specs can be derived
    # by tree-mapping over the running stats.
    new_stats = {"conv": conv_stats}
    if cfg.rnn_batch_norm:
        new_stats["rnn"] = {}
    for i in range(cfg.rnn_layers):
        p = params["rnn"][f"rnn{i}"]
        if cfg.rnn_batch_norm:
            x, st_i = _bn_sp(x, p["bn"], stats["rnn"][f"rnn{i}"]["bn"],
                             mask, train, axis)
            new_stats["rnn"][f"rnn{i}"] = {"bn": st_i}
            x = x.astype(dtype)
        xproj = (x.astype(dtype) @ p["wx"]["kernel"].astype(dtype)
                 + p["wx"]["bias"].astype(dtype))
        out = None
        for rev in dirs:
            sfx = "bw" if rev else "fw"
            ys = _relay_scan(cfg, xproj, mask, p[f"wh_{sfx}"],
                             p[f"bh_{sfx}"], rev, axis, n_shards, my)
            out = ys if out is None else out + ys
        x = (out * mask[:, :, None]).astype(dtype)
    x, st_out = _bn_sp(x, params["bn_out"], stats["bn_out"], mask,
                       train, axis)
    new_stats["bn_out"] = st_out
    logits = (x.astype(dtype) @ params["head"]["kernel"].astype(dtype)
              + params["head"]["bias"].astype(dtype))
    return logits.astype(jnp.float32), clens, new_stats


def sp_forward(cfg: ModelConfig, variables, features, feat_lens, mesh,
               axis: str = DATA_AXIS) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sequence-parallel offline forward: logits for utterances whose
    activations would not fit one device.

    ``features`` [B, T, F] with T % sp_frame_multiple == 0 (pad with
    zeros beyond ``feat_lens``; padding frames are masked identically
    to the offline path, so outputs match exactly). Returns
    (logits [B, T', V] — sharded over ``axis`` along T' — and conv
    lens). Designed for B small / T huge: batch parallelism is useless
    for one long recording, so the mesh's data axis is re-purposed as
    the sequence axis.

    **Cost model — what S-way sharding buys and what it costs.** The
    win is MEMORY: activations, xproj, logits, and the loss band all
    live [T/S] per device, which is what makes longer-than-HBM audio
    decodable/trainable at all. Compute splits S-ways only for the
    pointwise/matmul parts (conv, input projections, BN, head). The
    RECURRENCE does not: exactness forces the relay (_relay_scan) to
    run S rounds in which every shard re-scans its chunk and discards
    non-active rounds' work, so each RNN layer-direction costs the
    full O(T) wall-clock with device utilization 1/S during relays,
    i.e. ~S× redundant recurrence FLOPs vs one device. The L layers ×
    2 directions serialize exactly as offline. Rule of thumb: use the
    fewest shards that make the activations fit; SP is a capacity
    tool, not a recurrence speedup.
    """
    n_shards = _validate(cfg, mesh, axis, features.shape[1])
    params = variables["params"]
    stats = variables["batch_stats"]
    logits, clens, _ = shard_map(
        lambda f, l: _forward_local(cfg, params, stats, f, l, axis,
                                    n_shards),
        mesh=mesh,
        in_specs=(P(None, axis), P()),
        out_specs=(P(None, axis), P(), jax.tree.map(lambda _: P(),
                                                    stats)),
        check_vma=False,
    )(features, jnp.asarray(feat_lens))
    return logits, clens


def sp_greedy_decode(cfg: ModelConfig, variables, features, feat_lens,
                     mesh, axis: str = DATA_AXIS):
    """Greedy CTC ids for long audio: SP forward, local argmax, gather
    only the int32 ids (never the [T', V] logits)."""
    logits, lens = sp_forward(cfg, variables, features, feat_lens, mesh,
                              axis)
    ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return np.asarray(ids), np.asarray(lens)


def _ctc_alpha_relay(lp_local, labels, input_lens, label_lens, axis,
                     n_shards, my):
    """Per-utterance CTC negative log-likelihood with the time axis
    sharded: the banded alpha recursion's [B, S] state relays across
    shards exactly like an RNN carry (ops/ctc.py owns the step math;
    the t==0 initialization rides the global frame index so shard 0
    starts the recursion). Differentiable — grads flow by autodiff
    through the chunk scans and transpose-ppermute back along the
    relay, which is how sp_loss trains without materializing [T, V]
    logits anywhere."""
    from ..ops.ctc import NEG, _alpha_step, _transition_masks

    b, tl, v = lp_local.shape
    ext, allowed_skip, valid_s = _transition_masks(labels, label_lens)
    s_max = ext.shape[1]
    lp_ext = jnp.take_along_axis(
        lp_local, jnp.broadcast_to(ext[:, None, :], (b, tl, s_max)),
        axis=2)
    gidx = my * tl + jnp.arange(tl)
    # t==0 initialization, hoisted out of the per-frame step: only the
    # global first frame (shard 0's local frame 0) can take it, so it
    # reads lp_ext's first local frame unconditionally.
    lpe0 = lp_ext[:, 0]
    init0 = jnp.full((b, s_max), NEG)
    init0 = init0.at[:, 0].set(lpe0[:, 0])
    init0 = init0.at[:, 1].set(
        jnp.where(label_lens > 0, lpe0[:, 1], NEG))
    init0 = jnp.where(valid_s, init0, NEG)

    def chunk(alpha0):
        def step(alpha, xt):
            gt, lpe = xt
            new = _alpha_step(alpha, lpe, allowed_skip, valid_s)
            new = jnp.where(gt == 0, init0, new)
            new = jnp.where((gt < input_lens)[:, None], new, alpha)
            return new, None

        a, _ = jax.lax.scan(step, alpha0,
                            (gidx, jnp.moveaxis(lp_ext, 1, 0)))
        return a

    perm = [(k, k + 1) for k in range(n_shards - 1)]

    def body(state, r):
        alpha, fin = state
        a_new = chunk(alpha)
        keep = r == my
        delivered = jax.lax.ppermute(
            jnp.where(keep, a_new, NEG), axis, perm)
        alpha = jnp.where(r + 1 == my, delivered, alpha)
        fin = jnp.where(keep & (my == n_shards - 1), a_new, fin)
        return (alpha, fin), None

    init = jnp.full((b, s_max), NEG)
    (_, fin), _ = jax.lax.scan(body, (init, init),
                               jnp.arange(n_shards))
    # Replicate the last shard's final alpha (others contribute zeros).
    fin = jax.lax.psum(jnp.where(my == n_shards - 1, fin, 0.0), axis)
    s_last = 2 * label_lens
    a_last = jnp.take_along_axis(fin, s_last[:, None], axis=1)[:, 0]
    a_prev = jnp.where(
        label_lens > 0,
        jnp.take_along_axis(fin, jnp.maximum(s_last - 1, 0)[:, None],
                            axis=1)[:, 0],
        NEG)
    return -jnp.logaddexp(a_last, a_prev)


def sp_loss(cfg: ModelConfig, variables, features, feat_lens, labels,
            label_lens, mesh, axis: str = DATA_AXIS):
    """Mean CTC loss of a TRAIN-mode forward with the time axis sharded
    — long-audio training: activations, logits, and the loss recursion
    all live [T/S] per device; nothing full-length is ever
    materialized. Differentiate with ``jax.grad`` (the shard_map
    transpose psums the replicated params' cotangents, so gradients
    come out exactly the offline ones — tests/test_seqpar.py).

    Returns (loss scalar, new_batch_stats) where new_batch_stats holds
    this batch's BN statistics in the flax tree layout (caller applies
    the momentum update, mirroring MaskedBatchNorm).
    """
    n_shards = _validate(cfg, mesh, axis, features.shape[1])
    params = variables["params"]
    stats = variables["batch_stats"]

    def local(p, st, f, l, lab, lablen):
        my = jax.lax.axis_index(axis)
        logits, clens, new_stats = _forward_local(
            cfg, p, st, f, l, axis, n_shards, train=True)
        lp = jax.nn.log_softmax(logits, axis=-1)
        per_utt = _ctc_alpha_relay(lp, lab, clens, lablen, axis,
                                   n_shards, my)
        return jnp.mean(per_utt), new_stats

    # Params/stats ride as explicit replicated operands (not closure
    # captures) so jax.grad's shard_map transpose psums their
    # cotangents — the gradients of the replicated weights.
    return shard_map(
        local, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(), params),
                  jax.tree.map(lambda _: P(), stats),
                  P(None, axis), P(), P(), P()),
        out_specs=(P(), jax.tree.map(lambda _: P(), stats)),
        check_vma=False,
    )(params, stats, features, jnp.asarray(feat_lens),
      jnp.asarray(labels), jnp.asarray(label_lens))


def sp_beam_search(cfg: ModelConfig, variables, features, feat_lens,
                   mesh, beam_width: int, prune_top_k: int,
                   max_len: int, lm_table=None,
                   merge_impl: str = "auto", axis: str = DATA_AXIS):
    """Exact CTC prefix beam search over time-sharded long audio.

    Composition of two proven invariants: ``beam_search_chunk`` scanned
    over chunks is bit-identical to one offline scan (decode/beam.py),
    and the SP relay hands a state across shards exactly once in shard
    order. So the beam state itself relays: shard k advances the state
    over its local log-probs at round k and hands it rightward; the
    final state (shard S-1, round S-1) psum-replicates out and
    finalizes. The [T', V] log-probs never leave their shard — beam
    search (with optional on-device LM fusion) over recordings whose
    logits would not fit one device. Returns beam_search's
    (prefixes [B, W, Lmax], lens [B, W], scores [B, W]).
    """
    from ..decode.beam import beam_finalize, beam_init, beam_search_chunk

    logits, clens = sp_forward(cfg, variables, features, feat_lens, mesh,
                               axis)
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    n_shards = int(mesh.shape[axis])
    b, tg, v = lp.shape
    tl = tg // n_shards
    state0 = beam_init(b, beam_width, max_len)
    perm = [(k, k + 1) for k in range(n_shards - 1)]

    def local(lp_loc, clens, st0, lm):
        my = jax.lax.axis_index(axis)
        gidx = my * tl + jnp.arange(tl)
        valid = gidx[None, :] < clens[:, None]

        def body(r, carry):
            st, fin = carry
            new = beam_search_chunk(st, lp_loc, valid,
                                    prune_top_k=prune_top_k,
                                    lm_table=lm, merge_impl=merge_impl)
            keep = r == my
            sent = jax.tree.map(
                lambda n: jnp.where(keep, n, jnp.zeros_like(n)), new)
            delivered = jax.tree.map(
                lambda s: jax.lax.ppermute(s, axis, perm), sent)
            st = jax.tree.map(
                lambda c, d: jnp.where(r + 1 == my, d, c), st, delivered)
            last = keep & (my == n_shards - 1)
            fin = jax.tree.map(
                lambda f, n: jnp.where(last, n, f), fin, new)
            return st, fin

        zeros = jax.tree.map(jnp.zeros_like, st0)
        _, fin = jax.lax.fori_loop(0, n_shards, body, (st0, zeros))
        # Nonzero only on the last shard -> psum replicates it
        # (BeamState leaves are f32/int32/uint32; all psum cleanly).
        return jax.tree.map(lambda f: jax.lax.psum(f, axis), fin)

    lm_specs = jax.tree.map(lambda _: P(), lm_table) \
        if lm_table is not None else None
    final = shard_map(
        local, mesh=mesh,
        in_specs=(P(None, axis), P(),
                  jax.tree.map(lambda _: P(), state0), lm_specs),
        out_specs=jax.tree.map(lambda _: P(), state0),
        check_vma=False,
    )(lp, clens, state0, lm_table)
    return beam_finalize(final)
