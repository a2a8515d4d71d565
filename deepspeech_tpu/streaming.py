"""Chunked, state-carrying streaming inference for the lookahead variant.

The reference family's streaming model (SURVEY.md §2 component 7,
BASELINE.json:9) is unidirectional GRU + lookahead convolution so that
audio can be transcribed incrementally. This module is the TPU-idiomatic
engine for it: ONE jitted chunk function with static shapes, whose
carried state is an explicit pytree, giving output chunks numerically
equal to the offline ``DeepSpeech2.apply`` on the whole utterance
(inference mode; see tests/test_streaming.py).

Design (all lags are in post-conv frames; conv time stride is 2):

- **Conv frontend** (SAME-padded, non-causal): overlap-recompute. The
  state carries the last ``HIST=32`` raw feature frames; each chunk is
  processed as ``hist ++ chunk`` and only the ``K/2`` *interior* conv
  outputs — those whose receptive field (±16 raw frames) lies fully
  inside the window and in the past — are emitted. Net effect: the conv
  stage emits with a constant lag of ``CONV_LAG=8`` frames.
- **GRU stack**: exact state — the hidden carry of every layer crosses
  chunks through the state pytree (``gru_scan(h0=..., return_final)``).
  Frames before stream start / after stream end are *mask-held* (the
  same masking the offline model uses for padding), so the carry is
  bit-consistent with offline h0=0 at the first real frame.
- **Lookahead conv** (context C, future-only): the state carries the
  last ``C-1`` RNN outputs; outputs are emitted with lag ``C-1`` once
  their future context exists. The stream tail is zero-padded exactly
  like the offline right-pad.
- **BN / head**: inference-mode batch norm is pointwise (running
  stats), so these stages are stateless.

Total latency: ``(CONV_LAG + C - 1)`` conv frames = ``2*(8 + C - 1)``
raw feature frames on top of the chunk size.

The engine is batched: B independent streams advance together — this is
how a TPU serves many live audio sessions (the batch dim keeps the MXU
fed), with per-stream lengths.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np

from .config import Config, ModelConfig
from .data import CharTokenizer
from .decode.beam import (NEG_INF, beam_finalize, beam_init,
                          beam_search_chunk)
from .models.conv import ConvFrontend
from .models.layers import MaskedBatchNorm, clipped_relu
from .models.rnn import gru_scan

HIST = 32  # raw-frame history for conv overlap-recompute (>= 2*lag)
CONV_LAG = 8  # conv-output frames withheld until their future context exists
_BIG = np.int32(2**30)


@flax.struct.dataclass
class StreamState:
    """Carried across process_chunk calls. All arrays are batched [B, ...]."""

    raw_hist: jnp.ndarray    # [B, HIST, F] last raw feature frames
    h: Tuple[jnp.ndarray, ...]  # per-layer GRU carries [B, H]
    la_buf: jnp.ndarray      # [B, C-1, H] lookahead context (C>1) or [B,0,H]
    emitted: jnp.ndarray     # scalar: conv frames handed to the RNN so far
    raw_len: jnp.ndarray     # [B] true raw-frame length (BIG until finish)
    # [B] global raw-frame index where each stream STARTS (0 = the
    # batch's time origin). Frames before it are masked exactly like
    # the pre-stream warmup, so a session that joins a running batch
    # mid-flight (serving/session.py) decodes identically to a stream
    # that had the batch to itself. Must be even (chunk-aligned) so the
    # conv stride-2 grid stays exact.
    raw_start: jnp.ndarray


def _conv_halfwidth_raw(cfg: ModelConfig) -> int:
    """Conv-frontend receptive-field half-width, in raw feature frames.

    Layer i's time kernel spans ±(k_i // 2) frames of its own input;
    scaled by the cumulative stride of the layers below, these sum to
    the raw-frame context each conv output needs on either side.
    """
    r, stride = 0, 1
    for (tk, _, ts, _) in cfg.conv_layers:
        r += (tk // 2) * stride
        stride *= ts
    return r


def _check_streamable(cfg: ModelConfig) -> None:
    if cfg.bidirectional:
        raise ValueError("streaming needs a unidirectional model "
                         "(ds2_streaming preset)")
    if cfg.rnn_type != "gru":
        raise ValueError("streaming engine covers GRU stacks")
    if cfg.time_stride != 2:
        raise ValueError("streaming engine assumes conv time stride 2")
    # The overlap-recompute window must cover the conv receptive field:
    # emitted outputs lag by CONV_LAG post-conv (= 2*CONV_LAG raw) frames
    # of future context, and reach HIST raw frames into the past. A config
    # with larger time kernels than the defaults would otherwise produce
    # silently wrong logits near chunk seams.
    r = _conv_halfwidth_raw(cfg)
    if 2 * CONV_LAG < r or HIST < 2 * CONV_LAG + r:
        raise ValueError(
            f"conv receptive field needs ±{r} raw frames, exceeding the "
            f"streaming window (CONV_LAG={CONV_LAG} -> {2 * CONV_LAG} "
            f"future, HIST={HIST} past; need 2*CONV_LAG >= {r} and "
            f"HIST >= {2 * CONV_LAG + r}); shrink conv time kernels or "
            "enlarge streaming.HIST/CONV_LAG")


class StreamingTranscriber:
    """Incremental transcription with exact offline equivalence.

    >>> st = StreamingTranscriber(cfg, params, batch_stats, tokenizer)
    >>> state = st.init_state(batch=1)
    >>> for chunk in feature_chunks:           # [B, chunk_frames, F]
    ...     state, logits, valid = st.process_chunk(state, chunk)
    >>> state, logits, valid = st.finish(state, raw_lens)
    """

    def __init__(self, cfg: Config, params, batch_stats,
                 tokenizer: Optional[CharTokenizer] = None,
                 chunk_frames: int = 64, quantize: str = ""):
        _check_streamable(cfg.model)
        if chunk_frames % 2 or chunk_frames < 2 * CONV_LAG * 2:
            raise ValueError("chunk_frames must be even and >= "
                             f"{4 * CONV_LAG}")
        self.cfg = cfg
        self.mcfg = cfg.model
        self.params = params
        self.batch_stats = batch_stats or {}
        self.tokenizer = tokenizer
        self.chunk_frames = chunk_frames
        self.num_features = cfg.features.num_features
        # Fused Pallas cell for the per-chunk recurrence where the
        # route names one for a carried state (the resolved impl is
        # pallas, a GRU, weights resident); otherwise the XLA scan.
        from .models.rnn import layer_scan_route

        self._use_pallas = layer_scan_route(
            cfg.model, carry=True).kernel is not None
        # Weight-only int8 PTQ for live serving: one-shot consumers
        # dequantize at chunk entry (fused into their matmuls); the
        # recurrent matrices stay int8 into the resident q-kernel when
        # the impl is pallas and H fits the 1-byte budget — the
        # per-chunk recurrent weight fetch is then the quantized bytes.
        self._quantized = False
        self._keep_q = None
        self.quantize_report = None
        if quantize:
            if quantize != "int8":
                raise ValueError(f"quantize={quantize!r}; only 'int8'")
            from .utils.quantize import keep_recurrent_q, quantize_params

            self.params, self.quantize_report = quantize_params(self.params)
            self._quantized = True
            # streaming=True: the carried-h0 q-kernel is resident-only,
            # so beyond-residency H dequantizes at chunk entry rather
            # than routing to the batch path's blocked-q kernel.
            self._keep_q = keep_recurrent_q(cfg.model, streaming=True)
        self._chunk_jit = jax.jit(self._chunk_fn)

    # -- state ----------------------------------------------------------
    def init_state(self, batch: int) -> StreamState:
        m = self.mcfg
        c = max(m.lookahead_context - 1, 0)
        return StreamState(
            raw_hist=jnp.zeros((batch, HIST, self.num_features),
                               jnp.float32),
            h=tuple(jnp.zeros((batch, m.rnn_hidden), jnp.float32)
                    for _ in range(m.rnn_layers)),
            la_buf=jnp.zeros((batch, c, m.rnn_hidden), jnp.float32),
            emitted=jnp.zeros((), jnp.int32) - CONV_LAG,
            raw_len=jnp.full((batch,), _BIG, jnp.int32),
            raw_start=jnp.zeros((batch,), jnp.int32),
        )

    # -- the jitted chunk function --------------------------------------
    def _chunk_fn(self, params, batch_stats, state: StreamState,
                  chunk: jnp.ndarray):
        """chunk [B, K, F] -> (state', logits [B, K/2, V], valid [B, K/2]).

        ``valid[b, i]`` marks logits rows that correspond to real
        (in-stream) post-conv frames; invalid rows are pre-stream warmup
        or post-stream flush and must be discarded by the caller.
        """
        m = self.mcfg
        dtype = jnp.dtype(m.dtype)
        if self._quantized:
            from .utils.quantize import dequantize_params

            params = dequantize_params(params, keep=self._keep_q)
        b, k, f = chunk.shape
        window = jnp.concatenate(
            [state.raw_hist, chunk.astype(jnp.float32)], axis=1)
        # Window raw frame w sits at global raw index g0 + w.
        g0 = 2 * (state.emitted + CONV_LAG) - HIST
        # Two-sided validity in raw-frame units: frames before stream
        # start (pre-stream history, or before a mid-flight session's
        # per-stream raw_start) and past the true length must be zeroed
        # between conv layers, exactly where the offline model sees
        # SAME-padding zeros / its padding mask.
        wlen = jnp.clip(state.raw_len - g0, 0, HIST + k)
        vstart = jnp.maximum(state.raw_start - g0, 0)
        conv_out, _ = ConvFrontend(m, name=None).apply(
            {"params": params["conv"],
             "batch_stats": batch_stats.get("conv", {})},
            window, wlen, False, valid_start=vstart)
        # Interior outputs only: [CONV_LAG, CONV_LAG + K/2) of the window.
        x = conv_out[:, CONV_LAG:CONV_LAG + k // 2]
        n_new = k // 2

        # Global post-conv frame indices of these outputs, and their
        # validity (inside the real stream: at or past each stream's
        # start, before its true length).
        out_len = -(-state.raw_len // 2)
        start_out = state.raw_start // 2
        gidx = state.emitted + jnp.arange(n_new, dtype=jnp.int32)
        valid = ((gidx[None, :] >= start_out[:, None])
                 & (gidx[None, :] < out_len[:, None]))
        vmask = valid.astype(jnp.float32)

        # RNN stack with carried per-layer state; invalid frames are
        # mask-held (same mechanism as offline padding).
        new_h: List[jnp.ndarray] = []
        for i in range(m.rnn_layers):
            p = params["rnn"][f"rnn{i}"]
            bs = batch_stats.get("rnn", {}).get(f"rnn{i}", {})
            if m.rnn_batch_norm:
                x = MaskedBatchNorm().apply(
                    {"params": p["bn"], "batch_stats": bs["bn"]},
                    x, vmask, False)
            xp = (jnp.dot(x.astype(dtype),
                          p["wx"]["kernel"].astype(dtype))
                  + p["wx"]["bias"].astype(dtype))
            dot_dtype = None if dtype == jnp.float32 else dtype
            dd_str = None if dot_dtype is None else str(dot_dtype)
            from .models.rnn import _is_qdict

            if _is_qdict(p["wh_fw"]):
                # int8 stayed in the tree (self._keep_q): resident
                # q-kernel with the carried state.
                from .ops.rnn_pallas import gru_scan_pallas_q
                from .utils.impl import interpret_default

                ys, hf = gru_scan_pallas_q(
                    xp, vmask, p["wh_fw"]["q"], p["wh_fw"]["scale"],
                    p["bh_fw"], False, interpret_default(), dd_str,
                    h0=state.h[i])
            elif self._use_pallas:
                from .ops.rnn_pallas import gru_scan_pallas_stream
                from .utils.impl import interpret_default

                ys, hf = gru_scan_pallas_stream(
                    xp, vmask, p["wh_fw"], p["bh_fw"], state.h[i],
                    interpret_default(), dd_str)
            else:
                ys, hf = gru_scan(xp, vmask, p["wh_fw"], p["bh_fw"],
                                  dot_dtype=dot_dtype, h0=state.h[i],
                                  return_final=True)
            new_h.append(hf)
            x = (ys * vmask[:, :, None]).astype(dtype)

        # Lookahead conv over [la_buf ++ x]; emits with lag C-1.
        ctx = m.lookahead_context
        la_buf = state.la_buf
        if ctx > 0:
            xin = jnp.concatenate([la_buf.astype(dtype), x], axis=1)
            w = params["lookahead"]["w"]
            kernel = w[:, None, :].astype(dtype)
            y = jax.lax.conv_general_dilated(
                xin, kernel, window_strides=(1,),
                padding=[(0, ctx - 1)],
                dimension_numbers=("NHC", "HIO", "NHC"),
                feature_group_count=x.shape[-1])
            y = y[:, :n_new]  # outputs for global idx gidx - (ctx-1)
            y = clipped_relu(y, m.relu_clip)
            la_buf = jnp.concatenate([la_buf, x.astype(jnp.float32)],
                                     axis=1)[:, n_new:]
            out_gidx = gidx - (ctx - 1)
            x = y
        else:
            out_gidx = gidx

        x = MaskedBatchNorm().apply(
            {"params": params["bn_out"],
             "batch_stats": batch_stats["bn_out"]},
            x, None, False)
        logits = (jnp.dot(x.astype(dtype),
                          params["head"]["kernel"].astype(dtype))
                  + params["head"]["bias"].astype(dtype))
        out_valid = ((out_gidx[None, :] >= start_out[:, None])
                     & (out_gidx[None, :] < out_len[:, None]))

        new_state = StreamState(
            raw_hist=window[:, -HIST:],
            h=tuple(new_h),
            la_buf=la_buf,
            emitted=state.emitted + n_new,
            raw_len=state.raw_len,
            raw_start=state.raw_start,
        )
        return new_state, logits.astype(jnp.float32), out_valid

    # -- public API -----------------------------------------------------
    def process_chunk(self, state: StreamState, chunk) -> Tuple[
            StreamState, jnp.ndarray, jnp.ndarray]:
        chunk = jnp.asarray(chunk, jnp.float32)
        if chunk.ndim == 2:
            chunk = chunk[None]
        if chunk.shape[1] != self.chunk_frames:
            raise ValueError(
                f"chunk must have {self.chunk_frames} frames, "
                f"got {chunk.shape[1]}; pad the final chunk and call "
                "finish() with the true lengths")
        return self._chunk_jit(self.params, self.batch_stats, state, chunk)

    def finish(self, state: StreamState, raw_lens, tail=None) -> Tuple[
            StreamState, jnp.ndarray, jnp.ndarray]:
        """Close the streams. ``raw_lens`` [B] are the true total
        raw-frame counts per stream (including ``tail``). ``tail`` is
        the final partial chunk ([B, <chunk_frames, F]) not yet sent —
        it is zero-padded here AFTER the true lengths are recorded, so
        padding can never pollute the recurrent state. Returns the tail
        (logits, valid) from the remaining chunks + flush."""
        raw_lens = jnp.asarray(raw_lens, jnp.int32)
        state = dataclasses.replace(state, raw_len=raw_lens)
        b = state.raw_hist.shape[0]
        outs, valids = [], []
        if tail is not None:
            tail = jnp.asarray(tail, jnp.float32)
            if tail.ndim == 2:
                tail = tail[None]
            pad = self.chunk_frames - tail.shape[1]
            if pad < 0:
                raise ValueError("tail longer than chunk_frames")
            if pad:
                tail = jnp.pad(tail, ((0, 0), (0, pad), (0, 0)))
            state, lo, va = self._chunk_jit(self.params, self.batch_stats,
                                            state, tail)
            outs.append(lo)
            valids.append(va)
        lag = CONV_LAG + max(self.mcfg.lookahead_context - 1, 0)
        n_flush = -(-(2 * lag) // self.chunk_frames) + 1
        zeros = jnp.zeros((b, self.chunk_frames, self.num_features),
                          jnp.float32)
        for _ in range(n_flush):
            state, lo, va = self._chunk_jit(self.params, self.batch_stats,
                                            state, zeros)
            outs.append(lo)
            valids.append(va)
        return state, jnp.concatenate(outs, 1), jnp.concatenate(valids, 1)

    # -- convenience: full-utterance streaming decode -------------------
    def transcribe(self, features, raw_lens=None
                   ) -> Tuple[np.ndarray, np.ndarray]:
        """Stream [B, T, F] through chunking; return (logits [B, T', V],
        out_lens [B]) equal to the offline forward (valid rows packed
        left). Mainly for tests and batch evaluation of the streaming
        engine."""
        features = np.asarray(features, np.float32)
        if features.ndim == 2:
            features = features[None]
        b, t, f = features.shape
        raw_lens = (np.full((b,), t, np.int64) if raw_lens is None
                    else np.asarray(raw_lens))
        # The chunk fn compiles per [B, chunk_frames, F]; B is the only
        # shape that varies across transcribe() calls. Pad it to the
        # power-of-two rung (data/infer_bucket.batch_rung) with
        # raw_len-0 dummy rows — masked from the first chunk, stripped
        # below — so ragged eval batches reuse one compiled executable.
        from .data.infer_bucket import batch_rung

        b_pad = batch_rung(b)
        if b_pad > b:
            features = np.concatenate(
                [features, np.zeros((b_pad - b, t, f), np.float32)])
            raw_lens = np.concatenate(
                [raw_lens, np.zeros((b_pad - b,), raw_lens.dtype)])
        k = self.chunk_frames
        n_full = t // k
        state = self.init_state(b_pad)
        # Lengths are known up front here, so record them immediately:
        # per-stream padding (features[b, raw_lens[b]:]) must be masked
        # out of the recurrence exactly like offline padding.
        state = dataclasses.replace(
            state, raw_len=jnp.asarray(raw_lens, jnp.int32))
        chunks_l, chunks_v = [], []
        for i in range(n_full):
            state, lo, va = self.process_chunk(
                state, features[:, i * k:(i + 1) * k])
            chunks_l.append(np.asarray(lo))
            chunks_v.append(np.asarray(va))
        tail = features[:, n_full * k:] if t % k else None
        state, lo, va = self.finish(state, raw_lens, tail=tail)
        chunks_l.append(np.asarray(lo))
        chunks_v.append(np.asarray(va))
        lo = np.concatenate(chunks_l, 1)
        va = np.concatenate(chunks_v, 1)
        out_lens = -(-raw_lens[:b] // 2)
        t_out = int(out_lens.max())
        out = np.zeros((b, t_out, lo.shape[-1]), np.float32)
        for i in range(b):
            rows = lo[i][va[i]]
            out[i, :rows.shape[0]] = rows
        return out, out_lens.astype(np.int64)

    def decode_incremental(self, state_prev_ids, logits, valid
                           ) -> Tuple[np.ndarray, List[str]]:
        """CTC greedy collapse across chunk boundaries.

        ``state_prev_ids`` [B] is the last emitted frame id per stream
        (init to blank=0). Returns (new prev_ids, list of new text per
        stream)."""
        if self.tokenizer is None:
            raise ValueError("decode_incremental needs a tokenizer")
        prev = np.asarray(state_prev_ids).copy()
        ids = np.asarray(jnp.argmax(logits, axis=-1))
        valid = np.asarray(valid)
        texts = []
        for b in range(ids.shape[0]):
            out = []
            for t in range(ids.shape[1]):
                if not valid[b, t]:
                    continue
                i = int(ids[b, t])
                if i != 0 and i != prev[b]:
                    out.append(i)
                prev[b] = i
            texts.append(self.tokenizer.decode(np.asarray(out, np.int64)))
        return prev, texts


class StreamingBeamDecoder:
    """CTC prefix beam search carried across streaming chunks.

    The offline on-device search (decode/beam.py) keeps its whole state
    as dense arrays, so streaming it is just carrying that state between
    chunks: scanning chunks through ``advance`` is bit-identical to one
    offline ``beam_search`` over the concatenated frames — including
    optional on-device char-LM fusion (the rolling LM context rides in
    the state). Pair with ``StreamingTranscriber.process_chunk``; the
    ``finish`` call matters — it flushes the conv/lookahead lag frames
    and applies per-stream lengths, exactly like the greedy path::

        st = StreamingTranscriber(cfg, params, stats, tok, chunk_frames=64)
        bd = StreamingBeamDecoder(beam_width=16, max_len=200,
                                  lm_table=table)          # table opt.
        state, bstate = st.init_state(batch=B), bd.init(batch=B)
        for chunk in feature_chunks:
            state, logits, valid = st.process_chunk(state, chunk)
            bstate = bd.advance(bstate, logits, valid)     # on device
        state, logits, valid = st.finish(state, raw_lens, tail=tail)
        bstate = bd.advance(bstate, logits, valid)         # lag flush
        prefixes, lens, scores = bd.result(bstate)         # best-first

    Greedy streaming (``decode_incremental``) remains the low-latency
    path; this one trades a beam's worth of compute for beam accuracy
    and LM fusion without ever leaving the device.
    """

    def __init__(self, beam_width: int = 16, max_len: int = 200,
                 prune_top_k: int = 40, blank_id: int = 0, lm_table=None,
                 merge_impl: str = "auto"):
        self.beam_width = beam_width
        self.max_len = max_len
        self.prune_top_k = prune_top_k
        self.blank_id = blank_id
        self.merge_impl = merge_impl
        # Dense tables become device arrays; a HashedFusionTable is
        # already a pytree of device arrays and passes through.
        self.lm_table = (jnp.asarray(lm_table)
                         if isinstance(lm_table, np.ndarray)
                         else lm_table)

    def init(self, batch: int):
        return beam_init(batch, self.beam_width, self.max_len)

    def advance(self, bstate, logits, valid):
        """Fold one chunk's (logits [B, Tc, V], valid [B, Tc]) into the
        beam state. Accepts raw logits; softmax happens here so callers
        can pass ``process_chunk`` output directly."""
        lp = jax.nn.log_softmax(jnp.asarray(logits, jnp.float32), axis=-1)
        return beam_search_chunk(
            bstate, lp, jnp.asarray(valid),
            prune_top_k=self.prune_top_k,
            blank_id=self.blank_id, lm_table=self.lm_table,
            merge_impl=self.merge_impl)

    def result(self, bstate):
        """(prefixes [B, W, Lmax], lens [B, W], scores [B, W]),
        best-first; scores include the LM bonus when fusing."""
        return beam_finalize(bstate)

    def stable_prefix(self, bstate, margin: float = 10.0
                      ) -> Tuple[np.ndarray, np.ndarray]:
        """Longest common prefix of the *plausible* beams, per stream.

        The serving-side "partial transcript": symbols every hypothesis
        within ``margin`` log-score of the best agrees on (the beam
        always carries W hypotheses however improbable, so an
        unweighted LCP would rarely commit anything). Returns
        (ids [B, Lmax] int32, lens [B] int32). The LCP can shrink
        between chunks if beams diverge — emit-on-grow callers should
        track their own high-water mark.
        """
        prefixes, lens, scores = (np.asarray(a)
                                  for a in beam_finalize(bstate))
        b, w, lmax = prefixes.shape
        out = np.zeros((b, lmax), np.int32)
        out_lens = np.zeros((b,), np.int32)
        for i in range(b):
            live = scores[i] > max(float(NEG_INF), scores[i, 0] - margin)
            if not live.any():
                continue
            ps = prefixes[i][live]
            ls = lens[i][live]
            n = int(ls.min())
            agree = (ps[:, :n] == ps[0:1, :n]).all(axis=0) if n else \
                np.zeros((0,), bool)
            stop = int(np.argmin(agree)) if not agree.all() else n
            out[i, :stop] = ps[0, :stop]
            out_lens[i] = stop
        return out, out_lens

    def reset_streams(self, bstate, reset_mask):
        """Re-init the beams of the selected streams (``reset_mask``
        [B] bool), leaving the others untouched.

        Segment endpointing (serve.py): at a silence-detected segment
        boundary the transcript buffer restarts for that stream while
        the acoustic state (conv history, RNN carries in
        ``StreamingTranscriber``) keeps flowing — matching the scope
        note that continuous audio needs a fresh beam per segment, not
        a fresh model."""
        batch = bstate.lens.shape[0]
        fresh = self.init(batch)
        m = jnp.asarray(reset_mask, bool)
        return jax.tree.map(
            lambda cur, ini: jnp.where(
                m.reshape((batch,) + (1,) * (cur.ndim - 1)), ini, cur),
            bstate, fresh)
