"""Inference entrypoint: load checkpoint, decode, report WER/CER.

The reference's ``infer`` CLI (SURVEY.md §2 component 20, §3.2) maps to:

- restore params (+ batch stats) from an orbax checkpoint;
- jit-compiled forward -> log-softmax on device;
- decode:
  * ``greedy``      — on-device argmax/collapse (decode/greedy.py);
  * ``beam``        — on-device prefix beam search; the n-best ids are
                      the only thing copied to host, where an optional
                      KenLM/ARPA word LM rescores them
                      (score + alpha*logP_lm + beta*|words|);
  * ``beam_fused``  — host beam search with per-word LM fusion, the
                      reference decoder's semantics (slow path / oracle);
  * ``beam_fused_device`` — on-device beam search with char-level LM
                      shallow fusion: the ARPA LM is compiled to a dense
                      backoff-resolved table gathered inside the scan
                      (decode/ngram.py dense_fusion_table) — the
                      TPU-native replacement for string-keyed host
                      fusion; exact for char LMs (Mandarin);
- WER/CER over the decoded set, one JSON line per utterance plus a
  summary line.

CLI: ``python -m deepspeech_tpu.infer --config=<preset>
--checkpoint-dir=... [--manifest=...] [--synthetic=N]
[--section.key=value ...]``
"""

from __future__ import annotations

import dataclasses
import functools
import json
import logging
from typing import Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import obs
from .config import Config
from .data import CharTokenizer, DataPipeline
from .data.infer_bucket import (ladder_shapes, plan_infer_buckets,
                                slice_to_plan, unbucket)
from .decode import (beam_search, greedy_decode, ids_to_texts, load_lm,
                     prefix_beam_search_host, rescore_nbest)
from .metrics import cer, wer
from .models import create_model
from .utils.cache import ShapeBucketCache
from .utils.logging import JsonlLogger

_log = logging.getLogger(__name__)


def restore_params(checkpoint_dir: str, average_last: int = 0
                   ) -> Tuple[Dict, Dict]:
    """Load {params, batch_stats} from the latest training checkpoint.

    Restores the raw pytree (no optimizer template needed — ``infer``
    never touches opt_state, SURVEY.md §5 checkpoint contract).
    ``average_last`` > 1 averages the params of that many most recent
    checkpoints (checkpoint.average_checkpoints), the standard ASR
    WER-smoothing trick.
    """
    if average_last > 1:
        from .checkpoint import average_checkpoints

        return average_checkpoints(checkpoint_dir, average_last)
    from .checkpoint import CheckpointManager

    mgr = CheckpointManager(checkpoint_dir)
    raw = mgr.restore()
    if raw is None:
        raise FileNotFoundError(
            f"no checkpoint found in {checkpoint_dir!r}")
    state = raw["state"]
    return state["params"], state.get("batch_stats", {})


def _words_from_char_times(spans):
    """[[char, s, e]] -> [[word, s, e]]: split on space chars, word
    span = first char's start to last char's end."""
    words, cur = [], None
    for ch, s, e in spans:
        if ch == " ":
            if cur:
                words.append(cur)
            cur = None
            continue
        if cur is None:
            cur = [ch, s, e]
        else:
            cur[0] += ch
            cur[2] = e
    if cur:
        words.append(cur)
    return words


class Inferencer:
    """Batched decoding of a dataset with a restored (or given) model."""

    def __init__(self, cfg: Config, tokenizer: CharTokenizer,
                 params=None, batch_stats=None, mesh=None,
                 quantize: str = ""):
        self.cfg = cfg
        self.tokenizer = tokenizer
        if cfg.decode.mode in ("rnnt_greedy", "rnnt_beam"):
            # Transducer checkpoints (train.objective="rnnt") decode
            # through the RNNT model; the CTC forward below is unused
            # (jit is lazy). No LM path exists for the transducer yet
            # — a configured LM would silently be ignored: fail loud.
            if cfg.decode.lm_path:
                raise ValueError(
                    f"decode.mode={cfg.decode.mode} has no LM fusion/"
                    f"rescoring path; unset decode.lm_path")
            from .models.transducer import create_rnnt_model

            self.model = create_rnnt_model(cfg.model, mesh=mesh)
        elif cfg.decode.mode == "lm_greedy":
            # Decoder-only checkpoints (train.objective="lm") transcribe
            # through a cache (decode/lm_greedy.py, built below once
            # the parameters are here); no LM path, no quantized one.
            if cfg.decode.lm_path or quantize:
                raise ValueError(
                    "decode.mode=lm_greedy has no LM fusion and no "
                    "int8 path; unset decode.lm_path / quantize")
            self.model = None
        else:
            self.model = create_model(cfg.model, mesh=mesh)
        if params is None:
            params, batch_stats = restore_params(cfg.train.checkpoint_dir)
        self.lm_greedy = None
        if cfg.decode.mode == "lm_greedy":
            from .decode.lm_greedy import LMGreedy

            self.lm_greedy = LMGreedy(cfg, params, batch_stats)
            self.model = self.lm_greedy.model
            params = self.lm_greedy.params  # held once, in bfloat16
        self.params = params
        self.batch_stats = batch_stats or {}
        # Weight-only int8 PTQ (utils/quantize.py): kernels live int8 in
        # HBM; the dequant runs inside the jitted forward and fuses into
        # the consuming matmuls. Offline decode modes only — the
        # streaming/sp engines thread raw param trees.
        if cfg.decode.timestamps and cfg.decode.mode not in (
                "greedy", "streaming", "rnnt_greedy"):
            raise ValueError(
                "decode.timestamps needs a unique alignment (CTC argmax "
                "or the transducer's emission frames) — greedy/"
                "streaming/rnnt_greedy modes only; beam hypotheses "
                f"don't carry one ({cfg.decode.mode!r})")
        self._quantized = False
        self._stream_quantize = ""
        # How many times THIS engine ran PTQ (0 or 1): quantization is
        # an init-time cost, never a per-request one — the
        # two-tier scenario (tests/test_quantize.py) reads this per replica. Streaming mode
        # defers to the StreamingTranscriber's own PTQ; that call is
        # counted here too (see _decode_streaming).
        self.quantize_calls = 0
        self.quantize_report = None
        if quantize and quantize != "int8":
            raise ValueError(f"quantize={quantize!r}; only 'int8'")
        if quantize and cfg.decode.mode == "streaming":
            # The streaming engine owns its own PTQ (dequant at chunk
            # entry, recurrent matrices int8 into the resident
            # q-kernel); thread the flag, keep this tree raw.
            self._stream_quantize = quantize
            quantize = ""
        if quantize:
            # Allowlist = exactly the modes with a dequantizing entry
            # (_forward, or _decode_rnnt's keep-aware dequant);
            # anything else (sp_*) threads raw param trees.
            offline_modes = ("greedy", "beam", "beam_fused",
                             "beam_fused_device", "rnnt_greedy",
                             "rnnt_beam")
            if cfg.decode.mode not in offline_modes:
                raise ValueError(
                    f"--quantize-weights is for the offline decode "
                    f"modes {offline_modes} and streaming; "
                    f"{cfg.decode.mode!r} threads full-precision params")
            from .utils.quantize import quantization_error, quantize_params

            qtree, report = quantize_params(self.params)
            _log.info(
                "int8 weight-only PTQ: %d leaves quantized, %d kept, "
                "%.1f MB -> %.1f MB, max rel err %.4f",
                report["quantized"], report["kept"],
                report["bytes_before"] / 1e6, report["bytes_after"] / 1e6,
                quantization_error(self.params, qtree))
            self.params = qtree
            self._quantized = True
            self.quantize_calls += 1
            self.quantize_report = report
        self.lm = load_lm(cfg.decode.lm_path) if cfg.decode.lm_path else None
        # C++ LM handle for the native fused decoder (None when the LM
        # came from another engine or the native lib is unavailable).
        from . import native as _native

        self._native_lm = None
        if isinstance(self.lm, _native.NativeNGram):
            self._native_lm = self.lm
        elif (cfg.decode.lm_path and cfg.decode.mode == "beam_fused"
              and cfg.decode.host_impl != "python"
              and _native.available()):
            try:
                self._native_lm = _native.NativeNGram(cfg.decode.lm_path)
            except (ValueError, RuntimeError):
                self._native_lm = None
        # Space-less vocab (Mandarin) => char-level LM: fusion closes a
        # "word" per character; rescoring space-joins chars for the LM.
        self._streamer = None  # built lazily for decode.mode=streaming
        self._last_nbest = None  # beam modes stash [(text, score)] here
        self._last_times = None  # greedy timestamp mode stashes spans
        self._last_word_times = None  # word aggregation (spaced vocabs)
        self._rnnt_variables = None  # rnnt decode tree, dequant cached
        self._sp_mesh = None  # built lazily for decode.mode=sp_greedy
        self._device_lm = None  # fusion table (dense/hashed), lazy
        self._space_id = None
        self._to_lm_text = None
        if " " in getattr(tokenizer, "chars", []):
            self._space_id = tokenizer.chars.index(" ") + 1
        else:
            self._to_lm_text = lambda t: " ".join(t)

        quantized = self._quantized
        # int8-kernel regime: the recurrent matrices skip the jit-entry
        # dequant and feed the fused q kernels int8 — per-step
        # recurrent HBM traffic is then the quantized bytes, VMEM-
        # resident when H fits the 1-byte budget and s8 blocked
        # streaming (in-VMEM dequant) above it. Elsewhere the dequant
        # stays at entry (storage/transfer win only).
        keep_q = None
        if quantized:
            from .utils.quantize import keep_recurrent_q

            keep_q = keep_recurrent_q(cfg.model)
        # Which regime this replica's recurrence runs in ("resident-q"
        # / "blocked-q" / "fp") — the two-tier scenario records it
        # per replica to attribute throughput to the kernel path.
        from .utils.quantize import kernel_regime

        self.kernel_regime = kernel_regime(
            cfg.model, quantized or bool(self._stream_quantize),
            streaming=cfg.decode.mode == "streaming")

        # Donate the feature buffers into the jitted forward: a batch's
        # features/feat_lens are consumed exactly once per decode, so
        # XLA may reuse their HBM for activations instead of holding
        # input and activations live together. CPU has no donation
        # (every call would just warn), so donate on accelerators only.
        # Callers re-running the forward on the SAME device arrays must
        # re-put them; numpy inputs are safe (fresh transfer per call).
        donate = () if jax.default_backend() == "cpu" else (2, 3)

        @functools.partial(jax.jit, donate_argnums=donate)
        def forward(params, batch_stats, features, feat_lens):
            if quantized:
                from .utils.quantize import dequantize_params

                params = dequantize_params(params, keep=keep_q)
            logits, lens = self.model.apply(
                {"params": params, "batch_stats": batch_stats},
                features, feat_lens, train=False)
            lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            return lp, lens

        self._forward = forward
        # Per-rung executables installed from the warm store
        # (serving/warmstore.py): decode_batch consults this before
        # the jit, so a preloaded rung serves with ZERO trace/compile
        # work — the zero-compile-restart path. Keys are (B, T).
        self.preloaded_forwards: Dict[tuple, callable] = {}
        # Compiled-shape ledger, bounded by the planner's (B, T) ladder:
        # jit memoizes per shape, this makes the count (and the padding
        # volume) visible and warns when callers bypass the planner.
        self.shape_cache = ShapeBucketCache(max_shapes=len(ladder_shapes(
            cfg.data.bucket_frames, cfg.data.batch_size)))

    # -- decode paths ------------------------------------------------------

    def decode_batch_nbest(self, batch: Dict[str, np.ndarray]
                           ) -> List[List[tuple]]:
        """Per-utterance n-best [(text, score)] lists, best first,
        ``decode.nbest`` deep — the reference decoder's n-best surface.
        Beam modes return real beam scores (LM-rescored when an LM is
        loaded); greedy/streaming modes have a single hypothesis and
        return it with score 0.0."""
        self._last_nbest = None
        texts = self.decode_batch(batch)
        if self._last_nbest is None:
            return [[(t, 0.0)] for t in texts]
        return self._last_nbest

    def decode_batch(self, batch: Dict[str, np.ndarray]) -> List[str]:
        if self.cfg.decode.mode == "streaming":
            return self._decode_streaming(batch)
        if self.cfg.decode.mode == "sp_greedy":
            return self._decode_sp(batch)
        if self.cfg.decode.mode == "sp_beam":
            return self._decode_sp_beam(batch)
        if self.cfg.decode.mode in ("rnnt_greedy", "rnnt_beam"):
            return self._decode_rnnt(batch)
        if self.cfg.decode.mode == "lm_greedy":
            return self._decode_lm(batch)
        b, t = batch["features"].shape[:2]
        hit = self.shape_cache.note(
            b, t, int(np.minimum(np.asarray(batch["feat_lens"]), t).sum()))
        # A warm-store executable for this exact rung beats the jit:
        # same computation, zero trace/compile on first touch.
        fwd = self.preloaded_forwards.get((int(b), int(t)),
                                          self._forward)
        with obs.span("infer.forward", rung=f"{b}x{t}", cached=hit):
            lp, lens = fwd(self.params, self.batch_stats,
                           jnp.asarray(batch["features"]),
                           jnp.asarray(batch["feat_lens"]))
            if obs.tracer.enabled:
                # Trace mode: land the jitted forward in this span
                # (see train.fit) so decode below times host work only.
                jax.block_until_ready(lp)
        mode = self.cfg.decode.mode
        with obs.span("infer.decode", mode=mode):
            if mode == "greedy":
                if self.cfg.decode.timestamps:
                    return self._greedy_with_times(
                        jnp.argmax(lp, axis=-1), lens)
                ids, out_lens = greedy_decode(lp, lens)
                return ids_to_texts(ids, out_lens, self.tokenizer)
            if mode == "beam":
                return self._decode_beam(lp, lens)
            if mode == "beam_fused":
                return self._decode_beam_fused(lp, lens)
            if mode == "beam_fused_device":
                return self._decode_beam(lp, lens,
                                         lm_table=self._lm_table())
            raise ValueError(f"unknown decode mode {mode!r}")

    def decode_batch_bucketed(self, batch: Dict[str, np.ndarray],
                              plans=None) -> List[str]:
        """Ladder-bucketed decode of one mixed-length host batch.

        Plans the rows onto the (B, T) shape ladder
        (data/infer_bucket.plan_infer_buckets), decodes each plan's
        static-shaped sub-batch through ``decode_batch``, and
        reassembles texts — plus the n-best / timestamp stashes — in
        request order. Output-identical to decoding the full padded
        batch (the conv mask + feat_lens keeps valid frames blind to
        pad length; tests/test_infer.py proves bit-identity) while
        short utterances stop paying longest-utterance FLOPs and the
        compile count stays bounded by the ladder.

        ``plans`` lets a caller that already shaped the batch — the
        serving gateway's micro-batcher emits one pre-shaped plan per
        dispatch — skip the planner while reusing the slicing, decode,
        and stash-reassembly machinery.
        """
        lens = np.asarray(batch["feat_lens"])
        if plans is None:
            plans = plan_infer_buckets(lens, self.cfg.data.bucket_frames,
                                       self.cfg.data.batch_size)
        texts, nbest, times, wtimes = [], [], [], []
        for plan in plans:
            self._last_nbest = None
            self._last_times = None
            self._last_word_times = None
            texts.append(self.decode_batch(slice_to_plan(batch, plan)))
            nbest.append(self._last_nbest)
            times.append(self._last_times)
            wtimes.append(self._last_word_times)

        def _gather(per_plan):
            if any(x is None for x in per_plan):
                return None
            return unbucket(plans, per_plan)

        out = unbucket(plans, texts)
        self._last_nbest = _gather(nbest)
        self._last_times = _gather(times)
        self._last_word_times = _gather(wtimes)
        return out

    # -- AOT / warm-store surface ------------------------------------------

    def ladder(self) -> List[tuple]:
        """This engine's full ``(B, T)`` rung ladder — the shape set
        the warm store keys executables by."""
        return ladder_shapes(self.cfg.data.bucket_frames,
                             self.cfg.data.batch_size)

    def forward_arg_shapes(self, b: int, t: int) -> tuple:
        """ShapeDtypeStruct trees for one rung's forward call — the
        abstract arguments both ``compile_rung`` and the offline AOT
        tools lower against."""

        def _sds(x):
            a = x if hasattr(x, "dtype") else np.asarray(x)
            return jax.ShapeDtypeStruct(np.shape(a), a.dtype)

        return (jax.tree.map(_sds, self.params),
                jax.tree.map(_sds, self.batch_stats),
                jax.ShapeDtypeStruct(
                    (int(b), int(t), self.cfg.features.num_features),
                    np.float32),
                jax.ShapeDtypeStruct((int(b),), np.int32))

    def compile_rung(self, b: int, t: int):
        """Lower + compile the offline forward for one rung — the AOT
        leg the warm store serializes (``serving/warmstore.py`` export
        hook; same ``lower().compile()`` path as ``tools/aot_infer``).
        """
        p, s, feats, lens = self.forward_arg_shapes(b, t)
        return self._forward.lower(p, s, feats, lens).compile()

    def forward_signature(self) -> str:
        """Hash of the forward's weight-side calling convention
        (params + batch_stats structure/shapes/dtypes): store entries
        whose ``sig`` differs are rejected rather than called."""
        from .utils.aotstore import tree_signature

        return tree_signature((self.params, self.batch_stats))

    def _decode_streaming(self, batch: Dict[str, np.ndarray]) -> List[str]:
        """Greedy decode through the chunked streaming engine — the
        live-serving path (SURVEY §2 component 7) exercised over a
        dataset: results must equal offline greedy for streamable
        configs (lookahead variant), proven by tests/test_streaming.py."""
        if self._streamer is None:
            from .streaming import StreamingTranscriber

            self._streamer = StreamingTranscriber(
                self.cfg, self.params, self.batch_stats, self.tokenizer,
                chunk_frames=self.cfg.decode.chunk_frames,
                quantize=self._stream_quantize)
            if self._stream_quantize:
                # Don't pin the raw tree alongside the quantized one —
                # the streamer's (int8) tree is the serving copy now.
                self.params = self._streamer.params
                self._quantized = True
                self.quantize_calls += 1
                self.quantize_report = self._streamer.quantize_report
        logits, lens = self._streamer.transcribe(batch["features"],
                                                 batch["feat_lens"])
        if self.cfg.decode.timestamps:
            return self._greedy_with_times(
                jnp.argmax(jnp.asarray(logits), axis=-1),
                jnp.asarray(lens))
        ids, out_lens = greedy_decode(jnp.asarray(logits),
                                      jnp.asarray(lens))
        return ids_to_texts(ids, out_lens, self.tokenizer)

    def _greedy_with_times(self, best, lens) -> List[str]:
        """CTC-collapse with argmax-alignment character spans
        (decode.timestamps): stashes per-utt [[char, start_ms, end_ms]]
        for the utt JSONL / API and returns the texts."""
        from .decode.greedy import collapse_ids_with_times

        ids, out_lens, start, end = collapse_ids_with_times(
            jnp.asarray(best, jnp.int32), lens)
        texts = ids_to_texts(ids, out_lens, self.tokenizer)
        ids, out_lens = np.asarray(ids), np.asarray(out_lens)
        start, end = np.asarray(start), np.asarray(end)
        self._stash_char_times([
            [(ids[b, k], int(start[b, k]), int(end[b, k]) + 1)
             for k in range(out_lens[b])]
            for b in range(ids.shape[0])])
        return texts

    def _stash_char_times(self, per_utt) -> None:
        """Shared timestamp policy for every aligned decode (CTC argmax
        spans AND transducer emission frames): ``per_utt`` holds
        [(symbol_id, start_frame, end_frame_exclusive)] lists in
        post-conv frames. One post-conv frame = time_stride raw frames
        of stride_ms. Span labels decode PER SYMBOL (not by slicing
        the joined text): a vocab token longer than one char would
        desynchronize text positions from frame spans. Word spans
        aggregate on spaces for spaced vocabularies (spaceless zh has
        char == word)."""
        ms = (self.cfg.model.time_stride * self.cfg.features.stride_ms)
        self._last_times = [
            [[self.tokenizer.decode([k]), float(s * ms), float(e * ms)]
             for k, s, e in spans]
            for spans in per_utt]
        self._last_word_times = None
        if self._space_id is not None:
            self._last_word_times = [
                _words_from_char_times(spans) for spans in self._last_times]

    def _decode_rnnt(self, batch: Dict[str, np.ndarray]) -> List[str]:
        """Greedy or beam transducer decode of an RNN-T checkpoint
        (train.objective='rnnt'; models/transducer.py)."""
        from .models.transducer import (rnnt_beam_decode,
                                        rnnt_greedy_decode)

        if self._rnnt_variables is None:
            params = self.params
            if self._quantized:
                # One-shot consumers (conv/wx/head/pred/joint kernels)
                # dequantize ONCE per Inferencer (the rnnt applies run
                # un-jitted, so unlike the CTC forward the converts
                # can't fuse per step); the encoder's recurrent
                # matrices stay int8 into the resident q-kernels when
                # the regime holds (models/rnn handles the kept
                # qdicts, same as CTC decode).
                from .utils.quantize import (dequantize_params,
                                             keep_recurrent_q)

                params = dequantize_params(
                    params, keep=keep_recurrent_q(self.cfg.model))
            self._rnnt_variables = {"params": params,
                                    "batch_stats": self.batch_stats}
        variables = self._rnnt_variables
        feats = jnp.asarray(batch["features"])
        lens = jnp.asarray(batch["feat_lens"])
        if self.cfg.decode.mode == "rnnt_beam":
            nbest = rnnt_beam_decode(
                self.model, variables, feats, lens,
                beam_width=self.cfg.decode.beam_width,
                max_label_len=self.cfg.data.max_label_len,
                return_nbest=True)
            k = self.cfg.decode.nbest
            self._last_nbest = [
                [(self.tokenizer.decode(p), s) for p, s in row[:k]]
                for row in nbest]
            return [row[0][0] if row else ""
                    for row in self._last_nbest]
        else:
            want_times = self.cfg.decode.timestamps
            res = rnnt_greedy_decode(
                self.model, variables, feats, lens,
                max_label_len=self.cfg.data.max_label_len,
                return_times=want_times)
            if want_times:
                hyp_ids, frames = res
                # A transducer emission instant is one encoder frame:
                # span [t, t+1).
                self._stash_char_times([
                    [(k, t, t + 1) for k, t in zip(ids, fs)]
                    for ids, fs in zip(hyp_ids, frames)])
            else:
                hyp_ids = res
        return [self.tokenizer.decode(ids) for ids in hyp_ids]

    def _decode_lm(self, batch: Dict[str, np.ndarray]) -> List[str]:
        """Greedy transcripts of a decoder-only checkpoint: prefill,
        then the on-device loop (``decode/lm_greedy.py``). The batch
        may carry ``max_tokens [B]``, each stream's own limit."""
        out = self.lm_greedy.transcribe(
            batch["features"], batch["feat_lens"],
            max_tokens=batch.get("max_tokens"))
        return [self.tokenizer.decode(row[:n])
                for row, n in zip(out["ids"], out["tokens"])]

    def _sp_setup(self, batch: Dict[str, np.ndarray]):
        """Shared sp_* decode prep: all-device mesh (the data axis is
        re-purposed as time) + features zero-padded to the shard
        multiple (padding frames are masked exactly like offline)."""
        from .parallel import make_mesh
        from .parallel.seqpar import sp_frame_multiple, sp_min_frames

        if jax.process_count() > 1:
            # shard_map over a global mesh would consume host-LOCAL
            # arrays per process and fail confusingly (train.py has the
            # same guard for --train.sequence_parallel).
            raise ValueError(
                "sp_greedy/sp_beam decode is single-process: it shards "
                "one host's batch over local devices; run infer on one "
                "process (ADVICE r3 #5)")
        if self._sp_mesh is None:
            self._sp_mesh = make_mesh((0, 1))
        n_shards = int(self._sp_mesh.shape["data"])
        mult = sp_frame_multiple(self.cfg.model, n_shards)
        feats = np.asarray(batch["features"])
        t = feats.shape[1]
        # Shard-multiple alignment AND the conv-halo minimum: a short
        # utterance on many shards zero-pads up (masked, exact) rather
        # than tripping seqpar's halo guard.
        target = max(-(-t // mult) * mult,
                     sp_min_frames(self.cfg.model, n_shards))
        if target > t:
            feats = np.pad(feats, ((0, 0), (0, target - t), (0, 0)))
        return jnp.asarray(feats), self._sp_mesh

    def _decode_sp(self, batch: Dict[str, np.ndarray]) -> List[str]:
        """Greedy decode through the sequence-parallel engine
        (parallel/seqpar.py): the time axis shards over every device,
        so ONE long recording decodes with [T/n_devices] activations
        per chip — the offline-bidirectional complement of streaming.
        Equals offline greedy exactly (tests/test_seqpar.py)."""
        from .decode.greedy import collapse_ids
        from .parallel.seqpar import sp_greedy_decode

        feats, mesh = self._sp_setup(batch)
        ids, lens = sp_greedy_decode(
            self.cfg.model,
            {"params": self.params, "batch_stats": self.batch_stats},
            feats, jnp.asarray(batch["feat_lens"]), mesh)
        out, out_lens = collapse_ids(jnp.asarray(ids), jnp.asarray(lens))
        return ids_to_texts(out, out_lens, self.tokenizer)

    def _decode_beam(self, lp, lens, lm_table=None) -> List[str]:
        d = self.cfg.decode
        v = lp.shape[-1]
        prefixes, plens, scores = beam_search(
            lp, lens, beam_width=d.beam_width,
            prune_top_k=min(d.prune_top_k, v - 1),
            max_len=self.cfg.data.max_label_len, lm_table=lm_table,
            merge_impl=d.merge_impl)
        return self._nbest_texts(prefixes, plens, scores,
                                 lm_fused=lm_table is not None)

    def _decode_sp_beam(self, batch: Dict[str, np.ndarray]) -> List[str]:
        """Beam search through the sequence-parallel engine: the beam
        state relays shard-to-shard over time-sharded log-probs
        (parallel/seqpar.sp_beam_search) — exact long-audio beam
        decode, optionally with on-device LM fusion."""
        from .parallel.seqpar import sp_beam_search

        d = self.cfg.decode
        feats, mesh = self._sp_setup(batch)
        lm_table = self._lm_table() if d.lm_path else None
        prefixes, plens, scores = sp_beam_search(
            self.cfg.model,
            {"params": self.params, "batch_stats": self.batch_stats},
            feats, jnp.asarray(batch["feat_lens"]), mesh,
            beam_width=d.beam_width,
            prune_top_k=min(d.prune_top_k,
                            self.cfg.model.vocab_size - 1),
            max_len=self.cfg.data.max_label_len, lm_table=lm_table,
            merge_impl=d.merge_impl)
        return self._nbest_texts(prefixes, plens, scores,
                                 lm_fused=lm_table is not None)

    def _nbest_lists(self, prefixes, plens, scores,
                     lm_fused: bool) -> List[List[tuple]]:
        """Per-utterance [(text, score)] lists, best first, ``nbest``
        deep — the reference-decoder n-best surface. LM rescoring (when
        an LM is loaded and not already fused) reorders within the
        list."""
        d = self.cfg.decode
        prefixes = np.asarray(prefixes)
        plens = np.asarray(plens)
        scores = np.asarray(scores)
        out = []
        for b in range(prefixes.shape[0]):
            n = min(d.nbest, prefixes.shape[1])
            nbest = [(self.tokenizer.decode(prefixes[b, k, :plens[b, k]]),
                      float(scores[b, k])) for k in range(n)
                     if scores[b, k] > -1e29]
            # With on-device fusion the scores already include the LM;
            # rescoring would double-count it.
            if not lm_fused and self.lm is not None and nbest:
                nbest = rescore_nbest(nbest, self.lm, d.lm_alpha, d.lm_beta,
                                      to_lm_text=self._to_lm_text)
            out.append(nbest)
        self._last_nbest = out
        return out

    def _nbest_texts(self, prefixes, plens, scores,
                     lm_fused: bool) -> List[str]:
        return [nb[0][0] if nb else ""
                for nb in self._nbest_lists(prefixes, plens, scores,
                                            lm_fused)]

    def _lm_table(self):
        """Device-fusion table, built once per Inferencer.

        A dense [V^k, V] gather array or a hashed_lm.HashedFusionTable
        pytree, per decode.device_lm_impl (fusion_table_for picks under
        "auto"); both are accepted by beam_search's lm_table argument.
        The build walks the pure-Python reader's n-gram dicts, so the
        LM must be ARPA text.
        """
        if self._device_lm is None:
            d = self.cfg.decode
            if not d.lm_path:
                raise ValueError("beam_fused_device needs decode.lm_path")
            from .decode.ngram import NGramLM, fusion_table_for

            self._device_lm = fusion_table_for(
                self.lm if isinstance(self.lm, NGramLM) else d.lm_path,
                lambda i: self.tokenizer.decode([i]),
                self.cfg.model.vocab_size, d.lm_alpha, d.lm_beta,
                context_size=d.device_lm_context,
                vocab_has_space=self._space_id is not None,
                impl=d.device_lm_impl)
        return self._device_lm

    def _decode_beam_fused(self, lp, lens) -> List[str]:
        d = self.cfg.decode
        lens = np.asarray(lens)
        if self._use_native_fused():
            from . import native

            res = native.beam_search_batch_native(
                np.asarray(lp, np.float32), lens, beam_width=d.beam_width,
                prune_log_prob=d.prune_log_prob, lm=self._native_lm,
                lm_alpha=d.lm_alpha, lm_beta=d.lm_beta,
                space_id=self._space_id,
                id_to_char=lambda i: self.tokenizer.decode([i]),
                nbest=d.nbest)
            nbest = [[(self.tokenizer.decode(ids), float(score))
                      for ids, score in r[:d.nbest]] for r in res]
        else:
            lp64 = np.asarray(lp, np.float64)
            nbest = []
            for b in range(lp64.shape[0]):
                beams = prefix_beam_search_host(
                    lp64[b, :lens[b]], beam_width=d.beam_width,
                    prune_log_prob=d.prune_log_prob,
                    lm=self.lm, lm_alpha=d.lm_alpha, lm_beta=d.lm_beta,
                    space_id=self._space_id,
                    id_to_char=lambda i: self.tokenizer.decode([i]))
                nbest.append([(self.tokenizer.decode(ids), float(score))
                              for ids, score in beams[:d.nbest]])
        # Scores already include the fused LM — no rescoring pass.
        self._last_nbest = nbest
        return [nb[0][0] if nb else "" for nb in nbest]

    def _use_native_fused(self) -> bool:
        """C++ batch decoder for beam_fused (decode.host_impl policy).

        Fusion inside the C++ search needs the C++ LM engine; when an LM
        is configured but only loadable by another engine (e.g. a KenLM
        binary via the kenlm package), fused decode stays in Python.
        """
        impl = self.cfg.decode.host_impl
        if impl == "python":
            return False
        from . import native

        ok = native.available() and (
            self.lm is None or self._native_lm is not None)
        if impl == "native" and not ok:
            raise RuntimeError(
                f"decode.host_impl=native but: {native.build_error() or 'LM not loadable by the native engine'}")
        return ok

    # -- dataset loop ------------------------------------------------------

    def run(self, batches: Iterable[Tuple[Dict, int]],
            logger: Optional[JsonlLogger] = None,
            refs_of=None) -> Dict[str, float]:
        """Decode ``(batch, n_valid)`` pairs; report WER/CER vs labels.

        ``refs_of(batch, n_valid)`` may override reference transcripts;
        by default they come from the padded label ids.
        """
        refs: List[str] = []
        hyps: List[str] = []
        # Offline forward modes: double-buffer the feature transfer so
        # batch k+1 rides the wire while batch k decodes. Labels stay
        # host-side (the WER loop reads them with numpy), and the other
        # modes (streaming/sp/rnnt) pull features back to numpy anyway.
        if self.cfg.decode.mode in ("greedy", "beam", "beam_fused",
                                    "beam_fused_device", "lm_greedy"):
            from .data.pipeline import device_prefetch

            def _put(item):
                b, n_valid = item
                out = dict(b)
                out["features"] = jax.device_put(b["features"])
                out["feat_lens"] = jax.device_put(b["feat_lens"])
                return out, n_valid

            batches = device_prefetch(batches, put_fn=_put)
        for batch, n_valid in batches:
            self._last_nbest = None
            self._last_times = None
            self._last_word_times = None
            with obs.span("infer.batch", n_valid=n_valid):
                texts = self.decode_batch(batch)[:n_valid]
            # Beam modes with decode.nbest > 1: emit the alternatives
            # (with scores) alongside each top-1 hypothesis.
            nbest = (self._last_nbest[:n_valid]
                     if self._last_nbest is not None
                     and self.cfg.decode.nbest > 1 else None)
            times = (self._last_times[:n_valid]
                     if self._last_times is not None else None)
            word_times = (self._last_word_times[:n_valid]
                          if self._last_word_times is not None else None)
            if refs_of is not None:
                batch_refs = refs_of(batch, n_valid)
            else:
                batch_refs = [
                    self.tokenizer.decode(row[:n]) for row, n in
                    list(zip(batch["labels"], batch["label_lens"]))[:n_valid]]
            for i, (r, h) in enumerate(zip(batch_refs, texts)):
                if logger is not None:
                    extra = {"nbest": nbest[i]} if nbest else {}
                    if times is not None:
                        extra["times"] = times[i]
                    if word_times is not None:
                        extra["word_times"] = word_times[i]
                    logger.log("utt", ref=r, hyp=h, **extra)
            refs.extend(batch_refs)
            hyps.extend(texts)
        summary = {"wer": wer(refs, hyps), "cer": cer(refs, hyps),
                   "n_utts": len(refs)}
        if logger is not None:
            logger.log("infer_summary", **summary)
        return summary


def main(argv=None) -> None:
    import argparse

    from .config import (apply_overrides, get_config,
                     parse_cli_overrides)

    parser = argparse.ArgumentParser(prog="deepspeech_tpu.infer")
    parser.add_argument("--config", default="ds2_small")
    parser.add_argument("--checkpoint-dir", default="")
    parser.add_argument("--manifest", default="",
                        help="eval manifest (defaults to cfg.data.eval_manifest)")
    parser.add_argument("--vocab", default="", help="tokenizer vocab file")
    parser.add_argument("--synthetic", type=int, default=0,
                        help="decode N synthetic utterances (smoke test)")
    parser.add_argument("--average-last", type=int, default=0,
                        help="average the params of the last K saved "
                             "checkpoints before decoding (ASR "
                             "WER-smoothing trick); 0/1 = latest only")
    parser.add_argument("--quantize-weights", default="",
                        choices=["", "int8"],
                        help="weight-only post-training quantization: "
                             "kernels live int8 in HBM (per-output-"
                             "channel scales), dequant fuses into the "
                             "jitted forward. Offline decode modes only")
    parser.add_argument("--log-file", default="")
    args, extra = parser.parse_known_args(argv)
    cfg = apply_overrides(get_config(args.config),
                          parse_cli_overrides(extra))
    if args.checkpoint_dir:
        cfg = dataclasses.replace(
            cfg, train=dataclasses.replace(
                cfg.train, checkpoint_dir=args.checkpoint_dir))

    from .utils.cache import enable_compilation_cache

    enable_compilation_cache()
    logger = JsonlLogger(args.log_file or None)
    from .data.tokenizer import resolve_tokenizer

    if args.synthetic:
        from .train import _SyntheticPipeline

        tokenizer, cfg = resolve_tokenizer(cfg, synthetic=True,
                                           vocab_override=args.vocab)
        pipe = _SyntheticPipeline(cfg, args.synthetic)
        batches = pipe.eval_epoch()
    else:
        manifest = args.manifest or cfg.data.eval_manifest
        if not manifest:
            raise SystemExit("need --manifest, --synthetic, or "
                             "data.eval_manifest")
        from .data import load_manifest

        utts = load_manifest(manifest, cfg.data.min_duration_s,
                             cfg.data.max_duration_s)
        # A zh tokenizer is recovered from <checkpoint_dir>/vocab.txt
        # (written at training); deriving from eval transcripts would
        # permute the id->char map (resolve_tokenizer handles the
        # precedence).
        tokenizer, cfg = resolve_tokenizer(cfg, utterances=utts,
                                           vocab_override=args.vocab)
        pipe = DataPipeline(cfg, tokenizer, utterances=utts)
        batches = pipe.eval_epoch()
    # restore_params handles every average_last value (<=1 = latest),
    # so no dispatch here; Inferencer skips its internal restore.
    params, batch_stats = restore_params(cfg.train.checkpoint_dir,
                                         args.average_last)
    inf = Inferencer(cfg, tokenizer, params, batch_stats,
                     quantize=args.quantize_weights)
    summary = inf.run(batches, logger)
    print(json.dumps({"event": "done", **summary}))


if __name__ == "__main__":
    main()
