"""Greedy transcription with a decoder-only recogniser through a cache
(``decode.mode="lm_greedy"``; ``Inferencer`` owns one ``LMGreedy``).

A call transcribes a batch of utterances in two compiled programs:

``prefill``  ``decode.lm_prefill_rows`` utterances at a time, the audio
             prefix alone through the layers' sequence form; each
             layer's rows land in the call's cache (donated, written in
             place at the sub-batch's rows);
``decode``   ONE on-device loop over all streams: a step embeds every
             stream's input token at its next position, runs the
             layers' decode form against the cache and takes the
             argmax, which is the next step's input without a host
             round trip. A stream stops at the end id (0), unless
             its next input is forced, or at its ``max_tokens``; the
             loop ends when every stream has. The
             ids come back to the host once a call.

The step takes its input tokens through the argument ``forced [B, T]``
(-1: the stream's own argmax), and gives out, for the few streams named
in ``watch``, every step's logits, last-layer router scores and chosen
experts: the very executable that serves runs forced tokens for a
check against a reference. Weights are held in the model's compute
dtype (cast once, here). The cache is one array a layer, ``[streams,
cache_rows, C]``, allocated at the first call of a batch size and
reused.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..config import Config
from ..models.lfm2 import (cached_kinds, create_lfm2_model,
                           seq_positions)

WATCH = 8     # streams whose per-step logits the programs give out


def _watched(mid: dict, rows, layers: List[str]) -> dict:
    """Of a pass's sown router outputs, the ``rows`` of the batch: the
    last expert layer's scores and combine weights and every expert
    layer's chosen sets."""
    if not layers:
        return {}

    def of(name, key):
        x = mid[name]["moe"][key][0]
        return x.reshape((-1, rows[1]) + x.shape[1:])[rows[0]]

    return {"scores": of(layers[-1], "scores"),
            "weights": of(layers[-1], "weights"),
            "chosen": jnp.stack([of(n, "experts") for n in layers])}


class LMGreedy:
    def __init__(self, cfg: Config, params, buffers=None):
        m = cfg.model
        if not cached_kinds(m):
            missing = sorted({"conv": "a 2-position convolution state",
                              "full_attention": "a grouped-query "
                              "key/value cache"}.get(k, k)
                             for k in set(m.lfm_layer_types)
                             if k != "latent_attention")
            raise NotImplementedError(
                "decode.mode='lm_greedy' needs a cache for every layer "
                f"kind; {cfg.name!r} lacks " + " and ".join(missing)
                + " (latent attention alone has its decode form)")
        self.cfg = cfg
        self.model = create_lfm2_model(m, cfg.data.max_label_len)
        dtype = jnp.dtype(m.dtype)
        # Held in the compute dtype: cast once, not at every use.
        self.params = jax.tree.map(
            lambda x: x if x.dtype == dtype else x.astype(dtype), params)
        self.buffers = buffers or {}
        self.steps_max = cfg.data.max_label_len + 1
        self.sparse = [f"layer{i}" for i in range(len(m.lfm_layer_types))
                       if i >= m.lfm_dense_layers]
        self._cache = None
        self._calls = 0
        self.last_call: Optional[dict] = None
        donate = () if jax.default_backend() == "cpu" else (2,)
        self.prefill = jax.jit(self._prefill, donate_argnums=donate)
        self.decode = jax.jit(self._decode, donate_argnums=donate)

    # -- the two programs ----------------------------------------------

    def _prefill(self, params, buffers, cache, features, feat_lens, offset):
        """Sub-batch ``offset .. offset + rows`` of the call's batch."""
        rows = min(self.cfg.decode.lm_prefill_rows, features.shape[0])
        feats = jax.lax.dynamic_slice_in_dim(features, offset, rows)
        lens = jax.lax.dynamic_slice_in_dim(feat_lens, offset, rows)
        (new, a_lens, counters), state = self.model.apply(
            {"params": params, "buffers": buffers}, feats, lens,
            method="prefill", mutable=["intermediates"])
        cache = [jax.lax.dynamic_update_slice(c, r.astype(c.dtype),
                                              (offset, 0, 0))
                 for c, r in zip(cache, new)]
        a = new[0].shape[1]
        counters = dict(counters)
        counters["valid_positions"] = jnp.sum(a_lens)
        counters["padded_positions"] = rows * a - jnp.sum(a_lens)
        watch = _watched(state.get("intermediates", {}),
                         (slice(0, min(WATCH, rows)), a), self.sparse)
        return cache, a_lens, counters, watch

    def _decode(self, params, buffers, cache, a_lens, max_tokens, forced,
                watch, ignore_end):
        m = self.cfg.model
        b, t = forced.shape
        variables = {"params": params, "buffers": buffers}

        def step(tokens, pos, active, cache):
            return self.model.apply(
                variables, tokens, pos, active, cache, method="step",
                mutable=["intermediates"])

        def body(carry):
            j, tokens, done, out, cache, acc, seen = carry
            active = ~done
            (logits, cache, counters), state = step(
                tokens, a_lens + j, active, cache)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            out = jax.lax.dynamic_update_slice(
                out, jnp.where(active, nxt, 0)[:, None], (0, j))
            ahead = jax.lax.dynamic_slice_in_dim(
                forced, jnp.minimum(j + 1, t - 1), 1, axis=1)[:, 0]
            tokens = jnp.where(ahead >= 0, ahead, nxt)
            # The end id ends a stream whose next input is its own.
            done = done | (j + 1 >= max_tokens) \
                | (~ignore_end & (nxt == 0) & (ahead < 0))
            acc = dict(acc)
            acc["steps"] += 1
            acc["tokens"] += active
            acc["idle_slot_steps"] += jnp.sum(~active)
            acc["cache_rows_read"] += jnp.sum(
                jnp.where(active, a_lens + j + 1, 0))
            for k, v in counters.items():
                acc[k] = (jnp.maximum(acc[k], v)
                          if k in ("rows_high_water", "rows_capacity")
                          else acc[k] + v)
            if self.sparse:
                acc["experts_hit"] += jnp.sum(
                    counters["expert_pairs"] > 0, axis=-1)
            mid = _watched(state.get("intermediates", {}), (watch, 1),
                           self.sparse)
            mid["logits"] = logits[watch][:, None, :]
            seen = {k: jax.lax.dynamic_update_slice_in_dim(
                seen[k], mid[k], j, axis=seen[k].ndim - 2) for k in seen}
            return j + 1, tokens, done, out, cache, acc, seen

        def cond(carry):
            return (carry[0] < t) & ~jnp.all(carry[2])

        first = jnp.where(forced[:, 0] >= 0, forced[:, 0], 0)
        zeros = jax.eval_shape(
            lambda c: step(first, a_lens, max_tokens > 0, c)[0][2], cache)
        acc = {k: jnp.zeros(v.shape, v.dtype) for k, v in zeros.items()}
        acc.update(steps=jnp.int32(0), tokens=jnp.zeros(b, jnp.int32),
                   idle_slot_steps=jnp.int32(0),
                   cache_rows_read=jnp.int32(0))
        w = watch.shape[0]
        seen = {"logits": jnp.zeros((w, t, m.vocab_size), jnp.float32)}
        if self.sparse:
            acc["experts_hit"] = jnp.zeros(len(self.sparse), jnp.int32)
            seen["scores"] = jnp.zeros((w, t, m.lfm_experts), jnp.float32)
            seen["weights"] = jnp.zeros((w, t, m.lfm_top_k), jnp.float32)
            seen["chosen"] = jnp.zeros(
                (len(self.sparse), w, t, m.lfm_top_k), jnp.int32)
        carry = (jnp.int32(0), first, max_tokens <= 0,
                 jnp.zeros((b, t), jnp.int32), cache, acc, seen)
        _, _, _, out, cache, acc, seen = jax.lax.while_loop(
            cond, body, carry)
        return out, cache, acc, seen

    # -- a call --------------------------------------------------------------

    def cache_for(self, rows: int, frames: int) -> list:
        """The cache of ``rows`` streams whose prefix is ``frames``
        feature frames: ``model.lfm_seq_positions`` rows a stream, or
        (0) the least that hold the prefix and every step."""
        m = self.cfg.model
        positions = seq_positions(m, frames, self.cfg.data.max_label_len)
        shape = (rows, positions, m.mla_kv_rank + m.mla_rope_dim)
        if self._cache is None or self._cache[0].shape != shape:
            self._cache = None  # free the old one first
            self._cache = [jnp.zeros(shape, jnp.dtype(m.dtype))
                           for _ in m.lfm_layer_types]
            obs.registry().gauge("lm_cache_bytes", sum(
                c.nbytes for c in self._cache))
        cache, self._cache = self._cache, None
        return cache

    def transcribe(self, features, feat_lens, max_tokens=None,
                   forced=None, watch=None) -> Dict:
        """Token ids ``[B, T]`` (0 past a stream's end), how many each
        stream decoded, the call's counters and, for the ``watch``
        streams, the per-step outputs (still on the device)."""
        cfg, m = self.cfg, self.cfg.model
        features, feat_lens = jnp.asarray(features), jnp.asarray(feat_lens)
        b = features.shape[0]
        sub = min(cfg.decode.lm_prefill_rows, b)
        if b % sub:
            raise ValueError(f"{b} utterances are not whole prefill "
                             f"sub-batches of {sub}")
        t = self.steps_max
        if max_tokens is None:
            max_tokens = np.full(b, t, np.int32)
        max_tokens = jnp.minimum(jnp.asarray(max_tokens, jnp.int32), t)
        if forced is None:
            forced = np.full((b, t), -1, np.int32)
        if watch is None:
            watch = np.arange(min(WATCH, b), dtype=np.int32)
        self._calls += 1
        call = self._calls  # what the spans of one call share
        t0 = time.perf_counter()
        with obs.span("infer.transcribe", rows=b, call=call):
            with obs.span("infer.cache", call=call):
                cache = self.cache_for(b, features.shape[1])
            t_cache = time.perf_counter()
            a_lens, pre, pre_watch = [], [], None
            for i in range(b // sub):
                with obs.span("infer.prefill", rows=sub, call=call):
                    with obs.span("infer.prefill.dispatch", call=call):
                        cache, a, counters, mid = self.prefill(
                            self.params, self.buffers, cache, features,
                            feat_lens, i * sub)
                    if obs.tracer.enabled:
                        with obs.span("infer.prefill.wait", call=call):
                            jax.block_until_ready(cache)
                a_lens.append(a)
                pre.append(counters)
                pre_watch = mid if i == 0 else pre_watch
            t1 = time.perf_counter()
            with obs.span("infer.decode", rows=b, call=call):
                with obs.span("infer.decode.dispatch", call=call):
                    ids, cache, acc, seen = self.decode(
                        self.params, self.buffers, cache,
                        jnp.concatenate(a_lens), max_tokens,
                        jnp.asarray(forced, jnp.int32),
                        jnp.asarray(watch, jnp.int32),
                        jnp.asarray(cfg.decode.lm_ignore_end))
                t2 = time.perf_counter()
                with obs.span("infer.decode.fetch", call=call):
                    ids, acc, pre = jax.device_get((ids, acc, pre))
                t3 = time.perf_counter()
            self._cache = cache
        stats = obs.observe_lm_call(pre, acc, rows=b)
        # Host seconds, tracer on or off, so that a stalled call says
        # where: up to the prefill programs dispatched (``cache``, the
        # cache handed out or made, is its first part), then up to the
        # ids on the host (the device's whole call, where nothing blocks
        # before), of which ``decode_dispatch`` and ``fetch`` are the
        # decode program's call and the ``device_get``.
        stats["host_s"] = {"cache": t_cache - t0,
                           "prefill_dispatch": t1 - t0,
                           "decode_dispatch": t2 - t1,
                           "fetch": t3 - t2,
                           "to_ids": time.perf_counter() - t1}
        self.last_call = {"stats": stats, "prefill_watch": pre_watch,
                          "decode_watch": seen, "cache": cache}
        return {"ids": ids, "tokens": np.asarray(acc["tokens"]),
                "stats": stats}
