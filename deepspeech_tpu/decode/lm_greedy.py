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

With a draft module (``model.lm_draft_layers`` = 1: a
multi-token-prediction module, ``models/lfm2.DraftModule``) the loop
DRAFTS FOR ITSELF. A stream holds its last accepted token and a draft
of the next one; a step runs the model over both positions at once
(``verify``), takes the argmax at each, and if the first equals the
draft it emits both and moves two positions, else the first alone and
one (the rejected draft's cache rows are overwritten by the next step).
The module then runs over the same two positions with the tokens just
emitted, keeps its own cache (one more array after the layers'), and
its argmax at the last accepted position is the next draft; the first
draft comes from prefill, where the module follows the prefix. Every
stream has its own pointer, so streams finish after different numbers
of STEPS as well as of tokens. The emitted ids are those of the loop
without drafts: a draft changes how many steps a transcript takes, never
the transcript. A forced input is by definition the next input: it
takes the draft's place and is accepted.

The step takes its input tokens through the argument ``forced [B, T]``
(-1: the stream's own argmax), and gives out, for the few streams named
in ``watch``, every step's logits, last-layer router scores and chosen
experts: the very executable that serves runs forced tokens for a
check against a reference. Weights are held in the model's compute
dtype (cast once, here).

The cache is one entry a layer, of the layer's KIND, allocated at the
first call of a batch size and reused: latent attention's array
``[streams, cache_rows, C]``; grouped-query attention's pair of arrays
(keys, values), ``[streams, R, kv_heads, head]`` each, where a "full_attention" layer has R =
``cache_rows`` and a "sliding_attention" layer a RING of R =
``lfm_window`` rows (position p lives in slot ``p mod R``; fewer where
the cache rows are fewer: a ring that never wraps). Prefill writes a
stream's last ``min(a, R)`` prefix rows into a ring and all of them
into a full cache; a step writes its row in slot ``pos mod R`` and
attends to the rows the layer can reach; the call counts the rows
attended per kind, and the rows fetched to do so. A hybrid layer
("ssm_attention": a state-space mixer beside attention that sees all)
holds four arrays: keys and values as a "full_attention" layer, then
the mixer's RECURRENT STATE ``[streams, heads, state, head]`` in
float32 and the convolution's last inputs ``[streams, taps - 1,
channels]``. The last two are not rows by position: prefill writes what
a stream holds after its last prefix position, and a step updates the
state of every live stream where it lies (the loop's carry; on a TPU
the kernel ``ssd_state_step`` aliases it), so no second copy of it
ever exists. The call counts the live streams' steps and the bytes a
step needs by part (layer weights, head, state, rows in reach).
A "sparse_attention" layer holds three arrays: keys and values as a
"full_attention" layer but HEAD-MAJOR, ``[streams, kv_heads, R, head]``
(whole blocks of ``sparse_block`` rows, a head's block one contiguous
piece for the kernel that fetches blocks by index), and the POOLED keys
``[streams, windows, kv_heads, head]`` its queries rank the blocks by;
prefill writes the prefix's whole windows, a step whose row ends a
window writes that window's, and a step past ``sparse_dense_len``
rows reads the selected blocks only: the call counts the rows read
against the rows held, the pooled keys read and written. A
"linear_attention" layer holds its float32 state ``[streams, heads,
head, head]`` alone.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..config import Config
from ..models.lfm2 import (ATTENTION_KINDS, HYBRID, LINEAR, SPARSE,
                           attends_in_kernels, create_lfm2_model, head_dim,
                           pooled_rows, ring_positions, rows_selected,
                           seq_positions, uncached_kinds)
from ..ops import attn_pallas


# What a hybrid layer sows apart: its two branches in both forms, and
# in the steps its feed-forward too (the last layer's is dead code in
# prefill: it feeds nothing there).
BRANCHES = ("branch_mixer", "branch_attn")
STEP_BRANCHES = BRANCHES + ("branch_mlp",)


def _watched(mid: dict, rows, layers: List[str], mixed: str = "",
             gated=(), hybrid: str = "", branches=BRANCHES,
             selecting: str = "") -> dict:
    """Of a pass's sown outputs, the ``rows`` of the batch: the last
    expert layer's router scores and combine weights and every expert
    layer's chosen sets (a stack with expert layers); the
    feed-forward's hyper-connection coefficients of the layer
    ``mixed``, where the residual has streams; the gated attention
    output (before ``o``) of the layers ``gated`` ((layer, module)
    names); the ``branches`` of the hybrid layer ``hybrid``, each
    output apart; and the blocks the queries of the sparse layer
    ``selecting`` chose, ``[.., positions, kv_heads x blocks]``."""
    out = {}
    if layers:
        def of(name, key):
            x = mid[name]["moe"][key][0]
            return x.reshape((-1, rows[1]) + x.shape[1:])[rows[0]]

        out = {"scores": of(layers[-1], "scores"),
               "weights": of(layers[-1], "weights"),
               "chosen": jnp.stack([of(n, "experts") for n in layers])}
        for key in ("h_pre", "h_post", "h_res") if mixed else ():
            out[key] = mid[mixed]["ffn_hc"][key][0][rows[0]]
    for i, (name, module) in enumerate(gated):
        out[f"gated{i}"] = mid[name][module]["gated"][0][rows[0]]
    for key in branches if hybrid else ():
        out[key] = mid[hybrid][key][0][rows[0]]
    if selecting:
        sel = mid[selecting]["sparse"]["selected"][0]   # [B, kv, (Q,) NB]
        if sel.ndim == 3:                               # a step: one query
            sel = sel[:, :, None]
        sel = jnp.moveaxis(sel[rows[0]], 1, 2)          # [w, Q, kv, NB]
        out["selected"] = sel.reshape(sel.shape[:2] + (-1,))
    return out


def cache_bytes(cache) -> int:
    """Bytes of a cache, or of some layers' entries of one."""
    return sum(c.nbytes for c in jax.tree.leaves(cache))


class LMGreedy:
    def __init__(self, cfg: Config, params, buffers=None):
        m = cfg.model
        if uncached_kinds(m):
            missing = [{"conv": "a 2-position convolution state"}.get(k, k)
                       for k in uncached_kinds(m)]
            raise NotImplementedError(
                "decode.mode='lm_greedy' needs a cache for every layer "
                f"kind; {cfg.name!r} lacks " + " and ".join(missing)
                + " (latent attention, grouped-query attention, with or "
                "without a block selection, linear attention and the "
                "hybrid of a state-space mixer beside attention have "
                "their decode forms)")
        self.cfg = cfg
        self.model = create_lfm2_model(m, cfg.data.max_label_len)
        dtype = jnp.dtype(m.dtype)
        # Held in the compute dtype: cast once, not at every use.
        self.params = jax.tree.map(
            lambda x: x if x.dtype == dtype else x.astype(dtype), params)
        self.buffers = buffers or {}
        self.steps_max = cfg.data.max_label_len + 1
        if m.lm_draft_layers > 1:
            raise NotImplementedError(
                "the greedy loop drafts with one module; "
                f"{cfg.name!r} has lm_draft_layers={m.lm_draft_layers}")
        self.draft = m.lm_draft_layers == 1
        self.sparse = [f"layer{i}" for i in range(len(m.lfm_layer_types))
                       if i >= m.lfm_dense_layers]
        # The layer whose hyper-connection coefficients are given out.
        self.mixed = (f"layer{len(m.lfm_layer_types) - 1}"
                      if m.hc_streams > 1 else "")
        # Layers of each grouped-query kind (a hybrid layer's attention
        # sees all), and of each kind the last layer, whose gated
        # attention output is given out.
        sees = {"sliding_attention": ("sliding_attention",),
                "full_attention": ("full_attention", HYBRID)}
        self.kinds = {k: [i for i, t in enumerate(m.lfm_layer_types)
                          if t in sees[k]] for k in ATTENTION_KINDS}
        self.gated = [(f"layer{self.kinds[k][-1]}", "attn") for k in (
            "sliding_attention", "full_attention") if self.kinds[k]]
        # The layers whose queries read a selection of their cache's
        # blocks, and those that hold a state alone; of each the last
        # layer's gated output is given out too.
        self.selecting = [i for i, t in enumerate(m.lfm_layer_types)
                          if t == SPARSE]
        self.linear = [i for i, t in enumerate(m.lfm_layer_types)
                       if t == LINEAR]
        self.gated += [(f"layer{layers[-1]}", module) for layers, module in (
            (self.selecting, "sparse"), (self.linear, "lin")) if layers]
        # The layers whose caches hold a recurrent state (where in the
        # layer's arrays: after a hybrid layer's keys and values); of
        # the last hybrid layer the two branches' outputs are given out
        # apart.
        self.state_at = {i: 2 if t == HYBRID else 0
                         for i, t in enumerate(m.lfm_layer_types)
                         if t in (HYBRID, LINEAR)}
        self.stateful = sorted(self.state_at)
        hybrids = [i for i in self.stateful if self.state_at[i]]
        self.hybrid = f"layer{hybrids[-1]}" if hybrids else ""
        self.chooser = (f"layer{self.selecting[-1]}"
                        if self.selecting else "")
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
        (new, a_lens, counters, draft), state = self.model.apply(
            {"params": params, "buffers": buffers}, feats, lens,
            method="prefill", mutable=["intermediates"])
        with jax.named_scope("cache_update"):
            cache = jax.tree.map(
                lambda c, r: jax.lax.dynamic_update_slice(
                    c, self._rows_for(c, r, a_lens).astype(c.dtype),
                    (offset,) + (0,) * (c.ndim - 1)), cache, new)
        a = -(-features.shape[1] // self.cfg.model.frame_stack)
        counters = dict(counters)
        counters["valid_positions"] = jnp.sum(a_lens)
        counters["padded_positions"] = rows * a - jnp.sum(a_lens)
        watched = min(self.cfg.decode.lm_watch_rows, rows)
        watch = _watched(state.get("intermediates", {}),
                         (slice(0, watched), a), self.sparse, self.mixed,
                         self.gated, self.hybrid, selecting=self.chooser)
        if self.stateful:
            # What the last stateful layer holds after the prefix: the
            # steps then update it where it lies.
            last = self.stateful[-1]
            held = new[last][self.state_at[last]:]
            watch["state"] = held[0][:watched]
            if len(held) > 1:
                watch["conv"] = held[1][:watched]
        return cache, a_lens, counters, watch, draft

    @staticmethod
    def _rows_for(cache, rows, a_lens):
        """What prefill writes into one array ``cache`` of a layer's
        cache of a sub-batch's ``rows``: the rows themselves, or, into
        a ring shorter than the prefix, each slot's newest prefix
        position (a stream's last ``min(a, R)`` prefix rows; nothing
        where it has none)."""
        if cache.shape[1] >= rows.shape[1]:
            return rows
        held = ring_positions(a_lens - 1, cache.shape[1])
        held = held.reshape(held.shape + (1,) * (rows.ndim - 2))
        ring = jnp.take_along_axis(rows, jnp.maximum(held, 0), axis=1)
        return jnp.where(held >= 0, ring, 0)

    def _count(self, acc: dict, counters: dict) -> None:
        """A pass's expert-layer counters into the loop's."""
        for k, v in counters.items():
            acc[k] = (jnp.maximum(acc[k], v)
                      if k in ("rows_high_water", "rows_capacity")
                      else acc[k] + v)
        if self.sparse:
            acc["experts_hit"] += jnp.sum(
                counters["expert_pairs"] > 0, axis=-1)

    def _decode(self, params, buffers, cache, a_lens, max_tokens, forced,
                watch, ignore_end, draft=None):
        if self.draft:
            return self._decode_drafting(
                params, buffers, cache, a_lens, max_tokens, forced, watch,
                ignore_end, draft)
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
            with jax.named_scope("lm_head"):  # the argmax's passes
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
            if self.stateful:
                # (stream, layer) states a step read and wrote
                acc["state_updates"] += jnp.sum(active) * len(
                    self.stateful)
            reach = jnp.where(active, a_lens + j + 1, 0)
            if any(self.kinds.values()):
                # Rows attended, over the layers of each kind.
                for key, n in self._reach(reach).items():
                    acc[key] += n
                    acc["cache_rows_read"] += n
                # Rows the layers moved to attend to them.
                for key, n in self._fetched(a_lens + j, active,
                                            cache).items():
                    acc[key] += n
            if self.selecting:
                # Rows the selection read of the rows held, the pooled
                # keys it ranked them by (past ``sparse_dense_len``
                # rows) and those the step wrote, over the sparse
                # layers; the first three a stream, summed on the host:
                # a call's rows held pass 2^31.
                at, n = a_lens + j, len(self.selecting)
                read = n * jnp.where(active, rows_selected(m, at), 0)
                ranked = at >= m.sparse_kernel - 1
                past = at + 1 - m.sparse_kernel
                acc["select_rows_read"] += read
                acc["select_rows_held"] += n * reach
                acc["select_windows_read"] += n * jnp.where(
                    active & ranked & (at + 1 > m.sparse_dense_len),
                    past // m.sparse_stride + 1, 0)
                acc["pooled_key_writes"] += n * jnp.sum(
                    active & ranked & (past % m.sparse_stride == 0))
                acc["cache_rows_read"] += jnp.sum(read)
            if not self.selecting and not any(self.kinds.values()):
                acc["cache_rows_read"] += jnp.sum(reach)
            self._count(acc, counters)
            mid = _watched(state.get("intermediates", {}), (watch, 1),
                           self.sparse, gated=self.gated,
                           hybrid=self.hybrid, branches=STEP_BRANCHES,
                           selecting=self.chooser)
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
        if any(self.kinds.values()):
            acc.update({k: jnp.int32(0) for k in (
                "rows_attended_window", "rows_attended_global",
                "rows_fetched_window", "rows_fetched_global")})
            width = m.lfm_heads * head_dim(m)
            seen.update({f"gated{i}": jnp.zeros((w, t, width),
                                                jnp.dtype(m.dtype))
                         for i in range(len(self.gated))})
        if self.selecting or self.linear:
            seen.update({f"gated{i}": jnp.zeros(
                (w, t, m.lin_heads * m.lin_head_dim if module == "lin"
                 else m.lfm_heads * head_dim(m)), jnp.dtype(m.dtype))
                for i, (_, module) in enumerate(self.gated)})
        if self.selecting:
            acc.update({k: jnp.zeros(b, jnp.int32) for k in (
                "select_rows_read", "select_rows_held",
                "select_windows_read")},
                pooled_key_writes=jnp.int32(0))
            blocks = -(-cache[self.selecting[-1]][0].shape[2]
                       // m.sparse_block)
            seen["selected"] = jnp.zeros(
                (w, t, m.lfm_kv_heads * blocks), bool)
        if self.stateful:
            acc["state_updates"] = jnp.int32(0)
        if self.hybrid:
            seen.update({k: jnp.zeros((w, t, m.lfm_hidden),
                                      jnp.dtype(m.dtype))
                         for k in STEP_BRANCHES})
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
        if self.kinds["sliding_attention"]:
            # Streams whose position passed the window: their rings
            # have wrapped.
            acc["ring_wraps"] = jnp.sum(
                (max_tokens > 0)
                & (a_lens + acc["tokens"] > m.lfm_window))
        return out, cache, acc, seen

    def _reach(self, reach) -> dict:
        """Cache rows the streams' steps attend to, ``reach [B]`` each
        in a layer that sees everything (its own row among them): over
        the windowed layers, where a stream sees ``lfm_window`` at most,
        and over the global ones."""
        window = jnp.minimum(reach, self.cfg.model.lfm_window)
        return {"rows_attended_window": jnp.sum(window) * len(
                    self.kinds["sliding_attention"]),
                "rows_attended_global": jnp.sum(reach) * len(
                    self.kinds["full_attention"])}

    def _fetched(self, pos, active, cache) -> dict:
        """Cache rows (of keys; as many of values) a step's layers
        fetch, per kind: where the decode form is the kernel
        ``gqa_attn_decode`` (``models/lfm2.Attention``'s own choice)
        the rows of the tiles it visits for the active streams, else
        every row of every stream."""
        m = self.cfg.model
        kernel = attends_in_kernels(m)
        out = {}
        for kind, name, window in (
                ("sliding_attention", "window", m.lfm_window),
                ("full_attention", "global", 0)):
            layers = self.kinds[kind]
            if not layers:
                out["rows_fetched_" + name] = jnp.int32(0)
                continue
            rows = cache[layers[0]][0].shape[1]    # the kind's, every layer
            out["rows_fetched_" + name] = len(layers) * (
                attn_pallas.rows_fetched(pos, active, rows, window)
                if kernel else jnp.int32(pos.shape[0] * rows))
        return out

    def _decode_drafting(self, params, buffers, cache, a_lens, max_tokens,
                         forced, watch, ignore_end, draft):
        """The loop with one draft module: ``cache`` is the layers'
        arrays and then the module's, ``draft [B]`` each stream's first
        draft (prefill's). Per-stream pointers ``j`` (the index of the
        stream's input token); a step handles tokens ``j`` and ``j+1``
        of each active stream."""
        b, t = forced.shape
        w = watch.shape[0]
        variables = {"params": params, "buffers": buffers}
        stream, two = jnp.arange(b), jnp.arange(2)

        def forced_at(j):
            return jnp.take_along_axis(
                forced, jnp.minimum(j, t - 1)[:, None], axis=1)[:, 0]

        def passes(tokens, draft, j, active, cache):
            """A step's two passes over tokens ``j, j+1`` of every
            stream: the model on ``(tokens, draft)``, then the module
            on the inputs that follow them."""
            ahead, later = forced_at(j + 1), forced_at(j + 2)
            second = active & (j + 1 < max_tokens)
            # A forced input takes the draft's place, and is accepted.
            guess = jnp.where(ahead >= 0, ahead, draft)
            pos = (a_lens + j)[:, None] + two[None, :]
            with jax.named_scope("verify"):
                (logits, hidden, main, counters), state = self.model.apply(
                    variables, jnp.stack([tokens, guess], axis=1), pos,
                    jnp.stack([active, second], axis=1), cache[:-1],
                    method="verify", mutable=["intermediates"])
            with jax.named_scope("lm_head"):  # the argmax's passes
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, 2]
            # Tokens j+1 and j+2: forced, or the model's own.
            inputs = jnp.stack([jnp.where(ahead >= 0, ahead, nxt[:, 0]),
                                jnp.where(later >= 0, later, nxt[:, 1])],
                               axis=1)
            # The end id ends a stream whose next input is its own.
            ends = ~ignore_end & (nxt == 0) & (
                jnp.stack([ahead, later], axis=1) < 0)
            accept = second & ~ends[:, 0] & (inputs[:, 0] == guess)
            # The module drafts where the stream goes on after a token.
            goes_on = jnp.stack(
                [second, accept & (j + 2 < max_tokens)], axis=1) & ~ends
            with jax.named_scope("mtp_draft"):
                guesses, own, drafted = self.model.apply(
                    variables, inputs, hidden, pos, goes_on, cache[-1],
                    method="draft")
            # The layers' counters and then the module's.
            counters = jax.tree.map(
                lambda x, y: jnp.concatenate([x, y[None]]), counters,
                drafted)
            mid = _watched(state.get("intermediates", {}), (watch, 2),
                           self.sparse, self.mixed)
            if "chosen" in mid:  # [layers, W, 2, k] -> [W, 2, layers, k]
                mid["chosen"] = jnp.moveaxis(mid["chosen"], 0, 2)
            mid["logits"] = logits[watch]
            mid["draft_logits"] = guesses[watch]
            with jax.named_scope("lm_head"):
                drafts = jnp.argmax(guesses, axis=-1).astype(jnp.int32)
            ends = ends[:, 0] | (accept & ends[:, 1])
            return (counters, mid, main + [own], nxt, inputs, drafts,
                    second, accept, ends)

        def body(carry):
            i, j, tokens, draft, done, out, cache, acc, seen = carry
            active = ~done
            (counters, mid, cache, nxt, inputs, drafts, second, accept,
             ends) = passes(tokens, draft, j, active, cache)
            slot = jnp.stack([jnp.where(active, j, t),
                              jnp.where(accept, j + 1, t)], axis=1)
            out = out.at[stream[:, None], slot].set(nxt, mode="drop")
            emitted = active.astype(jnp.int32) + accept
            done = done | (j + emitted >= max_tokens) | ends
            tokens = jnp.where(accept, inputs[:, 1], inputs[:, 0])
            draft = jnp.where(accept, drafts[:, 1], drafts[:, 0])
            at = a_lens + j
            acc = dict(acc)
            acc["steps"] += 1
            acc["tokens"] += emitted
            acc["idle_slot_steps"] += jnp.sum(~active)
            acc["cache_rows_read"] += jnp.sum(
                jnp.where(active, at + 1, 0) + jnp.where(accept, at + 2, 0))
            acc["verify_positions"] += jnp.sum(active) + jnp.sum(second)
            acc["draft_positions"] += jnp.sum(second)
            acc["draft_accepted"] += jnp.sum(accept)
            acc["rejected_rows_overwritten"] += jnp.sum(
                second & ~accept & ~done)
            acc["drafts"] += jnp.sum(~done)
            self._count(acc, counters)
            at_w = slot[watch]
            seen = {k: seen[k].at[jnp.arange(w)[:, None], at_w].set(
                mid[k], mode="drop") for k in seen}
            return (i + 1, j + emitted, tokens, draft, done, out, cache,
                    acc, seen)

        def cond(carry):
            return (carry[0] < t) & ~jnp.all(carry[4])

        first = jnp.where(forced[:, 0] >= 0, forced[:, 0], 0)
        zero = jnp.zeros(b, jnp.int32)
        shapes = jax.eval_shape(
            lambda c: passes(first, draft, zero, max_tokens > 0, c)[:2],
            cache)
        acc = {k: jnp.zeros(v.shape, v.dtype) for k, v in shapes[0].items()}
        acc.update({k: jnp.int32(0) for k in (
            "steps", "idle_slot_steps", "cache_rows_read",
            "verify_positions", "draft_positions", "draft_accepted",
            "rejected_rows_overwritten")},
            tokens=zero, drafts=jnp.sum(max_tokens > 0),  # prefill's
            experts_hit=jnp.zeros(len(self.sparse) + 1, jnp.int32))
        # Per watched stream and TOKEN (a step writes two of them).
        seen = {k: jnp.zeros((w, t) + v.shape[2:], v.dtype)
                for k, v in shapes[1].items()}
        carry = (jnp.int32(0), zero, first, draft, max_tokens <= 0,
                 jnp.zeros((b, t), jnp.int32), cache, acc, seen)
        _, _, _, _, _, out, cache, acc, seen = jax.lax.while_loop(
            cond, body, carry)
        seen["chosen"] = jnp.moveaxis(seen["chosen"], 2, 0)
        return out, cache, acc, seen

    # -- a call --------------------------------------------------------------

    def cache_shapes(self, rows: int, frames: int) -> list:
        """Each layer's cache (a draft module's after them) for ``rows``
        streams whose prefix is ``frames`` feature frames, as
        ``jax.ShapeDtypeStruct`` s a layer: a list of one array for
        latent attention, else a tuple: keys and values for
        grouped-query attention,
        ``model.lfm_seq_positions`` rows a stream, or (0) the least
        that hold the prefix and every step, a windowed layer's ring
        ``lfm_window`` rows where that is fewer; for a hybrid layer
        keys, values, the mixer's state in float32 and the
        convolution's last inputs; for a sparse layer keys and values
        (whole blocks of ``sparse_block`` rows) and the pooled keys of
        their whole windows; for a linear layer its float32 state
        alone. All but the states are in the model's dtype."""
        m = self.cfg.model
        dtype = jnp.dtype(m.dtype)
        positions = seq_positions(m, frames, self.cfg.data.max_label_len)

        def arrays(*shapes):
            return [jax.ShapeDtypeStruct(s, dtype) for s in shapes]

        latent = arrays((rows, positions, m.mla_kv_rank + m.mla_rope_dim))

        def of(kind):
            if kind == LINEAR:
                return [jax.ShapeDtypeStruct(
                    (rows, m.lin_heads, m.lin_head_dim, m.lin_head_dim),
                    jnp.float32)]
            if kind == SPARSE:
                held = -(-positions // m.sparse_block) * m.sparse_block
                # head-major: a head's block of rows is one piece
                return arrays((rows, m.lfm_kv_heads, held, head_dim(m))) \
                    * 2 + arrays((rows, pooled_rows(m, held),
                                  m.lfm_kv_heads, head_dim(m)))
            if kind not in ATTENTION_KINDS + (HYBRID,):
                return latent
            ring = kind == "sliding_attention" and m.lfm_window
            held = arrays((rows, min(ring, positions) if ring else positions,
                           m.lfm_kv_heads, head_dim(m))) * 2
            if kind != HYBRID:
                return held
            return held + [
                jax.ShapeDtypeStruct(
                    (rows, m.ssm_heads, m.ssm_state,
                     m.ssm_d_ssm // m.ssm_heads), jnp.float32)
            ] + arrays((rows, m.ssm_conv - 1,
                        m.ssm_d_ssm + 2 * m.ssm_groups * m.ssm_state))

        # an array alone (a list of one) for latent rows, else a tuple
        held = [of(k) for k in m.lfm_layer_types]
        return [s if s is latent else tuple(s) for s in held] \
            + [latent] * m.lm_draft_layers

    def cache_for(self, rows: int, frames: int) -> list:
        """The cache of ``rows`` streams whose prefix is ``frames``
        feature frames: an array a latent layer or draft module, the
        pair (keys, values) a grouped-query layer, (keys, values,
        state, convolution inputs) a hybrid layer, (keys, values,
        pooled keys) a sparse layer, (state,) a linear layer."""
        shapes = self.cache_shapes(rows, frames)

        def spec(cache):
            return [(c.shape, c.dtype) for c in jax.tree.leaves(cache)]

        if self._cache is None or spec(self._cache) != spec(shapes):
            self._cache = None  # free the old one first
            self._cache = [
                tuple(jnp.zeros(x.shape, x.dtype) for x in s)
                if isinstance(s, tuple)
                else jnp.zeros(s[0].shape, s[0].dtype) for s in shapes]
            gauge = obs.registry().gauge
            gauge("lm_cache_bytes", cache_bytes(self._cache))
            for kind, name in (("sliding_attention", "window"),
                               ("full_attention", "global")):
                if self.kinds[kind]:
                    gauge("lm_cache_bytes_" + name, cache_bytes(
                        [self._cache[i][:2] for i in self.kinds[kind]]))
            if self.stateful:
                gauge("lm_cache_bytes_state", cache_bytes(
                    [self._cache[i][at] for i, at in self.state_at.items()]))
            if self.hybrid:
                gauge("lm_cache_bytes_conv", cache_bytes(
                    [self._cache[i][at + 1]
                     for i, at in self.state_at.items() if at]))
            if self.selecting:
                gauge("lm_cache_bytes_select", cache_bytes(
                    [self._cache[i][:2] for i in self.selecting]))
                gauge("lm_cache_bytes_pooled", cache_bytes(
                    [self._cache[i][2] for i in self.selecting]))
        cache, self._cache = self._cache, None
        return cache

    def step_bytes(self, acc: dict) -> dict:
        """Bytes a call's decode steps NEED to move, by part, from the
        loop's counters: every layer's weights and the head once a
        step, each live (stream, stateful layer)'s state read once and
        written once, the cache rows in reach (under a selection: the
        selected rows), keys and values, and (``select``) the pooled
        keys a selection ranked its blocks by."""
        steps = int(acc["steps"])
        named = {k: cache_bytes(v) for k, v in self.params.items()}
        head = named["embed" if self.cfg.model.lm_tied_head else "lm_head"]
        layers = sum(v for k, v in named.items() if k.startswith("layer"))
        m = self.cfg.model
        state = (m.ssm_d_ssm * m.ssm_state if self.hybrid
                 else m.lin_heads * m.lin_head_dim ** 2)
        row = m.lfm_kv_heads * head_dim(m) * jnp.dtype(m.dtype).itemsize
        out = {"weights": steps * layers, "head": steps * head,
               "state": int(acc["state_updates"]) * 2 * 4 * state,
               "rows": int(acc["cache_rows_read"]) * 2 * row}
        if self.selecting:
            out["select"] = int(np.sum(
                acc["select_windows_read"], dtype=np.int64)) * row
        return out

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
            watch = np.arange(min(cfg.decode.lm_watch_rows, b),
                              dtype=np.int32)
        self._calls += 1
        call = self._calls  # what the spans of one call share
        # The last call's watched outputs (with a vocabulary of 131,072
        # half a GB) are not held through this one: on a full chip the
        # loop's dispatch then waits for memory.
        self.last_call = None
        t0 = time.perf_counter()
        with obs.span("infer.transcribe", rows=b, call=call):
            with obs.span("infer.cache", call=call):
                cache = self.cache_for(b, features.shape[1])
            t_cache = time.perf_counter()
            a_lens, pre, pre_watch, drafts = [], [], None, []
            for i in range(b // sub):
                with obs.span("infer.prefill", rows=sub, call=call):
                    with obs.span("infer.prefill.dispatch", call=call):
                        cache, a, counters, mid, draft = self.prefill(
                            self.params, self.buffers, cache, features,
                            feat_lens, i * sub)
                    if obs.tracer.enabled:
                        with obs.span("infer.prefill.wait", call=call):
                            jax.block_until_ready(cache)
                        # The program's layer table, for a reader after
                        # the run (the new cache stands for the donated
                        # one).
                        obs.layers.watch(
                            "lm_prefill", self.prefill,
                            (self.params, self.buffers, cache, features,
                             feat_lens, i * sub))
                a_lens.append(a)
                pre.append(counters)
                drafts.append(draft)
                pre_watch = mid if i == 0 else pre_watch
            t1 = time.perf_counter()
            with obs.span("infer.decode", rows=b, call=call):
                with obs.span("infer.decode.dispatch", call=call):
                    rest = (jnp.concatenate(a_lens), max_tokens,
                            jnp.asarray(forced, jnp.int32),
                            jnp.asarray(watch, jnp.int32),
                            jnp.asarray(cfg.decode.lm_ignore_end),
                            jnp.concatenate(drafts) if self.draft
                            else None)
                    ids, cache, acc, seen = self.decode(
                        self.params, self.buffers, cache, *rest)
                t2 = time.perf_counter()
                if obs.tracer.enabled:
                    obs.layers.watch(
                        "lm_decode", self.decode,
                        (self.params, self.buffers, cache, *rest))
                with obs.span("infer.decode.fetch", call=call):
                    ids, acc, pre = jax.device_get((ids, acc, pre))
                t3 = time.perf_counter()
            self._cache = cache
        stats = obs.observe_lm_call(pre, acc, rows=b)
        if self.stateful:
            stats["decode_bytes"] = self.step_bytes(acc)
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
