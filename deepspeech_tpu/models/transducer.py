"""RNN-T (transducer) model family (Graves, arXiv:1211.3711).

Two encoders behind one model, told apart by ``ModelConfig.rnn_type``:

- ``gru`` / ``lstm``: this repo's conv frontend + (uni- or
  bidirectional) RNN stack, a GRU prediction network over label
  prefixes, an additive tanh joint (the small family of
  ``tests/test_transducer.py``).
- ``lstmp``: the streaming RNN-T of He et al. 2019 (arXiv:1811.06621;
  preset ``rnnt_he2019``): stacked frames, unidirectional
  LSTM-with-projection layers with layer normalisation and one time
  reduction (``models/rnn.LSTMPEncoder``), an LSTM-with-projection
  prediction network whose one-step decode path carries ``(c, r)`` of
  every layer, the same joint.

Training (``train.py``, ``objective="rnnt"``) calls
:meth:`RNNTModel.loss`: the joint's two projections go into
``ops/transducer.rnnt_joint_loss``, which computes the logits tile by
tile and never holds the [B, T', U+1, V] lattice. ``__call__``
materialises that lattice and is the small-size oracle; decoding
(greedy and beam, below; ``infer --decode.mode=rnnt_greedy|rnnt_beam``,
``Trainer._evaluate_rnnt``) needs single nodes of it only.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ..config import ModelConfig
from ..ops.transducer import rnnt_joint_loss
from .conv import ConvFrontend
from .layers import length_mask
from .rnn import LSTMPEncoder, LSTMPLayer, RNNStack, gru_scan


def _start_and_labels(labels: jnp.ndarray) -> jnp.ndarray:
    """[B, U] -> [B, U+1]: position 0 consumes the start token (the
    blank id 0), position u the label u."""
    return jnp.concatenate(
        [jnp.zeros((labels.shape[0], 1), labels.dtype), labels], axis=1)


class PredictionNet(nn.Module):
    """Label-prefix GRU: embeds [<blank>=start, y_1..y_U] and scans —
    output row u is the state after consuming u labels (the context
    for emitting label u+1). ``step`` runs one carried-state step for
    time-synchronous decoding."""

    vocab_size: int
    hidden: int
    embed_dim: int = 64

    def setup(self):
        self.embed = nn.Embed(self.vocab_size, self.embed_dim)
        self.wx = nn.Dense(3 * self.hidden)
        self.w_h = self.param("wh", nn.initializers.orthogonal(),
                              (self.hidden, 3 * self.hidden), jnp.float32)
        self.b_h = self.param("bh", nn.initializers.zeros,
                              (3 * self.hidden,), jnp.float32)

    def __call__(self, labels: jnp.ndarray) -> jnp.ndarray:
        inputs = _start_and_labels(labels)  # [B, U+1]
        xp = self.wx(self.embed(inputs))
        # All U+1 prefix states matter (row u feeds lattice row u), so
        # the scan mask is all-ones; label_lens bounds are applied by
        # the loss/decode consumers.
        mask = jnp.ones(inputs.shape, jnp.float32)
        return gru_scan(xp, mask, self.w_h, self.b_h)  # [B, U+1, H]

    def step(self, last_ids: jnp.ndarray, h: jnp.ndarray):
        """Consume one label id per stream: (out [B, H], h' [B, H])."""
        xp = self.wx(self.embed(last_ids))[:, None, :]  # [B, 1, 3H]
        mask = jnp.ones((last_ids.shape[0], 1), jnp.float32)
        ys, hf = gru_scan(xp, mask, self.w_h, self.b_h, h0=h,
                          return_final=True)
        return ys[:, 0], hf


class LSTMPPredictionNet(nn.Module):
    """Label-prefix LSTM-with-projection stack (He et al. 2019):
    ``rnnt_pred_layers`` layers of ``hidden`` cells projected to
    ``rnn_proj`` over a ``rnnt_pred_embed``-wide embedding. ``step``
    carries every layer's (c, r), packed into ONE array
    [B, layers * (hidden + rnn_proj)] so the decoders handle it like
    the GRU's h."""

    cfg: ModelConfig
    hidden: int
    mesh: Optional[Mesh] = None

    def setup(self):
        cfg = self.cfg
        self.embed = nn.Embed(cfg.vocab_size, cfg.rnnt_pred_embed)
        self.layers = [
            LSTMPLayer(cfg, self.hidden, cfg.rnn_proj, self.mesh,
                       name=f"lstmp{i}")
            for i in range(cfg.rnnt_pred_layers)]

    def __call__(self, labels: jnp.ndarray) -> jnp.ndarray:
        inputs = _start_and_labels(labels)
        x = self.embed(inputs)
        mask = jnp.ones(inputs.shape, jnp.float32)
        for layer in self.layers:
            x = layer(x, mask)
        return x  # [B, U+1, P]

    def step(self, last_ids: jnp.ndarray, state: jnp.ndarray):
        """Consume one label id per stream: (out [B, P], state')."""
        h, p = self.hidden, self.cfg.rnn_proj
        x = self.embed(last_ids)[:, None, :]
        mask = jnp.ones((last_ids.shape[0], 1), jnp.float32)
        new = []
        for i, layer in enumerate(self.layers):
            at = i * (h + p)
            cr0 = (state[:, at:at + h], state[:, at + h:at + h + p])
            x, (c, r) = layer(x, mask, cr0=cr0, return_final=True)
            new += [c, r]
        return x[:, 0], jnp.concatenate(new, axis=-1)


class _OutLayer(nn.Module):
    """The joint's output layer as bare parameters (the tree of an
    ``nn.Dense``): the tiled loss multiplies by them tile by tile."""

    vocab_size: int
    joint_dim: int

    @nn.compact
    def __call__(self):
        return (self.param("kernel", nn.initializers.lecun_normal(),
                           (self.joint_dim, self.vocab_size), jnp.float32),
                self.param("bias", nn.initializers.zeros,
                           (self.vocab_size,), jnp.float32))


class RNNTJoint(nn.Module):
    """Additive joint (Graves et al. 2013):
    tanh(W_e enc + W_p pred + b) W_o + b_o -> vocab logits."""

    vocab_size: int
    joint_dim: int = 256
    dtype: str = "float32"

    def setup(self):
        dtype = jnp.dtype(self.dtype)
        self.enc_proj = nn.Dense(self.joint_dim, dtype=dtype)
        self.pred_proj = nn.Dense(self.joint_dim, use_bias=False,
                                  dtype=dtype)
        self.out = _OutLayer(self.vocab_size, self.joint_dim)

    def __call__(self, enc: jnp.ndarray, pred: jnp.ndarray) -> jnp.ndarray:
        # enc [B, T, De] + pred [B, U+1, Dp] -> [B, T, U+1, V]
        e = self.enc_proj(enc)[:, :, None, :].astype(jnp.float32)
        p = self.pred_proj(pred)[:, None, :, :].astype(jnp.float32)
        w_o, b_o = self.out()
        dtype = jnp.dtype(self.dtype)
        return jnp.dot(jnp.tanh(e + p).astype(dtype), w_o.astype(dtype),
                       preferred_element_type=jnp.float32) + b_o

    def loss(self, enc, pred, labels, input_lens, label_lens):
        """Per-utterance NLL [B] through the tiled joint + loss."""
        w_o, b_o = self.out()
        return rnnt_joint_loss(
            self.enc_proj(enc), self.pred_proj(pred),
            w_o.astype(jnp.dtype(self.dtype)), b_o, labels, input_lens,
            label_lens)


class RNNTModel(nn.Module):
    """Encoder + prediction net + joint from the shared ModelConfig.
    ``loss`` is the training path; ``__call__`` returns the
    full-lattice log-probs (small sizes only);
    ``encode``/``predict``/``predict_step``/``joint_logits`` serve
    decoding."""

    cfg: ModelConfig
    pred_hidden: int = 128
    joint_dim: int = 256
    mesh: Optional[Mesh] = None

    def setup(self):
        cfg = self.cfg
        if cfg.rnn_type == "lstmp":
            self._enc = LSTMPEncoder(cfg, self.mesh, name="enc")
            self._pred = LSTMPPredictionNet(cfg, self.pred_hidden,
                                            self.mesh, name="pred")
        else:
            self._conv = ConvFrontend(cfg, name="conv")
            self._rnn = RNNStack(cfg, mesh=self.mesh, name="rnn")
            self._pred = PredictionNet(cfg.vocab_size, self.pred_hidden,
                                       cfg.rnnt_pred_embed, name="pred")
        self._joint = RNNTJoint(cfg.vocab_size, self.joint_dim,
                                cfg.dtype, name="joint")

    @property
    def pred_state_size(self) -> int:
        """Width of the prediction net's carried decode state."""
        cfg = self.cfg
        if cfg.rnn_type == "lstmp":
            return cfg.rnnt_pred_layers * (self.pred_hidden + cfg.rnn_proj)
        return self.pred_hidden

    def encode(self, features, feat_lens, train: bool = False):
        if self.cfg.rnn_type == "lstmp":
            x, lens = self._enc(features, feat_lens)
        else:
            x, lens = self._conv(features, feat_lens, train)
            x = self._rnn(x, lens, train)
        mask = length_mask(lens, x.shape[1])
        return (x * mask[:, :, None]).astype(jnp.float32), lens

    def predict(self, labels):
        # No length argument by design: all U+1 prefix states matter
        # (row u feeds lattice row u), so label bounds are applied by
        # the loss/decode consumers, not here.
        return self._pred(labels)

    def predict_step(self, last_ids, state):
        return self._pred.step(last_ids, state)

    def joint_logits(self, enc, pred):
        return self._joint(enc, pred).astype(jnp.float32)

    def __call__(self, features, feat_lens, labels, label_lens,
                 train: bool = False
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        enc, lens = self.encode(features, feat_lens, train)
        pred = self.predict(labels)
        logits = self.joint_logits(enc, pred)
        return jax.nn.log_softmax(logits, axis=-1), lens

    def joint_loss(self, enc, pred, labels, enc_lens, label_lens):
        """Per-utterance NLL [B] of encoder rows against prediction
        rows through the tiled joint + loss."""
        return self._joint.loss(enc, pred, labels, enc_lens, label_lens)

    def loss(self, features, feat_lens, labels, label_lens,
             train: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(per-utterance NLL [B], encoder lengths [B]); equal to
        ``transducer_loss(self(...))`` without its lattice."""
        enc, lens = self.encode(features, feat_lens, train)
        pred = self.predict(labels)
        return self.joint_loss(enc, pred, labels, lens, label_lens), lens


def create_rnnt_model(cfg: ModelConfig, mesh: Optional[Mesh] = None
                      ) -> RNNTModel:
    """Single construction point (train + infer share it): the
    transducer widths ride ModelConfig.rnnt_*."""
    return RNNTModel(cfg, pred_hidden=cfg.rnnt_pred_hidden,
                     joint_dim=cfg.rnnt_joint_dim, mesh=mesh)


@functools.lru_cache(maxsize=8)
def _beam_fns(model: RNNTModel, w: int):
    """Jitted beam helpers, cached by (model, beam_width) so repeated
    decode_batch calls across a dataset reuse ONE compilation
    (variables ride as a pytree argument, not a closure)."""

    @jax.jit
    def pstep(variables, last_ids, h):  # [W], [W, H] -> ([W, H], [W, H])
        return model.apply(variables, last_ids, h,
                           method=RNNTModel.predict_step)

    @jax.jit
    def frame_logps(variables, enc_t, pred_outs):  # [De],[W,H] -> [W,V]
        logits = model.apply(
            variables, jnp.broadcast_to(enc_t, (w, 1) + enc_t.shape),
            pred_outs[:, None, :], method=RNNTModel.joint_logits)
        return jax.nn.log_softmax(logits[:, 0, 0, :], axis=-1)

    @jax.jit
    def rescore(variables, enc_i, enc_len, labels, label_lens):
        """Exact lattice log-likelihood of W label sequences against ONE
        utterance's encoder output: enc_i [T, De], labels [W, U],
        label_lens [W] -> [W] f32. One training-style forward through
        the tiled joint + loss, so the scores the search returns are
        honest full-sum likelihoods, not pruned-alignment bounds, at
        any vocabulary size."""
        enc_b = jnp.broadcast_to(enc_i[None], (w,) + enc_i.shape)
        pred = model.apply(variables, labels, method=RNNTModel.predict)
        lens = jnp.full((w,), enc_len, jnp.int32)
        return -model.apply(variables, enc_b, pred, labels, lens,
                            label_lens, method=RNNTModel.joint_loss)

    return pstep, frame_logps, rescore


@functools.lru_cache(maxsize=8)
def _greedy_fns(model: RNNTModel):
    """Jitted greedy helpers, cached by model (see _beam_fns)."""

    @jax.jit
    def pstep(variables, last_id, h):
        return model.apply(variables, last_id, h,
                           method=RNNTModel.predict_step)

    @jax.jit
    def step_logits(variables, enc_t, pred_u):
        return model.apply(variables, enc_t[None, None, :],
                           pred_u[None, None, :],
                           method=RNNTModel.joint_logits)[0, 0, 0]

    return pstep, step_logits


def rnnt_beam_decode(model: RNNTModel, variables, features, feat_lens,
                     beam_width: int, max_label_len: int,
                     max_symbols_per_frame: int = 4,
                     return_nbest: bool = False):
    """Time-synchronous RNN-T beam search (host loop).

    At each encoder frame every hypothesis either takes BLANK (consume
    the frame) or emits symbols (up to the per-frame cap) before
    consuming it; hypotheses reaching the same prefix merge by
    ``logaddexp`` (summing alignment probabilities, the transducer
    analogue of CTC prefix merging). Prediction-net states advance one
    carried GRU step per emission, padded to a FIXED beam_width batch
    so the two jitted applies compile exactly once.

    The per-frame merged score is a LOWER BOUND on the true lattice
    likelihood — pruning (top-w per expansion and per frame) discards
    proportionally more alignment mass for longer prefixes, so ranking
    the final beam by it can invert e.g. ``[4,4,4]`` above ``[4,4,4,4]``
    even when the longer prefix has the higher full-sum likelihood.
    The search therefore finishes with an EXACT full-lattice rescoring
    of the surviving <=W hypotheses (one batched training-style
    forward per utterance through the tiled loss, static
    [W, max_label_len] shapes so it compiles once) and ranks by that. Returns list[list[int]] — or,
    with ``return_nbest``, per-utterance ``[(prefix_list,
    exact_log_likelihood)]`` best-first. (Even ``beam_width=1`` can
    beat greedy: the frame loop compares "blank now" against "emit
    then blank", a one-frame lookahead greedy lacks.)
    """
    enc, lens = model.apply(variables, features, feat_lens,
                            method=RNNTModel.encode)
    enc = np.asarray(enc)
    lens = np.asarray(lens)
    hidden = model.pred_state_size
    w = beam_width
    pstep_v, frame_logps_v, rescore_v = _beam_fns(model, w)
    pstep = functools.partial(pstep_v, variables)
    frame_logps = functools.partial(frame_logps_v, variables)
    rescore = functools.partial(rescore_v, variables)

    def padded(rows):  # stack K<=W rows, pad with the first to W
        k = len(rows)
        return np.stack(rows + [rows[0]] * (w - k))

    # Start-token state is input-independent: one device step for the
    # whole batch.
    pred0, h0 = pstep(jnp.zeros((w,), jnp.int32),
                      jnp.zeros((w, hidden), jnp.float32))
    pred0, h0 = np.asarray(pred0)[0], np.asarray(h0)[0]
    out = []
    for i in range(enc.shape[0]):
        # hyp: prefix tuple -> [score, pred_out row, h row]
        hyps = {(): [0.0, pred0, h0]}
        for t in range(int(lens[i])):
            enc_t = jnp.asarray(enc[i, t])
            done: dict = {}   # prefixes that consumed frame t (blank)
            frontier = hyps
            for step in range(max_symbols_per_frame + 1):
                if not frontier:
                    break
                keys = list(frontier)
                lp = np.asarray(frame_logps(enc_t, jnp.asarray(
                    padded([frontier[p][1] for p in keys]))))
                # Blank: consume the frame, prefix unchanged.
                for j, p in enumerate(keys):
                    s = frontier[p][0] + lp[j, 0]
                    if p in done:
                        done[p][0] = np.logaddexp(done[p][0], s)
                    else:
                        done[p] = [s, frontier[p][1], frontier[p][2]]
                if step == max_symbols_per_frame:
                    break  # cap reached: emissions would be discarded
                # Emissions: expand, prune to the beam, then advance
                # the pruned hypotheses' prediction states in one batch.
                cands = []
                for j, p in enumerate(keys):
                    if len(p) >= max_label_len:
                        continue
                    for v in range(1, lp.shape[1]):
                        cands.append((frontier[p][0] + lp[j, v], p, v, j))
                cands.sort(key=lambda c: -c[0])
                cands = cands[:w]
                if not cands:
                    break
                ids = jnp.asarray(
                    np.concatenate([np.asarray([c[2] for c in cands],
                                               np.int32),
                                    np.zeros(w - len(cands), np.int32)]))
                hs = jnp.asarray(padded(
                    [frontier[c[1]][2] for c in cands]))
                pred_new, h_new = pstep(ids, hs)
                pred_new, h_new = np.asarray(pred_new), np.asarray(h_new)
                nxt: dict = {}
                for j, (s, p, v, _) in enumerate(cands):
                    # (p, v) pairs are unique within one expansion, so
                    # no collision here; PREFIX merging (logaddexp over
                    # alignments) happens in `done` across steps.
                    nxt[p + (v,)] = [s, pred_new[j], h_new[j]]
                frontier = nxt
            hyps = dict(sorted(done.items(),
                               key=lambda kv: -kv[1][0])[:w])
        # Exact full-lattice rescoring of the surviving beam (see
        # docstring): pad the <=W prefixes to static [W, max_label_len]
        # so the jitted forward compiles once per decode shape.
        prefixes = [list(p) for p, _ in hyps.items()]
        k = len(prefixes)
        labels_np = np.zeros((w, max(1, max_label_len)), np.int32)
        lens_np = np.zeros((w,), np.int32)
        for j, p in enumerate(prefixes):
            labels_np[j, :len(p)] = p
            lens_np[j] = len(p)
        ll = np.asarray(rescore(jnp.asarray(enc[i]),
                                jnp.asarray(int(lens[i]), jnp.int32),
                                jnp.asarray(labels_np),
                                jnp.asarray(lens_np)))[:k]
        order = sorted(range(k), key=lambda j: -ll[j])
        if return_nbest:
            out.append([(prefixes[j], float(ll[j])) for j in order])
        else:
            out.append(prefixes[order[0]])
    return out


def rnnt_greedy_decode(model: RNNTModel, variables, features, feat_lens,
                       max_label_len: int, max_symbols_per_frame: int = 4,
                       return_times: bool = False):
    """Time-synchronous greedy transducer decode (host loop).

    At each encoder frame emit argmax symbols until blank (or the
    per-frame cap). The prediction net advances ONE carried-state GRU
    step per emitted symbol (O(U) total, compile-once jitted applies).
    Returns list[list[int]]; with ``return_times`` also a parallel
    list of per-symbol EMISSION frame indices (the time-synchronous
    search knows each symbol's frame natively — no separate alignment
    pass, unlike CTC's argmax-alignment proxy).
    """
    enc, lens = model.apply(variables, features, feat_lens,
                            method=RNNTModel.encode)
    enc = np.asarray(enc)
    lens = np.asarray(lens)
    b = enc.shape[0]
    hidden = model.pred_state_size
    pstep_v, step_logits_v = _greedy_fns(model)
    pstep = functools.partial(pstep_v, variables)
    step_logits = functools.partial(step_logits_v, variables)

    # Start-token state is input-independent: compute once.
    pred_start, h_start = pstep(jnp.zeros((1,), jnp.int32),
                                jnp.zeros((1, hidden), jnp.float32))
    out = []
    times = []
    for i in range(b):
        prefix: list = []
        frames: list = []
        pred_out, h = pred_start, h_start
        for t in range(int(lens[i])):
            emitted = 0
            while emitted < max_symbols_per_frame and \
                    len(prefix) < max_label_len:
                logits = np.asarray(step_logits(
                    jnp.asarray(enc[i, t]), pred_out[0]))
                k = int(np.argmax(logits))
                if k == 0:
                    break
                prefix.append(k)
                frames.append(t)
                pred_out, h = pstep(jnp.full((1,), k, jnp.int32), h)
                emitted += 1
        out.append(prefix)
        times.append(frames)
    return (out, times) if return_times else out
