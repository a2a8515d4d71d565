"""RNN-T (transducer) loss (Graves, arXiv:1211.3711).

Lattice: a T x (U+1) grid per utterance; at node (t, u) the model
either emits label u+1 (move up) or consumes frame t with BLANK (move
right, id 0). The forward variable

  alpha[t, u] = logaddexp(alpha[t-1, u] + blank[t-1, u],
                          alpha[t, u-1] + emit[t, u-1])

ends in loss = -(alpha[T-1, U] + blank[T-1, U]).

Two entry points:

``rnnt_joint_loss`` is what training runs (``train.py``, every
``objective="rnnt"`` model). It takes the joint's two projected
inputs ``e [B,T,J]`` and ``p [B,U+1,J]`` and its output layer
``w_o [J,V]``, ``b_o [V]``, and never holds logits for more than a
tile of ``tile_t`` frames: the forward maps over tiles of T, computes
``tanh(e + p) w_o + b_o`` for the tile's nodes and keeps only the
log-sum-exp and the blank and label log-probabilities ([B,T,U+1]
each), then runs alpha. Its ``custom_vjp`` backward runs beta, forms
the lattice occupancies, recomputes each tile's logits and contracts
``occupancy * softmax - picked`` into the gradients of e, p, w_o and
b_o. Padded nodes (t >= T_b or u > U_b) cost matmul time and
contribute exactly zero to loss and gradient.

``transducer_loss`` takes the materialised ``log_probs [B,T,U+1,V]``
and differentiates by autodiff: the small-size oracle (tests, and the
beam search's rescoring of W hypotheses against ONE utterance).

TPU mapping of the recursions: one ``lax.scan`` over T carries a row
[B, U+1]. The within-row recurrence is a first-order LINEAR
recurrence in the log semiring — x_u = logaddexp(b_u, a_u + x_{u-1})
— which is associative under the composition
  (a2, b2) ∘ (a1, b1) = (a1 + a2, logaddexp(b2, a2 + b1)),
so each time step runs ``lax.associative_scan`` over U: O(log U)
depth instead of a U-step serial loop, static shapes throughout.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

LOG_ZERO = -1e30


def _log_linear_scan(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Solve x_u = logaddexp(b_u, a_u + x_{u-1}) (x_{-1} = LOG_ZERO)
    along the LAST axis with an associative scan."""

    def combine(left, right):
        a1, b1 = left
        a2, b2 = right
        return a1 + a2, jnp.logaddexp(b2, a2 + b1)

    _, x = jax.lax.associative_scan(combine, (a, b), axis=-1)
    return x


def _alpha_rows(blank: jnp.ndarray, emit: jnp.ndarray) -> jnp.ndarray:
    """alpha [T, B, U+1] from blank [B, T, U+1] and emit [B, T, U]
    (already LOG_ZERO where u >= label_len)."""
    b, t_max, u1 = blank.shape
    init = jnp.full((b, u1), LOG_ZERO).at[:, 0].set(0.0)
    pad = jnp.full((b, 1), LOG_ZERO)

    # t = 0 row: only emits reachable — alpha[0, u] = sum of the first
    # u emit scores at t=0, closed by the same linear recurrence seeded
    # with init.
    alpha0 = _log_linear_scan(
        jnp.concatenate([pad, emit[:, 0]], axis=-1), init)

    # Rows t = 1..T-1 feed from the PREVIOUS row through that previous
    # t's blanks, then close the within-row emit recurrence.
    emit_rest = jnp.moveaxis(emit[:, 1:], 1, 0)        # [T-1, B, U]
    blank_prev = jnp.moveaxis(blank[:, :-1], 1, 0)     # [T-1, B, U+1]

    def step(alpha, inputs):
        emit_t, blank_p = inputs
        new = _log_linear_scan(
            jnp.concatenate([pad, emit_t], axis=-1), alpha + blank_p)
        return new, new

    _, rows = jax.lax.scan(step, alpha0, (emit_rest, blank_prev))
    return jnp.concatenate([alpha0[None], rows], axis=0)


def _mask_emit(emit: jnp.ndarray, label_lens: jnp.ndarray) -> jnp.ndarray:
    """No emission off the top of the lattice: LOG_ZERO at
    u >= label_len. emit [B, T, U]."""
    uidx = jnp.arange(emit.shape[-1])
    return jnp.where(uidx[None, None, :] < label_lens[:, None, None],
                     emit, LOG_ZERO)


def _lattice_nll(blank, emit, input_lens, label_lens):
    """(per-utterance NLL [B], alpha [T, B, U+1]) from the blank
    [B, T, U+1] and masked emit [B, T, U] log-probabilities."""
    t_max = blank.shape[1]
    rows = _alpha_rows(blank, emit)
    # Terminal: alpha[input_len-1, label_len] + blank there.
    tgood = jnp.clip(input_lens - 1, 0, t_max - 1)
    alpha_T = jnp.take_along_axis(
        rows, tgood[None, :, None], axis=0)[0]  # [B, U+1]
    alpha_end = jnp.take_along_axis(
        alpha_T, label_lens[:, None], axis=-1)[:, 0]
    blank_end = jnp.take_along_axis(
        jnp.take_along_axis(blank, tgood[:, None, None], axis=1)[:, 0],
        label_lens[:, None], axis=-1)[:, 0]
    nll = -(alpha_end + blank_end)
    # input_lens == 0: tgood clamped to frame 0 above, so alpha/blank
    # reads there are meaningless — mask to the explicit sentinel.
    return jnp.where(input_lens > 0, nll, -LOG_ZERO), rows


def transducer_loss(log_probs: jnp.ndarray, labels: jnp.ndarray,
                    input_lens: jnp.ndarray, label_lens: jnp.ndarray
                    ) -> jnp.ndarray:
    """Per-utterance RNN-T negative log-likelihood from the
    MATERIALISED lattice (the small-size oracle; training runs
    :func:`rnnt_joint_loss`).

    log_probs [B, T, U+1, V] (normalized over V, blank id 0), labels
    [B, U] (the id emitted FROM row u is labels[:, u]), input_lens [B],
    label_lens [B] <= U. Returns [B] f32.

    Zero-frame rows (``input_lens == 0``) have no lattice and therefore
    no likelihood: they are masked to the explicit sentinel
    ``-LOG_ZERO`` (a huge finite NLL) rather than silently reading the
    t=0 alpha/blank values — callers batching variable-length data must
    filter or down-weight such rows before averaging.
    """
    lp = log_probs.astype(jnp.float32)
    v = lp.shape[-1]
    u_max = lp.shape[2] - 1
    emit_ids = jnp.clip(labels.astype(jnp.int32), 0, v - 1)  # [B, U]
    emit = jnp.take_along_axis(
        lp[:, :, :u_max, :], emit_ids[:, None, :, None], axis=-1
    )[..., 0]  # [B, T, U]
    nll, _ = _lattice_nll(lp[:, :, :, 0], _mask_emit(emit, label_lens),
                          input_lens, label_lens)
    return nll


# ---------------------------------------------------------------------------
# The tiled joint + loss: what training runs.
# ---------------------------------------------------------------------------

# Lattice nodes whose logits one tile holds at a time. At V=4096 a tile
# of 16384 nodes is 268 MB of float32 logits: large enough that the
# [nodes, J] x [J, V] matmuls fill the MXU, small next to a chip's HBM.
TILE_NODES = 16384


def joint_tile_frames(b: int, u1: int, t_max: int) -> int:
    """Frames of T a tile of the joint takes at batch ``b`` and
    ``u1`` = U+1 prefix rows (a function of shapes only, so that a
    reader can reproduce the tile the compiled step uses)."""
    return max(1, min(t_max, TILE_NODES // max(b * u1, 1)))


def _tiles(x: jnp.ndarray, tile_t: int) -> jnp.ndarray:
    """[B, T, ...] -> [n_tiles, B, tile_t, ...], T zero-padded up."""
    b, t = x.shape[:2]
    n = -(-t // tile_t)
    x = jnp.pad(x, [(0, 0), (0, n * tile_t - t)]
                + [(0, 0)] * (x.ndim - 2))
    return jnp.moveaxis(
        x.reshape((b, n, tile_t) + x.shape[2:]), 1, 0)


def _untile(x: jnp.ndarray, t: int) -> jnp.ndarray:
    """Inverse of :func:`_tiles`."""
    n, b, tile_t = x.shape[:3]
    return jnp.moveaxis(x, 0, 1).reshape(
        (b, n * tile_t) + x.shape[3:])[:, :t]


def _tile_hidden(e_tile, p):
    """The joint's hidden layer for one tile, float32:
    tanh(e[b,t] + p[b,u]) -> [B, tile_t, U+1, J]."""
    return jnp.tanh(e_tile[:, :, None, :].astype(jnp.float32)
                    + p[:, None, :, :].astype(jnp.float32))


def _tile_logits(hq, w_o, b_o):
    """[B, tile_t, U+1, J] x [J, V] -> float32 logits; ``hq`` is the
    hidden layer in w_o's dtype (the model's compute dtype), the
    accumulation float32."""
    return jnp.einsum("btuj,jv->btuv", hq, w_o,
                      preferred_element_type=jnp.float32) + b_o


@jax.named_scope("rnnt_joint")
def _joint_picks(e, p, w_o, b_o, labels_ext, tile_t):
    """Forward over tiles of T: (lse, blank, emit) float32 [B, T, U+1];
    ``emit[..., u]`` is the log-probability of labels_ext[:, u] (the
    last column, past every label, is masked by the caller)."""
    t_max = e.shape[1]
    # The label's column of w_o, one row per (b, u): the picked logit
    # is a 640-wide dot, not a gather over the tile's V-wide logits.
    w_lab = jnp.take(w_o, labels_ext, axis=1)          # [J, B, U+1]
    w_lab = jnp.moveaxis(w_lab, 0, -1).astype(jnp.float32)
    b_lab = jnp.take(b_o, labels_ext).astype(jnp.float32)   # [B, U+1]

    def tile(e_tile):
        hq = _tile_hidden(e_tile, p).astype(w_o.dtype)
        logits = _tile_logits(hq, w_o, b_o)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.sum(hq.astype(jnp.float32) * w_lab[:, None],
                         axis=-1) + b_lab[:, None]
        return lse, logits[..., 0] - lse, picked - lse

    lse, blank, emit = jax.lax.map(tile, _tiles(e, tile_t))
    return _untile(lse, t_max), _untile(blank, t_max), _untile(emit, t_max)


def _labels_ext(labels: jnp.ndarray, v: int) -> jnp.ndarray:
    """[B, U] -> [B, U+1] int32 in range, a (masked) blank past the
    last label: the class whose log-probability row u picks."""
    return jnp.concatenate(
        [jnp.clip(labels.astype(jnp.int32), 0, v - 1),
         jnp.zeros((labels.shape[0], 1), jnp.int32)], axis=1)


def rnnt_joint_scores(e, p, w_o, b_o, labels, label_lens,
                      tile_t: Optional[int] = None):
    """What the lattice recursions of :func:`rnnt_joint_loss` run on
    (same arguments): (blank [B, T, U+1], emit [B, T, U]) float32
    log-probabilities, emit LOG_ZERO at u >= label_len."""
    if tile_t is None:
        tile_t = joint_tile_frames(e.shape[0], p.shape[1], e.shape[1])
    _, blank, emit = _joint_picks(
        e, p, w_o, b_o.astype(jnp.float32),
        _labels_ext(labels, w_o.shape[1]), int(tile_t))
    return blank, _mask_emit(emit[:, :, :-1], label_lens)


def _beta_rows(blank, emit, input_lens, label_lens):
    """beta [T, B, U+1]: log-probability of finishing the utterance
    from node (t, u); LOG_ZERO outside the valid lattice
    (t >= input_len or u > label_len). blank [B, T, U+1], emit
    [B, T, U] masked."""
    b, t_max, u1 = blank.shape
    uidx = jnp.arange(u1)
    pad = jnp.full((b, 1), LOG_ZERO)
    at_top = uidx[None, :] == label_lens[:, None]          # [B, U+1]
    xs = (jnp.arange(t_max), jnp.moveaxis(blank, 1, 0),
          jnp.moveaxis(emit, 1, 0))

    def step(beta_next, inputs):
        t, blank_t, emit_t = inputs
        last = (t == input_lens - 1)[:, None]
        # Leaving frame t by a blank: into row t+1, or, from the
        # terminal node (input_len-1, label_len), out of the lattice.
        from_next = jnp.where(
            last, jnp.where(at_top, blank_t, LOG_ZERO),
            beta_next + blank_t)
        # x_u = logaddexp(from_next_u, emit_u + x_{u+1}): the linear
        # recurrence of the alpha rows, run from the top row down.
        a = jnp.concatenate([emit_t, pad], axis=-1)
        new = _log_linear_scan(a[:, ::-1], from_next[:, ::-1])[:, ::-1]
        new = jnp.where((t < input_lens)[:, None], new, LOG_ZERO)
        return new, new

    _, rows = jax.lax.scan(step, jnp.full((b, u1), LOG_ZERO), xs,
                           reverse=True)
    return rows


def _occupancies(alpha, beta, blank, emit, nll, input_lens, label_lens):
    """Posterior probability of leaving node (t, u) by a blank (ob) and
    by its label (oe), [B, T, U+1] each: -d nll / d blank and
    -d nll / d emit. Exactly zero on padded nodes and on zero-frame
    rows."""
    b, t_max, u1 = blank.shape
    alpha = jnp.moveaxis(alpha, 0, 1)
    beta = jnp.moveaxis(beta, 0, 1)
    tidx = jnp.arange(t_max)[None, :, None]
    uidx = jnp.arange(u1)[None, None, :]
    t_len = input_lens[:, None, None]
    u_len = label_lens[:, None, None]
    ll = -nll[:, None, None]
    # beta of the node a blank leads to: row t+1, or 0 (certainty) out
    # of the terminal node.
    beta_right = jnp.concatenate(
        [beta[:, 1:], jnp.full((b, 1, u1), LOG_ZERO)], axis=1)
    beta_right = jnp.where((tidx == t_len - 1) & (uidx == u_len), 0.0,
                           beta_right)
    beta_up = jnp.concatenate(
        [beta[:, :, 1:], jnp.full((b, t_max, 1), LOG_ZERO)], axis=2)
    emit_full = jnp.concatenate(
        [emit, jnp.full((b, t_max, 1), LOG_ZERO)], axis=2)
    inside = (tidx < t_len) & (uidx <= u_len) & (t_len > 0)
    ob = jnp.where(inside, jnp.exp(jnp.minimum(
        alpha + blank + beta_right - ll, 0.0)), 0.0)
    oe = jnp.where(inside & (uidx < u_len), jnp.exp(jnp.minimum(
        alpha + emit_full + beta_up - ll, 0.0)), 0.0)
    return ob, oe


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _rnnt_joint_loss(e, p, w_o, b_o, labels_ext, input_lens, label_lens,
                     tile_t):
    nll, _ = _joint_loss_fwd(e, p, w_o, b_o, labels_ext, input_lens,
                             label_lens, tile_t)
    return nll


def _joint_loss_fwd(e, p, w_o, b_o, labels_ext, input_lens, label_lens,
                    tile_t):
    lse, blank, emit = _joint_picks(e, p, w_o, b_o, labels_ext, tile_t)
    with jax.named_scope("rnnt_lattice"):
        emit = _mask_emit(emit[:, :, :-1], label_lens)
        nll, alpha = _lattice_nll(blank, emit, input_lens, label_lens)
    return nll, (e, p, w_o, b_o, labels_ext, input_lens, label_lens,
                 lse, blank, emit, alpha, nll)


def _joint_loss_bwd(tile_t, residuals, g):
    (e, p, w_o, b_o, labels_ext, input_lens, label_lens,
     lse, blank, emit, alpha, nll) = residuals
    t_max = e.shape[1]
    v = w_o.shape[1]
    with jax.named_scope("rnnt_lattice"):
        beta = _beta_rows(blank, emit, input_lens, label_lens)
        ob, oe = _occupancies(alpha, beta, blank, emit, nll, input_lens,
                              label_lens)
        g = g.astype(jnp.float32)[:, None, None]
        ob, oe = ob * g, oe * g
    vidx = jnp.arange(v)
    is_label = vidx[None, None, :] == labels_ext[:, :, None]  # [B,U+1,V]

    def tile(carry, xs):
        dw, db, dp = carry
        e_tile, lse_t, ob_t, oe_t = xs
        h = _tile_hidden(e_tile, p)
        hq = h.astype(w_o.dtype)
        logits = _tile_logits(hq, w_o, b_o)
        # d nll / d logits = (ob + oe) softmax - ob [v = blank]
        #                    - oe [v = label]; zero where ob = oe = 0.
        dlogits = ((ob_t + oe_t)[..., None]
                   * jnp.exp(logits - lse_t[..., None])
                   - jnp.where(vidx == 0, ob_t[..., None], 0.0)
                   - jnp.where(is_label[:, None], oe_t[..., None], 0.0))
        dq = dlogits.astype(w_o.dtype)
        dh = jnp.einsum("btuv,jv->btuj", dq, w_o,
                        preferred_element_type=jnp.float32)
        dw = dw + jnp.einsum("btuj,btuv->jv", hq, dq,
                             preferred_element_type=jnp.float32)
        db = db + jnp.sum(dlogits, axis=(0, 1, 2))
        dz = dh * (1.0 - h * h)
        return (dw, db, dp + jnp.sum(dz, axis=1)), jnp.sum(dz, axis=2)

    init = (jnp.zeros(w_o.shape, jnp.float32),
            jnp.zeros(b_o.shape, jnp.float32),
            jnp.zeros(p.shape, jnp.float32))
    with jax.named_scope("rnnt_joint"):
        (dw, db, dp), de = jax.lax.scan(
            tile, init, (_tiles(e, tile_t), _tiles(lse, tile_t),
                         _tiles(ob, tile_t), _tiles(oe, tile_t)))
        return (_untile(de, t_max).astype(e.dtype), dp.astype(p.dtype),
                dw.astype(w_o.dtype), db.astype(b_o.dtype), None, None,
                None)


_rnnt_joint_loss.defvjp(_joint_loss_fwd, _joint_loss_bwd)


def rnnt_joint_loss(e: jnp.ndarray, p: jnp.ndarray, w_o: jnp.ndarray,
                    b_o: jnp.ndarray, labels: jnp.ndarray,
                    input_lens: jnp.ndarray, label_lens: jnp.ndarray,
                    tile_t: Optional[int] = None) -> jnp.ndarray:
    """Per-utterance RNN-T NLL [B] of the joint
    ``tanh(e[b,t] + p[b,u]) w_o + b_o`` without its lattice of logits.

    e [B, T, J] and p [B, U+1, J] are the encoder's and the prediction
    net's projections into the joint (biases included), w_o [J, V] in
    the dtype the matmuls should run in, b_o [V]; labels [B, U] (blank
    id 0), input_lens [B] <= T, label_lens [B] <= U. Equal to
    ``transducer_loss(log_softmax(logits), ...)`` on the materialised
    logits, gradients included; zero-frame rows give the same
    ``-LOG_ZERO`` sentinel and no gradient. ``tile_t`` (frames of T a
    tile holds) defaults to :func:`joint_tile_frames`; any value gives
    the same result.
    """
    b, t_max, _ = e.shape
    u1 = p.shape[1]
    if tile_t is None:
        tile_t = joint_tile_frames(b, u1, t_max)
    return _rnnt_joint_loss(e, p, w_o, b_o.astype(jnp.float32),
                            _labels_ext(labels, w_o.shape[1]),
                            input_lens.astype(jnp.int32),
                            label_lens.astype(jnp.int32), int(tile_t))


def transducer_loss_ref(log_probs, labels, input_lens, label_lens):
    """Brute-force O(T*U) python/numpy oracle (tests only): the same
    DP with explicit loops."""
    import numpy as np

    lp = np.asarray(log_probs, np.float64)
    b, t_max, u1, v = lp.shape
    out = np.zeros((b,), np.float64)
    for i in range(b):
        t_len = int(input_lens[i])
        u_len = int(label_lens[i])
        alpha = np.full((t_len, u_len + 1), -np.inf)
        for t in range(t_len):
            for u in range(u_len + 1):
                if t == 0 and u == 0:
                    alpha[0, 0] = 0.0
                    continue
                cands = []
                if t > 0:
                    cands.append(alpha[t - 1, u] + lp[i, t - 1, u, 0])
                if u > 0:
                    cands.append(alpha[t, u - 1]
                                 + lp[i, t, u - 1, labels[i][u - 1]])
                alpha[t, u] = np.logaddexp.reduce(cands)
        out[i] = -(alpha[t_len - 1, u_len] + lp[i, t_len - 1, u_len, 0])
    return out
