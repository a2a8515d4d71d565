"""The sparse expert layer of one chip: routing over all experts,
dropless dispatch to the experts held here, combine.

An expert-parallel deployment divides a layer's experts over chips.
This layer is told which it holds (``offset``, and as many as its
weights have groups): it scores every position over ALL ``experts``
at the published width, keeps the (position, expert) pairs whose
expert lives here, computes their part of the result and leaves out
what the absent experts would have added. On one chip it runs without
its exchange; nothing here stands in for the absent chips.

Dropless: a pair that lands here is computed, whatever the imbalance.
Pairs are sorted by expert, the sizes of the runs are the groups of
two grouped matrix products (``moe_pallas.gmm`` on a TPU,
``jax.lax.ragged_dot`` elsewhere), and the rows go back to their
positions by a weighted scatter-add. The one static size is the row
capacity; at the worst case (``rows_bound`` 0) nothing can exceed it,
and under a stated bound the layer counts what did (``dropped``).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..utils.impl import interpret_default, resolve_impl
from . import moe_pallas


class Routing(NamedTuple):
    experts: jnp.ndarray   # [N, k] int32: chosen expert ids
    weights: jnp.ndarray   # [N, k] float32: their combine weights
    scores: jnp.ndarray    # [N, E] float32: the router's scores


@jax.named_scope("moe_route")   # the name its device time is read under
def route(x, w_gate, bias, top_k: int, groups: int = 1,
          groups_kept: int = 1, scale: float = 1.0,
          score_func: str = "sigmoid") -> Routing:
    """Scores over all experts in float32, then the preset's
    selection rule. The experts are ``groups`` runs of consecutive
    ids; a group's score is its best expert's, and only the
    ``groups_kept`` best groups can be chosen from (``n_group`` /
    ``topk_group``; 1 of 1 is the plain rule). Among those the
    ``top_k`` of score + ``bias`` are chosen (``use_expert_bias``: the
    bias chooses, it does not weigh; None where the family has none).
    With ``score_func`` "sigmoid" the scores are the logits' sigmoids
    and the chosen ones, normalised to sum to one (``norm_topk_prob``),
    are the weights; with "softmax" the scores are the logits
    themselves and the weights a softmax over the chosen ones (a
    softmax over all, the chosen renormalised). Either times ``scale``
    (``routed_scaling_factor``)."""
    scores = jnp.dot(x.astype(jnp.float32), w_gate.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    if score_func == "sigmoid":
        scores = jax.nn.sigmoid(scores)
    elif score_func != "softmax":
        raise ValueError(f"moe_score_func {score_func!r}")
    choose_by = scores if bias is None else scores + bias[None, :]
    if groups > 1:
        n, e = choose_by.shape
        best = jnp.max(choose_by.reshape(n, groups, e // groups), axis=2)
        _, kept = lax.top_k(best, groups_kept)               # [N, kept]
        in_kept = jnp.any(
            kept[:, :, None] == jnp.arange(groups)[None, None, :], axis=1)
        choose_by = jnp.where(jnp.repeat(in_kept, e // groups, axis=1),
                              choose_by, -jnp.inf)
    _, experts = lax.top_k(choose_by, top_k)
    weights = jnp.take_along_axis(scores, experts, axis=1)
    if score_func == "softmax":
        weights = jax.nn.softmax(weights, axis=1)
    else:
        weights = weights / (jnp.sum(weights, axis=1, keepdims=True)
                             + 1e-6)
    if scale != 1.0:
        weights = weights * scale
    return Routing(experts.astype(jnp.int32), weights, scores)


def capacity_rows(positions: int, top_k: int, held: int,
                  rows_bound: float) -> int:
    """Static rows of the dispatch buffer for ``positions`` computed
    positions: the worst case (every position sends min(top_k, held)
    pairs here), or ``rows_bound`` of all pairs; in whole row tiles of
    the size a call of that many rows over ``held`` groups takes."""
    worst = positions * min(top_k, held)
    rows = worst if rows_bound <= 0 else min(
        worst, math.ceil(positions * top_k * rows_bound))
    return moe_pallas.row_capacity(rows, held)


def grouped_dot(lhs, rhs, group_sizes, impl: str):
    """``lhs [m,k]`` by row groups times ``rhs [g,k,n]``; rows past the
    groups give zeros under either implementation."""
    if impl == "pallas":
        out = moe_pallas.gmm(lhs, rhs, group_sizes, lhs.dtype,
                             interpret_default())
    else:
        out = lax.ragged_dot(lhs, rhs, group_sizes)
    # A layer that rematerialises keeps these (models/lfm2.py).
    return checkpoint_name(out, "moe_rows")


ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def expert_layer(x, valid, routing: Routing, w13, w2, *, offset: int,
                 rows_bound: float = 0.0, impl: str = "auto",
                 act: str = "silu"):
    """``x [N, D]`` through the held experts.

    ``w13 [G, D, 2F]`` holds each expert's gate and up matrices side by
    side, ``w2 [G, F, D]`` its down matrix (``act(x gate) * (x up)``
    through ``down``, ``act`` one of :data:`ACTIVATIONS`); the experts
    are ids ``offset .. offset+G`` of the router's. ``valid [N]`` marks the
    positions that are routed at all (padding is not). Returns the
    partial result ``[N, D]`` (zeros where no chosen expert lives
    here) and the step's counters.
    """
    impl = resolve_impl(impl, oracle="xla")
    n, d = x.shape
    g, _, f2 = w13.shape
    k = routing.experts.shape[1]
    m = capacity_rows(n, k, g, rows_bound)

    # The scopes are the names the device's time is read under
    # (obs/layers.py): the sort and the row gather, the experts'
    # products with the gate between them, the weighted scatter-add.
    with jax.named_scope("moe_dispatch"):
        local = routing.experts - offset
        here = valid[:, None] & (local >= 0) & (local < g)
        key = jnp.where(here, local, g).reshape(-1)          # [N*k]
        # Stable: rows of one expert stay in position order.
        order = jnp.argsort(key, stable=True)[:m]
        if order.shape[0] < m:  # fewer pairs than one row tile
            order = jnp.pad(order, (0, m - order.shape[0]))
        token = order // k
        counts = jnp.sum(jax.nn.one_hot(key, g + 1, dtype=jnp.int32),
                         axis=0)[:g]
        # Under a stated bound the groups are cut to the rows there are.
        ends = jnp.minimum(jnp.cumsum(counts), m)
        sizes = jnp.diff(ends, prepend=0).astype(jnp.int32)
        routed = jnp.arange(m) < ends[-1]
        weight = jnp.where(routed,
                           routing.weights.reshape(-1)[order], 0.0)
        xs = jnp.take(x, token, axis=0)                       # [M, D]
    with jax.named_scope("moe_gmm"):
        h = grouped_dot(xs, w13.astype(x.dtype), sizes, impl)
        f = f2 // 2
        gated = (ACTIVATIONS[act](h[:, :f].astype(jnp.float32))
                 * h[:, f:].astype(jnp.float32)).astype(x.dtype)
        ys = grouped_dot(gated, w2.astype(x.dtype), sizes, impl)
    with jax.named_scope("moe_combine"):
        out = jnp.zeros((n, d), jnp.float32).at[token].add(
            ys.astype(jnp.float32) * weight[:, None])

    held = jnp.sum(counts)
    counters = {
        "expert_pairs": counts,                       # [G] pairs each
        "pairs_elsewhere": jnp.sum(valid) * k - held,
        "rows_high_water": held,
        "rows_capacity": jnp.int32(m),
        "dropped": held - ends[-1],
    }
    return out.astype(x.dtype), counters
