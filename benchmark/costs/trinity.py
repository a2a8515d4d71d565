"""Operations and bytes of the Trinity-Large-Preview decoder-only
recogniser as it is SERVED (configuration ``trinity_large``), computed
from shapes.

Conventions as in ``costs/axk1.py`` (a matmul [m,k]x[k,n] is 2*m*k*n
operations; element-wise work, the router's sigmoid and top-k, sort,
gather and scatter are left out; padded positions, a finished stream's
idle slot and the absent experts' share count for nothing; bytes count
once), whose count of one expert and of one grouped product are used as
they are. What differs is the attention: grouped-query heads of a size
of their own with an output gate, and KEYS IN REACH that depend on the
layer's kind: position p attends to ``p + 1`` keys in a layer that
sees all and to ``min(p + 1, window)`` in a sliding layer, with
``4 * heads * head`` operations a key (q . k and p . v).

``model`` is anything with the fields of the program's ``ModelConfig``.
"""

from __future__ import annotations

from benchmark.costs.axk1 import (  # noqa: F401  (shared)
    DOT_BYTES, expert_params, gmm_call_cost, prefix_positions,
    roofline_seconds, sparse_layers)

KINDS = ("sliding_attention", "full_attention")


def head_dim(model) -> int:
    return model.lfm_head_dim or model.lfm_hidden // model.lfm_heads


def attention_params(model) -> int:
    """q, k, v, o and the output gate of one layer."""
    d, hd = model.lfm_hidden, head_dim(model)
    wide = model.lfm_heads * hd
    return (d * wide * (3 if model.lfm_attn_gate else 2)
            + 2 * d * model.lfm_kv_heads * hd)


def layers_of(model, kind: str) -> int:
    return sum(k == kind for k in model.lfm_layer_types)


def position_params(model) -> int:
    """Parameters every valid position passes through, all layers,
    without its routed experts: attention's projections, the dense
    feed-forward of the leading layers, router and shared expert of the
    others."""
    return (len(model.lfm_layer_types) * attention_params(model)
            + model.lfm_dense_layers * 3 * model.lfm_hidden
            * model.lfm_ffn_dim
            + sparse_layers(model) * (
                model.lfm_hidden * model.lfm_experts
                + model.moe_shared_experts * expert_params(model)))


def parameters(model, num_features: int = 161) -> int:
    """Every parameter held on the chip (norm gains and the selection
    bias left out)."""
    d = model.lfm_hidden
    heads = (1 if model.lm_tied_head else 2) * model.vocab_size * d
    return (heads + num_features * model.frame_stack * d
            + position_params(model) + sparse_layers(model)
            * model.experts_held * expert_params(model))


def cache_row_bytes(model) -> int:
    """One position's keys and values in one layer's cache."""
    return 2 * model.lfm_kv_heads * head_dim(model) * DOT_BYTES


def cache_bytes(model, streams: int, cache_rows: int) -> int:
    """The cache of ``streams`` streams: a ring of ``lfm_window`` rows
    (fewer where the cache rows are fewer) a sliding layer, ``cache_rows``
    a layer that sees all."""
    ring = min(model.lfm_window, cache_rows)
    rows = (layers_of(model, "sliding_attention") * ring
            + layers_of(model, "full_attention") * cache_rows)
    return streams * rows * cache_row_bytes(model)


def keys_in_reach(model, positions: int) -> dict:
    """Over positions 0 .. ``positions`` - 1 of one sequence, the keys
    each attends to, summed, in one layer of each kind."""
    n, w = int(positions), model.lfm_window
    full = n * (n + 1) // 2
    m = min(n, w)
    return {"full_attention": full,
            "sliding_attention": m * (m + 1) // 2 + (n - m) * w}


def mixing_flops(model, positions: int, start: int = 0) -> int:
    """q k^T and probabilities times v of the positions ``start ..
    positions - 1`` of one sequence, all layers."""
    per_key = 4 * model.lfm_heads * head_dim(model)
    upto, before = (keys_in_reach(model, positions),
                    keys_in_reach(model, start))
    return per_key * sum(layers_of(model, k) * (upto[k] - before[k])
                         for k in KINDS)


def stream_flops(model, frames: int, steps: int,
                 num_features: int = 161) -> int:
    """Operations one stream needs without its routed experts: ``a``
    prefix positions through the prefix projection and the layers,
    ``steps`` decoded positions through embedding, layers and head."""
    a = prefix_positions(model, frames)
    s = a + int(steps)
    return (a * 2 * num_features * model.frame_stack * model.lfm_hidden
            + s * 2 * position_params(model) + mixing_flops(model, s)
            + int(steps) * 2 * model.lfm_hidden * model.vocab_size)


def call_flops_valid(model, valid_frames, steps, pairs_held: int,
                     num_features: int = 161) -> int:
    """Operations one served call NEEDS: every stream at its own frames
    and decoded steps, and the ``pairs_held`` pairs that the call's
    routing sent to experts held here (all expert layers, prefill and
    decode)."""
    return (sum(stream_flops(model, t, u, num_features)
                for t, u in zip(valid_frames, steps))
            + int(pairs_held) * 2 * expert_params(model))


def prefill_attention_flops(model, valid_frames) -> int:
    """Attention's mixing operations the prefix positions of a call's
    streams need, all layers."""
    return sum(mixing_flops(model, prefix_positions(model, t))
               for t in valid_frames)


def decode_attention_bytes(model, rows_attended: float) -> float:
    """HBM bytes the decode steps' attention needs: the cache rows in
    reach (``rows_attended``: over streams, steps and layers; the
    program's own counters), keys and values, each once."""
    return rows_attended * cache_row_bytes(model)


def decode_step_bytes(model, experts_hit: float, rows_attended: float
                      ) -> float:
    """HBM bytes one decode step needs: every weight a position passes
    through and the head's slice once, the matrices of the
    ``experts_hit`` held experts (over all expert layers) that received
    a pair, and the ``rows_attended`` cache rows in reach (over streams
    and layers), each once. The batch's activations are left out."""
    weights = (position_params(model)
               + model.lfm_hidden * model.vocab_size
               + experts_hit * expert_params(model))
    return DOT_BYTES * weights + decode_attention_bytes(
        model, rows_attended)
