"""Operations and bytes of the A.X-K1 decoder-only recogniser as it is
SERVED (configuration ``ax_k1``), computed from shapes.

Conventions as in ``costs/lfm2.py``: a matmul [m,k]x[k,n] is 2*m*k*n
operations; element-wise work (norms, gates, softmax, rotations, the
router's sigmoid, group selection and top-k, sort, gather and scatter)
is left out. What is needed is the work of VALID positions (a stream's
prefix, and the steps it decodes) and of the (position, expert) pairs
routed to experts HELD HERE: padded positions, a finished stream's idle
slot and the absent experts' share count for nothing, and bytes count
once. Attention's mixing is counted in its cheaper, expanded form for
both of the layer's forms (a decode step's absorbed form does more
operations a key to move fewer bytes).

``model`` is anything with the fields of the program's ``ModelConfig``.
"""

from __future__ import annotations

from benchmark.costs.ds2 import roofline_seconds  # noqa: F401  (shared)

DOT_BYTES = 2  # bfloat16 weights, activations and cache


def prefix_positions(model, frames: int) -> int:
    return -(-int(frames) // model.frame_stack)


def attention_params(model) -> int:
    """q_a, q_b, kv_a, kv_b, o of one layer."""
    d, nh = model.lfm_hidden, model.lfm_heads
    dn, dr, dv = model.mla_nope_dim, model.mla_rope_dim, model.mla_v_dim
    rq, rkv = model.mla_q_rank, model.mla_kv_rank
    return (d * rq + rq * nh * (dn + dr) + d * (rkv + dr)
            + rkv * nh * (dn + dv) + nh * dv * d)


def expert_params(model) -> int:
    """One expert, routed or shared: gate, up and down matrices."""
    return 3 * model.lfm_hidden * model.lfm_expert_dim


def sparse_layers(model) -> int:
    return len(model.lfm_layer_types) - model.lfm_dense_layers


def position_params(model) -> int:
    """Parameters every valid position passes through, all layers,
    without its routed experts: attention's projections, the dense
    feed-forward of the leading layers, router and shared expert of the
    others."""
    layers = len(model.lfm_layer_types)
    dense = model.lfm_dense_layers
    return (layers * attention_params(model)
            + dense * 3 * model.lfm_hidden * model.lfm_ffn_dim
            + sparse_layers(model) * (
                model.lfm_hidden * model.lfm_experts
                + model.moe_shared_experts * expert_params(model)))


def mixing_flops(model, positions: int) -> int:
    """q k^T and probabilities times v of one causal sequence of
    ``positions``, all layers: position p attends to p + 1 keys with
    heads x (nope + rope + v) multiply-adds a key."""
    per_key = 2 * model.lfm_heads * (
        model.mla_nope_dim + model.mla_rope_dim + model.mla_v_dim)
    return len(model.lfm_layer_types) * per_key \
        * positions * (positions + 1) // 2


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


def gmm_call_cost(k: int, n: int, groups_hit: float, rows: float) -> dict:
    """Operations and HBM bytes ONE grouped product ``moe_gmm``
    [rows,k] x [groups,k,n] -> [rows,n] needs for ``rows`` routed rows
    that lie in ``groups_hit`` of its groups: the rows read once, the
    result written once, and the matrices of the groups that have a
    row read once (an expert without a row needs nothing)."""
    return {"flops": 2 * rows * k * n,
            "bytes": DOT_BYTES * (rows * (k + n) + groups_hit * k * n)}


def decode_step_bytes(model, experts_hit: float, cache_rows: float
                      ) -> float:
    """HBM bytes one decode step needs: every weight a position passes
    through and the head once, the matrices of the ``experts_hit`` held
    experts (over all expert layers) that received a pair, and the
    ``cache_rows`` rows (over all streams, per layer) its attention
    reads, each once. The batch's activations are left out."""
    weights = (position_params(model)
               + model.lfm_hidden * model.vocab_size
               + experts_hit * expert_params(model))
    row = model.mla_kv_rank + model.mla_rope_dim
    return DOT_BYTES * (weights + len(model.lfm_layer_types)
                        * cache_rows * row)
