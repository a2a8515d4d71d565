"""Operations and bytes of the SmallThinker-21BA3B-Instruct decoder-only
recogniser as it is TRAINED (configuration ``smallthinker_21b_a3b``),
computed from shapes.

Conventions as in ``costs/lfm2.py`` (a matmul [m,k]x[k,n] is 2*m*k*n
operations; backward is twice forward for every matmul, so a training
step NEEDS three forwards; element-wise work, the router's softmax and
top-k, sort, gather and scatter are left out; recomputation is needed
work zero times; padded positions and the absent experts' share count
for nothing), whose count of one grouped product is used as it is.
What differs is the attention, counted as ``costs/trinity.py`` counts
it: grouped-query heads of a size of their own and KEYS IN REACH that
depend on the layer's kind (``p + 1`` keys for position p in a layer
that sees all, ``min(p + 1, window)`` in a sliding one), with ``4 *
heads * head`` operations a (query, key) pair forward (q . k and p . v)
and twice that backward (dv, dp, dq, dk; the scores the backward
kernels compute again count for nothing).

``model`` is anything with the fields of the program's ``ModelConfig``.
"""

from __future__ import annotations

from benchmark.costs.lfm2 import (  # noqa: F401  (shared)
    gmm_call_cost, prefix_positions, roofline_seconds)
from benchmark.costs.trinity import (  # noqa: F401  (shared)
    KINDS, head_dim, keys_in_reach, layers_of)


def attention_params(model) -> int:
    """q, k, v, o of one layer: no gate, no bias, no q/k norm."""
    d, hd = model.lfm_hidden, head_dim(model)
    return 2 * d * model.lfm_heads * hd + 2 * d * model.lfm_kv_heads * hd


def expert_params(model) -> int:
    """Gate, up and down matrices of one expert."""
    return 3 * model.lfm_hidden * model.lfm_expert_dim


def router_params(model) -> int:
    return model.lfm_hidden * model.lfm_experts


def layer_params(model, held: int) -> int:
    """One layer with ``held`` of its experts (norm gains left out)."""
    return (attention_params(model) + router_params(model)
            + held * expert_params(model))


def parameters(model, num_features: int = 161) -> int:
    """Every parameter held on the chip (norm gains left out): the
    layers with their held experts, embedding and untied head over the
    vocabulary slice, the prefix projection."""
    d = model.lfm_hidden
    heads = (1 if model.lm_tied_head else 2) * model.vocab_size * d
    return (len(model.lfm_layer_types)
            * layer_params(model, model.experts_held)
            + heads + num_features * model.frame_stack * d)


def published_parameters(model, layers: int, vocab: int) -> int:
    """The whole published model by the same formulas: every layer with
    all the router's experts, embedding and head over ``vocab``."""
    return (layers * layer_params(model, model.lfm_experts)
            + 2 * vocab * model.lfm_hidden)


def state_bytes(model, num_features: int = 161) -> int:
    """Float32 parameters, gradients and AdamW's two moments."""
    return 16 * parameters(model, num_features)


def mixing_flops(model, positions: int) -> int:
    """q k^T and probabilities times v of one sequence of ``positions``
    positions, forward, all layers."""
    per_pair = 4 * model.lfm_heads * head_dim(model)
    reach = keys_in_reach(model, positions)
    return per_pair * sum(layers_of(model, k) * reach[k] for k in KINDS)


def position_flops(model) -> int:
    """Forward operations of one valid position through all layers
    without mixing and routed experts: projections and router."""
    return 2 * len(model.lfm_layer_types) * (
        attention_params(model) + router_params(model))


def utterance_forward_flops(model, frames: int, labels: int,
                            num_features: int = 161) -> int:
    """Forward operations of one utterance without its routed experts."""
    a = prefix_positions(model, frames)
    s = a + 1 + int(labels)
    return (a * 2 * num_features * model.frame_stack * model.lfm_hidden
            + s * position_flops(model) + mixing_flops(model, s)
            + (int(labels) + 1) * 2 * model.lfm_hidden * model.vocab_size)


def train_flops_valid(model, valid_frames, label_lens, pairs_held: int,
                      num_features: int = 161) -> int:
    """Forward + backward operations a step NEEDS: every utterance at
    its own frames and labels, and the ``pairs_held`` pairs that the
    step's routing sent to experts held here (all layers)."""
    return 3 * (sum(utterance_forward_flops(model, t, u, num_features)
                    for t, u in zip(valid_frames, label_lens))
                + int(pairs_held) * 2 * expert_params(model))


# Products a (query, key) pair in reach needs in each attention kernel,
# of 2 * head operations each and query head: forward q . k and p . v;
# backward dp and dq in ``gqa_attn_bwd_dq``, dv and dk in
# ``gqa_attn_bwd_dkv`` (their recomputed scores count for nothing).
ATTN_PRODUCTS = {"gqa_attn_fwd": 2, "gqa_attn_bwd_dq": 2,
                 "gqa_attn_bwd_dkv": 2}


def pairs_in_reach(s: int, window: int) -> int:
    """(query, key) pairs in reach over ``s`` positions of one layer:
    what a call over ``s`` positions must compute, whatever its tiles."""
    n = int(s)
    if not window:
        return n * (n + 1) // 2
    m = min(n, int(window))
    return m * (m + 1) // 2 + (n - m) * int(window)


def attn_call_cost(facts: dict, dot_bytes: int = 2) -> dict:
    """Operations and HBM bytes ONE attention kernel call needs, from
    its facts (``ops/kernel_id.py``: ``kernel``, ``b``, ``s``, ``kv``,
    ``rep``, ``head``, ``window``; all strings): the pairs in reach of
    its ``s`` positions over all rows and query heads; q (or dq), k, v
    (or dk, dv), the result (or its gradient) each once."""
    b, s, kv, rep, hd, window = (int(facts[k]) for k in (
        "b", "s", "kv", "rep", "head", "window"))
    products = ATTN_PRODUCTS[facts["kernel"]]
    wide, narrow = b * s * kv * rep * hd, b * s * kv * hd
    bytes_ = {"gqa_attn_fwd": 2 * wide + 2 * narrow,
              "gqa_attn_bwd_dq": 3 * wide + 2 * narrow,
              "gqa_attn_bwd_dkv": 2 * wide + 4 * narrow}[facts["kernel"]]
    return {"flops": products * 2 * hd * b * kv * rep
            * pairs_in_reach(s, window),
            "bytes": bytes_ * dot_bytes}
