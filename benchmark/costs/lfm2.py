"""Operations and bytes of the LFM2 decoder-only recogniser
(configuration ``lfm2_24b_a2b``), computed from shapes.

Conventions as in ``costs/ds2.py``: a matmul [m,k]x[k,n] is 2*m*k*n
operations; backward is twice forward for every matmul, so a training
step NEEDS three forwards; element-wise work (norms, gates, the 3-tap
filter, softmax, rotations, the router's sigmoid and top-k, sort,
gather and scatter) is left out. Recomputation is needed work zero
times. What is needed is the work of VALID positions and of the
(position, expert) pairs routed to experts HELD HERE: padded positions
and the absent experts' share count for nothing.

``model`` is anything with the fields of the program's ``ModelConfig``.
"""

from __future__ import annotations

from benchmark.costs.ds2 import roofline_seconds  # noqa: F401  (shared)


def prefix_positions(model, frames: int) -> int:
    """Prefix positions of an utterance of ``frames`` feature frames."""
    return -(-int(frames) // model.frame_stack)


def valid_positions(model, frames: int, labels: int) -> int:
    """Audio prefix, the start symbol, the labels."""
    return prefix_positions(model, frames) + 1 + int(labels)


def conv_position_flops(model) -> int:
    """Gated short convolution, one position: W_in [D,3D], W_out [D,D]."""
    d = model.lfm_hidden
    return 2 * d * 3 * d + 2 * d * d


def attention_projection_flops(model) -> int:
    """q, k, v, o of one position."""
    d = model.lfm_hidden
    kv = d // model.lfm_heads * model.lfm_kv_heads
    return 2 * d * d * 2 + 2 * d * kv * 2


def attention_mixing_flops(model, positions: int) -> int:
    """q k^T and probabilities times v of one causal sequence of
    ``positions``: position p attends to p + 1 keys over all heads."""
    return 2 * 2 * model.lfm_hidden * positions * (positions + 1) // 2


def dense_ffn_position_flops(model) -> int:
    return 3 * 2 * model.lfm_hidden * model.lfm_ffn_dim


def router_position_flops(model) -> int:
    return 2 * model.lfm_hidden * model.lfm_experts


def expert_pair_flops(model) -> int:
    """One (position, expert) pair: gate, up and down matrices."""
    return 3 * 2 * model.lfm_hidden * model.lfm_expert_dim


def head_position_flops(model) -> int:
    """The tied output head at one text position, over the slice."""
    return 2 * model.lfm_hidden * model.vocab_size


def utterance_forward_flops(model, frames: int, labels: int,
                            num_features: int = 161) -> int:
    """Forward operations of one utterance without its routed experts."""
    a = prefix_positions(model, frames)
    s = a + 1 + int(labels)
    flops = a * 2 * num_features * model.frame_stack * model.lfm_hidden
    for i, kind in enumerate(model.lfm_layer_types):
        if kind == "conv":
            flops += s * conv_position_flops(model)
        else:
            flops += s * attention_projection_flops(model) \
                + attention_mixing_flops(model, s)
        if i < model.lfm_dense_layers:
            flops += s * dense_ffn_position_flops(model)
        else:
            flops += s * router_position_flops(model)
    return flops + (int(labels) + 1) * head_position_flops(model)


def train_flops_valid(model, valid_frames, label_lens, pairs_held: int,
                      num_features: int = 161) -> int:
    """Forward + backward operations a step NEEDS: every utterance at
    its own frames and labels, and the ``pairs_held`` pairs that the
    step's routing sent to experts held here (all expert layers)."""
    return 3 * (sum(utterance_forward_flops(model, t, u, num_features)
                    for t, u in zip(valid_frames, label_lens))
                + int(pairs_held) * expert_pair_flops(model))


def gmm_call_cost(kernel: str, k: int, n: int, groups: int, rows: int,
                  dot_bytes: int = 2) -> dict:
    """Operations and HBM bytes ONE grouped product needs for ``rows``
    routed rows (the rows of the static capacity past them need
    nothing): every operand read once, the result written once.

    ``moe_gmm`` [rows,k] x [groups,k,n] -> [rows,n]; with
    ``transpose_rhs`` the facts' ``k`` is the contraction all the
    same. ``moe_tgmm`` [rows,k]^T x [rows,n] per group -> [groups,k,n].
    """
    w = groups * k * n * dot_bytes
    if kernel == "moe_gmm":
        bytes_ = rows * k * dot_bytes + w + rows * n * dot_bytes
    elif kernel == "moe_tgmm":
        bytes_ = rows * (k + n) * dot_bytes + w
    else:
        raise ValueError(kernel)
    return {"flops": 2 * rows * k * n, "bytes": bytes_}
