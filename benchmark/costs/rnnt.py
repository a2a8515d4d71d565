"""Operations and bytes of the streaming RNN-T of He et al. 2019
(configuration ``rnnt_he2019``), computed from shapes.

Conventions as in ``costs/ds2.py``: a matmul [m,k]x[k,n] is 2*m*k*n
operations; backward is twice forward for every matmul, so a training
step NEEDS three forwards; elementwise work (gates, layer norm, tanh,
the softmax) and the lattice recursions are left out. Recomputation is
needed work zero times: the backward pass of the tiled joint computes
every tile's logits a second time and the backward scan kernels
recompute every step's gates, and neither counts here.

``model`` is anything with the fields of the program's ``ModelConfig``
(duck-typed: a namespace built from the configuration file works).
"""

from __future__ import annotations

from benchmark.costs.ds2 import roofline_seconds  # noqa: F401  (shared)


def enc_frames(model, frames: int) -> tuple:
    """(frames after stacking, frames after the time reduction) of one
    utterance of ``frames`` feature frames; each rounds up."""
    t1 = -(-int(frames) // model.frame_stack)
    t2 = -(-t1 // model.time_reduction) \
        if model.time_reduction_layer > 0 else t1
    return t1, t2


def lstmp_frame_flops(d_in: int, hidden: int, proj: int) -> int:
    """Forward operations of one LSTM-with-projection layer for ONE
    frame: x W_x [d,4H], r W_r [P,4H], m W_p [H,P]."""
    return 2 * d_in * 4 * hidden + 2 * proj * 4 * hidden \
        + 2 * hidden * proj


def encoder_layers(model, frames: int, num_features: int = 161) -> list:
    """[(input width, frames)] per encoder layer: each layer at its own
    frame rate, the layer after the reduction twice as wide."""
    t1, t2 = enc_frames(model, frames)
    out, d, t = [], num_features * model.frame_stack, t1
    for i in range(model.rnn_layers):
        out.append((d, t))
        d = model.rnn_proj
        if i + 1 == model.time_reduction_layer:
            d, t = model.rnn_proj * model.time_reduction, t2
    return out


def encoder_flops(model, frames: int, num_features: int = 161) -> int:
    return sum(t * lstmp_frame_flops(d, model.rnn_hidden, model.rnn_proj)
               for d, t in encoder_layers(model, frames, num_features))


def prediction_flops(model, positions: int) -> int:
    """Forward operations of the prediction net over ``positions``
    = U+1 label prefixes (the embedding is a lookup)."""
    flops, d = 0, model.rnnt_pred_embed
    for _ in range(model.rnnt_pred_layers):
        flops += positions * lstmp_frame_flops(
            d, model.rnnt_pred_hidden, model.rnn_proj)
        d = model.rnn_proj
    return flops


def joint_node_flops(model) -> int:
    """Forward operations of ONE lattice node: the output layer
    [J] x [J,V]."""
    return 2 * model.rnnt_joint_dim * model.vocab_size


def joint_flops(model, t_enc: int, positions: int) -> int:
    """Forward operations of the joint for one utterance: the two
    projections into the joint and every node's output layer."""
    proj = 2 * model.rnn_proj * model.rnnt_joint_dim
    return (t_enc + positions) * proj \
        + t_enc * positions * joint_node_flops(model)


def forward_flops(model, frames: int, labels: int,
                  num_features: int = 161) -> int:
    """Forward operations for one utterance of ``frames`` feature
    frames and ``labels`` labels, at its own lengths."""
    t_enc = enc_frames(model, frames)[1]
    return (encoder_flops(model, frames, num_features)
            + prediction_flops(model, labels + 1)
            + joint_flops(model, t_enc, labels + 1))


def train_flops_valid(model, valid_frames, label_lens,
                      num_features: int = 161) -> int:
    """Forward + backward operations a step NEEDS: every utterance at
    its own frames and labels; padding to the bucket and to
    ``max_label_len`` counts for nothing."""
    return 3 * sum(forward_flops(model, int(t), int(u), num_features)
                   for t, u in zip(valid_frames, label_lens))


def lattice_nodes(model, valid_frames, label_lens) -> int:
    """Valid lattice nodes of a batch: T'_b * (U_b + 1) summed."""
    return sum(enc_frames(model, int(t))[1] * (int(u) + 1)
               for t, u in zip(valid_frames, label_lens))


def padded_nodes(model, rows: int, bucket_frames: int,
                 max_label_len: int) -> int:
    """Lattice nodes a step computes: every row at the bucket's T' and
    ``max_label_len`` + 1 prefix rows."""
    return rows * enc_frames(model, bucket_frames)[1] * (max_label_len + 1)


def joint_step_cost(model, rows: int, t_enc: int, positions: int,
                    dot_bytes: int = 2) -> dict:
    """Operations and HBM bytes the tiled joint + loss of ONE step has
    to do, forward and backward, over the ``rows * t_enc * positions``
    nodes it computes (padded nodes cost matmul time like valid ones).

    Operations: the output layer forward (2JV a node) and its two
    gradients (4JV a node: into the hidden layer and into W_o); the
    backward pass's second computation of the logits is recomputation
    and is not counted. Bytes: every operand read once and every result
    written once, per pass: e [rows,T',J] and p [rows,U+1,J] in the dot
    type, W_o [J,V], the three [rows,T',U+1] float32 score tensors out
    of the forward pass; lse and the two occupancies in, and the
    gradients of e, p (float32) and W_o (float32) out of the backward
    pass. No logits: a tile's logits never need to leave the core.
    """
    j, v = model.rnnt_joint_dim, model.vocab_size
    nodes = rows * t_enc * positions
    acts = (rows * t_enc * j + rows * positions * j) * dot_bytes
    w = j * v * dot_bytes + v * 4
    scores = 3 * nodes * 4
    fwd_bytes = acts + w + scores
    bwd_bytes = acts + w + scores \
        + (rows * t_enc * j + rows * positions * j) * 4 + j * v * 4 + v * 4
    return {"nodes": nodes,
            "flops": 3 * nodes * joint_node_flops(model),
            "bytes": fwd_bytes + bwd_bytes}


def lstmp_scan_cost(model, hidden: int, batch: int, steps: int, *,
                    backward: bool, dot_bytes: int = 2) -> dict:
    """Operations and HBM bytes of ONE layer's recurrence over
    ``steps`` time steps at ``batch`` rows (the hoisted input
    projection is outside it).

    Forward, per step: r W_r (2*b*P*4H) and m W_p (2*b*H*P); reads
    xproj [b,4H] in the dot type and the mask, writes r [b,P] float32
    (the cell-state tape a training forward also writes is the
    backward pass's input and is counted there). Backward, per step:
    the gradients into the two matmuls' inputs, dr W_p^T and da W_r^T,
    the forward's operations once (the kernel also recomputes r W_r:
    recomputation, not counted; the weight gradients are contracted
    outside the time loop and are not the kernel's); reads xproj, the
    mask, the taped cell state [b,H] and r [b,P] and the output's
    cotangent [b,P] (float32), writes the gate gradients [b,4H], the
    recomputed cell outputs [b,H] and the masked output gradients
    [b,P] in the dot type. The weights and the layer-norm vectors are
    read once per call."""
    p, b = model.rnn_proj, batch
    flops = 2 * b * p * 4 * hidden + 2 * b * hidden * p
    w_bytes = (p * 4 * hidden + hidden * p) * dot_bytes + 8 * hidden * 4
    if backward:
        act = b * 4 * hidden * dot_bytes + b * 4 \
            + (b * hidden + 2 * b * p) * 4 \
            + (b * 4 * hidden + b * hidden + b * p) * dot_bytes
    else:
        act = b * 4 * hidden * dot_bytes + b * 4 + b * p * 4
    return {"flops": flops * steps, "bytes": act * steps + w_bytes,
            "weight_bytes": w_bytes}
