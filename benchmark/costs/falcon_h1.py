"""Operations and bytes of the Falcon-H1 decoder-only recogniser as it
is SERVED (configuration ``falcon_h1_34b``), computed from shapes.

Conventions as in ``costs/axk1.py`` (a matmul [m,k]x[k,n] is 2*m*k*n
operations; element-wise work, norms, gates, the convolution's four
taps, exponentials and decays are left out; padded positions and a
finished stream's idle slot count for nothing; bytes count once). What
is new is the state-space mixer:

  the sequence form (``ssd_chunk_scan``) is counted at what the
  chunked algorithm NEEDS on valid positions: inside a chunk the
  causal half of ``C B^T`` (once a GROUP: the heads of a group share B
  and C) and of its product with x, and a valid position's read of the
  carried state (``C h``) and its write to it (``x B^T``), per head;
  the decode form (``ssd_state_step``) is bound by bytes: a live
  (stream, layer)'s float32 state read once and written once.

``model`` is anything with the fields of the program's ``ModelConfig``.
"""

from __future__ import annotations

from benchmark.costs.axk1 import (  # noqa: F401  (shared)
    DOT_BYTES, prefix_positions, roofline_seconds)
from benchmark.costs.trinity import (  # noqa: F401  (shared)
    attention_params, cache_row_bytes, head_dim)

STATE_BYTES = 4  # the recurrent state is float32


def conv_channels(model) -> int:
    """x, B and C: what the depthwise convolution reads."""
    return model.ssm_d_ssm + 2 * model.ssm_groups * model.ssm_state


def mixer_matrices(model) -> int:
    """The mixer's two projections: ``[z | x | B | C | dt]`` and out."""
    d = model.ssm_d_ssm
    return (model.lfm_hidden * (d + conv_channels(model) + model.ssm_heads)
            + d * model.lfm_hidden)


def mixer_params(model) -> int:
    """... and its filter and bias, ``dt_bias``, ``A_log``, ``D``, the
    gated norm's gain."""
    return (mixer_matrices(model)
            + (model.ssm_conv + 1) * conv_channels(model)
            + 3 * model.ssm_heads + model.ssm_d_ssm)


def mlp_params(model) -> int:
    return 3 * model.lfm_hidden * model.lfm_ffn_dim


def layer_params(model) -> int:
    """One layer: attention, mixer, MLP and its two norms."""
    return (attention_params(model) + mixer_params(model)
            + mlp_params(model) + 2 * model.lfm_hidden)


def position_params(model) -> int:
    """Matrix parameters every valid position passes through, all
    layers."""
    return len(model.lfm_layer_types) * (
        attention_params(model) + mixer_matrices(model)
        + mlp_params(model))


def parameters(model, num_features: int = 161) -> int:
    """Every parameter held on the chip."""
    d = model.lfm_hidden
    heads = (1 if model.lm_tied_head else 2) * model.vocab_size * d
    return (heads + num_features * model.frame_stack * d + d
            + len(model.lfm_layer_types) * layer_params(model))


def state_bytes(model) -> int:
    """One (stream, layer)'s recurrent state."""
    return STATE_BYTES * model.ssm_d_ssm * model.ssm_state


def cache_bytes(model, streams: int, cache_rows: int) -> dict:
    """The cache of ``streams`` streams by part: the state, keys and
    values, the convolution's last inputs."""
    layers = len(model.lfm_layer_types)
    return {"state": streams * layers * state_bytes(model),
            "rows": streams * layers * cache_rows * cache_row_bytes(model),
            "conv": streams * layers * (model.ssm_conv - 1)
            * conv_channels(model) * DOT_BYTES}


def mixing_flops(model, positions: int, start: int = 0) -> int:
    """Attention's q k^T and probabilities times v of the positions
    ``start .. positions - 1`` of one causal sequence, all layers."""
    n, s = int(positions), int(start)
    return len(model.lfm_layer_types) * 4 * model.lfm_heads \
        * head_dim(model) * (n * (n + 1) // 2 - s * (s + 1) // 2)


def scan_flops(model, positions: int) -> int:
    """What ``ssd_chunk_scan`` needs for ONE sequence of ``positions``
    valid positions in ONE layer: over its chunks of ``ssm_chunk``, the
    causal half of ``C B^T`` a group and of ``(C B^T o L) x`` a head,
    and a position's read of and write to the carried state a head."""
    q, n = model.ssm_chunk, model.ssm_state
    p = model.ssm_d_ssm // model.ssm_heads
    whole, rest = divmod(int(positions), q)
    pairs = whole * q * (q + 1) // 2 + rest * (rest + 1) // 2
    return (2 * pairs * (model.ssm_groups * n + model.ssm_heads * p)
            + 2 * 2 * int(positions) * model.ssm_heads * n * p)


def scan_bytes(model, positions: int) -> int:
    """... and the bytes it must move: x in and y out, B and C, dt, and
    the state given out."""
    act = int(positions) * DOT_BYTES * (
        2 * model.ssm_d_ssm + 2 * model.ssm_groups * model.ssm_state)
    return act + 4 * int(positions) * model.ssm_heads + state_bytes(model)


def step_bytes(model) -> int:
    """What ``ssd_state_step`` must move for ONE live stream in ONE
    layer: the state read once and written once (its inputs, 20 kB, are
    left out)."""
    return 2 * state_bytes(model)


def stream_flops(model, frames: int, steps: int,
                 num_features: int = 161) -> int:
    """Operations one stream needs: ``a`` prefix positions through the
    prefix projection and the layers (the mixer's sequence form over
    them), ``steps`` decoded positions through embedding, layers (a
    state update of ``4 heads state head`` each) and head."""
    a = prefix_positions(model, frames)
    s = a + int(steps)
    layers = len(model.lfm_layer_types)
    return (a * 2 * num_features * model.frame_stack * model.lfm_hidden
            + s * 2 * position_params(model) + mixing_flops(model, s)
            + layers * (scan_flops(model, a)
                        + int(steps) * 4 * model.ssm_d_ssm * model.ssm_state)
            + int(steps) * 2 * model.lfm_hidden * model.vocab_size)


def call_flops_valid(model, valid_frames, steps,
                     num_features: int = 161) -> int:
    """Operations one served call NEEDS: every stream at its own frames
    and decoded steps."""
    return sum(stream_flops(model, t, u, num_features)
               for t, u in zip(valid_frames, steps))


def prefill_scan_cost(model, valid_frames) -> tuple:
    """``(flops, bytes)`` the mixers of all layers need for the prefix
    positions of a call's streams."""
    layers = len(model.lfm_layer_types)
    lens = [prefix_positions(model, t) for t in valid_frames]
    return (layers * sum(scan_flops(model, a) for a in lens),
            layers * sum(scan_bytes(model, a) for a in lens))


def decode_step_bytes(model, live: float, rows_attended: float) -> dict:
    """HBM bytes one decode step needs, by part, whatever implements
    them: every layer's weights and the head once; the state of the
    ``live`` (stream, layer) pairs read once and written once; the
    ``rows_attended`` cache rows in reach (over streams and layers).
    The batch's activations and the logits are left out."""
    return {"weights": DOT_BYTES * len(model.lfm_layer_types)
            * layer_params(model),
            "head": DOT_BYTES * model.lfm_hidden * model.vocab_size,
            "state": live * step_bytes(model),
            "rows": rows_attended * cache_row_bytes(model)}


def training_floor_bytes(model, layers: int = 4, vocab_share: int = 8,
                         bytes_per_param: int = 16) -> int:
    """What TRAINING the guide's floor of this block would hold:
    ``layers`` layers and a ``vocab_share``-th of the vocabulary, at
    weights + gradients + Adam's state."""
    rows = model.vocab_size // vocab_share
    return bytes_per_param * (layers * layer_params(model)
                              + 2 * rows * model.lfm_hidden)
