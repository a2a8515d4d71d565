"""Operations and bytes of the MiniCPM-SALA decoder-only recogniser as
it is SERVED (configuration ``minicpm_sala``), computed from shapes.

Conventions as in ``costs/axk1.py`` (a matmul [m,k]x[k,n] is 2*m*k*n
operations; element-wise work, norms, gates, rotations, exponentials
and decays, top-k and sorting are left out; padded positions and a
finished stream's idle slot count for nothing; bytes count once). What
is new are the two mixers:

  the SPARSE layer is counted at the (query, key) pairs its selection
  READS (``rows_selected``: every row up to ``sparse_dense_len`` rows of
  a call's sequence, else the rows ``<= t`` of the first block, the
  local window's blocks and the top-k ones), ``4 x heads x head``
  operations a pair, and at the (query, pooled key) pairs its scores
  rank, ``2 x heads x head`` each; its decode form is bound by bytes:
  the selected rows, keys and values, and the pooled keys in reach;
  the LINEAR layer's sequence form (``ssd_chunk_scan``) is counted at
  what the chunked algorithm needs on valid positions, a GROUP a head
  (``costs/falcon_h1.scan_flops`` with groups = heads, state = head);
  its decode form (``ssd_state_step``) is bound by bytes: a live
  (stream, layer)'s float32 state read once and written once.

A call's sequences are the served ones: one prefill over the prefix
(``a`` rows; dense where ``a <= sparse_dense_len``) and then single
steps (row t: dense where ``t + 1 <= sparse_dense_len``).

``model`` is anything with the fields of the program's ``ModelConfig``.
"""

from __future__ import annotations

import numpy as np

from benchmark.costs.axk1 import (  # noqa: F401  (shared)
    DOT_BYTES, prefix_positions, roofline_seconds)
from benchmark.costs.trinity import (  # noqa: F401  (shared)
    attention_params, cache_row_bytes, head_dim)

STATE_BYTES = 4  # the recurrent state is float32
SPARSE, LINEAR = "sparse_attention", "linear_attention"


def layers_of(model, kind: str) -> int:
    return sum(k == kind for k in model.lfm_layer_types)


def linear_params(model) -> int:
    """q, k, v, gate and o of one linear-attention layer."""
    return 5 * model.lfm_hidden * model.lin_heads * model.lin_head_dim


def mlp_params(model) -> int:
    return 3 * model.lfm_hidden * model.lfm_ffn_dim


def position_params(model) -> int:
    """Matrix parameters every valid position passes through, all
    layers."""
    return (layers_of(model, SPARSE) * attention_params(model)
            + layers_of(model, LINEAR) * linear_params(model)
            + len(model.lfm_layer_types) * mlp_params(model))


def parameters(model, num_features: int = 161) -> int:
    """Every parameter held on the chip (norm gains left out)."""
    d = model.lfm_hidden
    heads = (1 if model.lm_tied_head else 2) * model.vocab_size * d
    return (heads + num_features * model.frame_stack * d
            + position_params(model))


def state_bytes(model) -> int:
    """One (stream, linear layer)'s recurrent state."""
    return STATE_BYTES * model.lin_heads * model.lin_head_dim ** 2


def pooled_row_bytes(model) -> int:
    """One pooled key of one sparse layer (keys only)."""
    return model.lfm_kv_heads * head_dim(model) * DOT_BYTES


def pooled_windows(model, rows: int) -> int:
    """Whole pooling windows inside ``rows`` rows."""
    return max((int(rows) - model.sparse_kernel) // model.sparse_stride + 1,
               0)


def cache_bytes(model, streams: int, cache_rows: int) -> dict:
    """The cache of ``streams`` streams by part: the linear layers'
    states, the sparse layers' keys and values, their pooled keys (as
    allocated: a multiple of 8 windows)."""
    held = -(-cache_rows // model.sparse_block) * model.sparse_block
    windows = -(-max(pooled_windows(model, held), 1) // 8) * 8
    sparse = layers_of(model, SPARSE)
    return {"state": streams * layers_of(model, LINEAR) * state_bytes(model),
            "rows": streams * sparse * held * cache_row_bytes(model),
            "pooled": streams * sparse * windows * pooled_row_bytes(model)}


def selected_blocks(model) -> int:
    return (model.sparse_init_blocks
            + model.sparse_window // model.sparse_block + model.sparse_topk)


def rows_selected(model, pos, length=None):
    """Cache rows the query at row ``pos`` (an array) reads in one
    sparse layer, its call's sequence ``length`` rows long (``pos + 1``
    where None: a decode step)."""
    pos = np.asarray(pos, np.int64)
    length = pos + 1 if length is None else np.asarray(length, np.int64)
    block = model.sparse_block
    blocks = np.minimum(pos // block + 1, selected_blocks(model))
    chosen = (blocks - 1) * block + pos % block + 1
    return np.where(length <= model.sparse_dense_len, pos + 1, chosen)


def windows_ranked(model, pos):
    """Pooled keys the query at row ``pos`` scores: the windows whole
    inside ``0 .. pos``."""
    pos = np.asarray(pos, np.int64)
    return np.where(pos >= model.sparse_kernel - 1,
                    (pos + 1 - model.sparse_kernel) // model.sparse_stride
                    + 1, 0)


def stream_rows(model, frames: int, steps: int) -> dict:
    """Of one stream: the (query, key) pairs its prefix queries and its
    steps' queries read in ONE sparse layer, the rows its steps held,
    and the (query, pooled key) pairs ranked (none where the query's
    sequence is dense)."""
    a = prefix_positions(model, frames)
    pre = np.arange(a)
    dec = a + np.arange(int(steps))
    ranked_pre = 0 if a <= model.sparse_dense_len \
        else int(windows_ranked(model, pre).sum())
    past = dec + 1 > model.sparse_dense_len
    return {"prefill_pairs": int(rows_selected(model, pre, a).sum()),
            "decode_pairs": int(rows_selected(model, dec).sum()),
            "decode_held": int((dec + 1).sum()),
            "prefill_ranked": ranked_pre,
            "decode_ranked": int(windows_ranked(model, dec)[past].sum())}


def scan_flops(model, positions: int) -> int:
    """What ``ssd_chunk_scan`` needs for ONE sequence of ``positions``
    valid positions in ONE linear layer: over its chunks, the causal
    half of ``q k^T`` and of its product with v, a head, and a
    position's read of and write to the carried state."""
    q, hd = model.ssm_chunk, model.lin_head_dim
    whole, rest = divmod(int(positions), q)
    pairs = whole * q * (q + 1) // 2 + rest * (rest + 1) // 2
    return model.lin_heads * (2 * pairs * 2 * hd
                              + 2 * 2 * int(positions) * hd * hd)


def scan_bytes(model, positions: int) -> int:
    """... and the bytes it must move: v in and y out, q and k, and the
    state given out."""
    wide = model.lin_heads * model.lin_head_dim
    return int(positions) * DOT_BYTES * 4 * wide + state_bytes(model)


def step_bytes(model) -> int:
    """What ``ssd_state_step`` must move for ONE live stream in ONE
    linear layer: the state read once and written once."""
    return 2 * state_bytes(model)


def stream_flops(model, frames: int, steps: int,
                 num_features: int = 161) -> int:
    """Operations one stream needs: ``a`` prefix positions through the
    prefix projection and the layers, ``steps`` decoded positions
    through embedding, layers (a state update of ``4 heads head^2``
    each) and head; the sparse layers' mixing and ranking over the
    SELECTED pairs only."""
    a = prefix_positions(model, frames)
    s = a + int(steps)
    rows = stream_rows(model, frames, steps)
    wide = model.lfm_heads * head_dim(model)
    sparse = layers_of(model, SPARSE) * (
        4 * wide * (rows["prefill_pairs"] + rows["decode_pairs"])
        + 2 * wide * (rows["prefill_ranked"] + rows["decode_ranked"]))
    linear = layers_of(model, LINEAR) * (
        scan_flops(model, a)
        + int(steps) * 4 * model.lin_heads * model.lin_head_dim ** 2)
    return (a * 2 * num_features * model.frame_stack * model.lfm_hidden
            + s * 2 * position_params(model) + sparse + linear
            + int(steps) * 2 * model.lfm_hidden * model.vocab_size)


def call_flops_valid(model, valid_frames, steps,
                     num_features: int = 161) -> int:
    """Operations one served call NEEDS: every stream at its own frames
    and decoded steps."""
    return sum(stream_flops(model, t, u, num_features)
               for t, u in zip(valid_frames, steps))


def prefill_select_flops(model, valid_frames) -> int:
    """What the sparse layers' sequence form needs for the prefix
    positions of a call's streams: ``4 x heads x head`` a SELECTED
    (query, key) pair."""
    wide = model.lfm_heads * head_dim(model)
    return layers_of(model, SPARSE) * 4 * wide * sum(
        stream_rows(model, t, 0)["prefill_pairs"] for t in valid_frames)


def prefill_scan_cost(model, valid_frames) -> tuple:
    """``(flops, bytes)`` the linear layers need for the prefix
    positions of a call's streams."""
    layers = layers_of(model, LINEAR)
    lens = [prefix_positions(model, t) for t in valid_frames]
    return (layers * sum(scan_flops(model, a) for a in lens),
            layers * sum(scan_bytes(model, a) for a in lens))


def decode_select_bytes(model, rows_read: float) -> float:
    """HBM bytes the decode form under a selection needs: the selected
    rows (``rows_read``: over streams, steps and sparse layers; the
    program's own counter), keys and values, each once."""
    return rows_read * cache_row_bytes(model)


def decode_step_bytes(model, live: float, rows_read: float,
                      windows_read: float) -> dict:
    """HBM bytes one decode step needs, by part, whatever implements
    them: every layer's weights and the head once; the state of the
    ``live`` (stream, linear layer) pairs read once and written once;
    the ``rows_read`` selected cache rows; the ``windows_read`` pooled
    keys ranked. The batch's activations and the logits are left
    out."""
    return {"weights": DOT_BYTES * position_params(model),
            "head": DOT_BYTES * model.lfm_hidden * model.vocab_size,
            "state": live * step_bytes(model),
            "rows": rows_read * cache_row_bytes(model),
            "select": windows_read * pooled_row_bytes(model)}


def training_floor_bytes(model, vocab_share: int = 1,
                         bytes_per_param: int = 16) -> int:
    """What TRAINING this cut (one period) would hold at weights +
    gradients + Adam's state."""
    rows = model.vocab_size // vocab_share
    return bytes_per_param * (position_params(model)
                              + 2 * rows * model.lfm_hidden)
