"""Operations and bytes of the Xing4.0 decoder-only recogniser as it is
SERVED with self-drafting (configuration ``xing4_29b_a4b``), computed
from shapes.

Conventions as in ``costs/axk1.py``, whose counts of latent attention,
of one expert and of attention's mixing are used as they are. What a
call NEEDS is what plain greedy decoding needs: every stream's valid
prefix positions and EMITTED tokens through the model, and the
(position, expert) pairs their routing made. Padding, a finished
stream's idle slot, a rejected draft's verify position and the whole
draft pass (the module over the prefix and in every step, its second
reading of the head) count for nothing in operations: drafting is a way
to take fewer steps, not work the transcript asks for. BYTES are
counted for the step the loop is (a step that drafts reads the module's
weights and the head a second time, whatever it accepts), since a
step's time is what its share of the memory roofline is about.

The hyper-connections add one product a sub-layer, [n*D] x [n*D,
n*(n+2)], and three mixes over the n streams; the mixes are
element-wise and left out of the operations, and are what
:func:`mhc_bytes` counts.

``model`` is anything with the fields of the program's ``ModelConfig``.
"""

from __future__ import annotations

from benchmark.costs.axk1 import (  # noqa: F401  (shared)
    DOT_BYTES, attention_params, expert_params, gmm_call_cost,
    mixing_flops, prefix_positions, roofline_seconds, sparse_layers)
from benchmark.costs import axk1


def hc_params(model) -> int:
    """One sub-layer's hyper-connection: phi and the norm's gain."""
    n, d = model.hc_streams, model.lfm_hidden
    return n * d * n * (n + 2) + n * d


def sub_layers(model) -> int:
    """Sub-layers under a hyper-connection: two a layer, the draft
    modules' included."""
    return 2 * (len(model.lfm_layer_types) + model.lm_draft_layers)


def position_params(model) -> int:
    """Parameters every valid position of the MODEL passes through
    without its routed experts (``costs/axk1.position_params``) and its
    layers' hyper-connections."""
    return (axk1.position_params(model)
            + 2 * len(model.lfm_layer_types) * hc_params(model))


def draft_params(model) -> int:
    """Parameters a position of ONE draft module passes through
    without its routed experts: the joining projection, latent
    attention, router, shared expert and two hyper-connections."""
    d = model.lfm_hidden
    return (2 * d * d + attention_params(model) + d * model.lfm_experts
            + model.moe_shared_experts * expert_params(model)
            + 2 * hc_params(model))


def parameters(model, num_features: int = 161) -> int:
    """Every parameter held on the chip (norm gains, the selection
    bias and the hyper-connections' scalars left out)."""
    d = model.lfm_hidden
    routed = model.experts_held * expert_params(model)
    heads = (1 if model.lm_tied_head else 2) * model.vocab_size * d
    return (heads + num_features * model.frame_stack * d
            + position_params(model) + sparse_layers(model) * routed
            + model.lm_draft_layers * (draft_params(model) + routed))


def stream_flops(model, frames: int, steps: int,
                 num_features: int = 161) -> int:
    """Operations one stream needs without its routed experts: ``a``
    prefix positions through the prefix projection and the model's
    layers, ``steps`` emitted tokens through embedding, layers and
    head."""
    a = prefix_positions(model, frames)
    s = a + int(steps)
    return (a * 2 * num_features * model.frame_stack * model.lfm_hidden
            + s * 2 * position_params(model) + mixing_flops(model, s)
            + int(steps) * 2 * model.lfm_hidden * model.vocab_size)


def call_flops_valid(model, valid_frames, steps, pairs: int,
                     num_features: int = 161) -> int:
    """Operations one served call NEEDS: every stream at its own frames
    and emitted tokens, and the ``pairs`` (position, expert) pairs of
    the MODEL's expert layers (prefill and decode; the module's are the
    draft pass)."""
    return (sum(stream_flops(model, t, u, num_features)
                for t, u in zip(valid_frames, steps))
            + int(pairs) * 2 * expert_params(model))


def decode_step_bytes(model, experts_hit: float, cache_rows: float
                      ) -> float:
    """HBM bytes one drafting step needs: every weight a position of
    the model and of the module passes through, the head twice (the
    model's logits and the module's), the matrices of the
    ``experts_hit`` experts (over all expert layers, the module's
    included) that received a pair, and the ``cache_rows`` rows (over
    all streams, per array) its attention reads in every array of the
    cache, each once. The batch's activations are left out."""
    drafts = model.lm_draft_layers
    weights = (position_params(model) + drafts * draft_params(model)
               + (1 + drafts) * model.lfm_hidden * model.vocab_size
               + experts_hit * expert_params(model))
    row = model.mla_kv_rank + model.mla_rope_dim
    arrays = len(model.lfm_layer_types) + drafts
    return DOT_BYTES * (weights + arrays * cache_rows * row)


def mhc_bytes(model, positions: float) -> float:
    """HBM bytes the hyper-connections of ``positions`` valid positions
    need: each sub-layer reads the n streams once and writes them once
    (the coefficients are a few values a position)."""
    return (sub_layers(model) * positions * 2 * DOT_BYTES
            * model.hc_streams * model.lfm_hidden)
