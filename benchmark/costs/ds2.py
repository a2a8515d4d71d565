"""Operations and bytes of the DS2 family, computed from shapes.

The arithmetic half is a copy of ``deepspeech_tpu/utils/flops.py``
(conv frontend, recurrent stack, head), kept here so that no later PR
can move the yardstick; ``benchmark/tests`` holds the two equal on every
preset. The bytes half is new: what the recurrent scan kernels of
``ops/rnn_pallas.py`` have to move between HBM and the core, from their
block specs.

Conventions: a matmul [m,k]x[k,n] is 2*m*k*n operations; backward is
twice forward for every matmul and conv, so a training step is three
forwards; elementwise work and the CTC recursion are left out (under 1%
at every preset). ``model`` is anything with the fields of the
program's ``ModelConfig`` (duck-typed, so a plain namespace built from a
configuration file works as well).
"""

from __future__ import annotations

# ops/rnn_pallas.py: weights stay in VMEM up to this many bytes,
# above it the kernel streams them from HBM every time step.
VMEM_WEIGHT_BUDGET = 10 * 1024 * 1024
LANE = 128


def conv_frontend_flops(model, frames: int, num_features: int = 161):
    """(operations, out_frames, out_features) of the conv stack for one
    utterance: out_len = ceil(T/stride), each output element costs
    2*kt*kf*C_in."""
    t, f, c_in, flops = frames, num_features, 1, 0
    for (kt, kf, st, sf), c_out in zip(model.conv_layers,
                                       model.conv_channels):
        t = -(-t // st)
        f = -(-f // sf)
        flops += 2 * t * f * c_out * kt * kf * c_in
        c_in = c_out
    return flops, t, f * c_in


def n_gates(model) -> int:
    return 4 if model.rnn_type == "lstm" else 3


def n_dirs(model) -> int:
    return 2 if model.bidirectional else 1


def rnn_stack_flops(model, t: int, d_in: int) -> int:
    """Forward operations of the recurrent stack for one utterance of
    ``t`` post-conv frames: per layer and direction the hoisted input
    projection [t,d]x[d,gH] plus the recurrent [1,H]x[H,gH] per step.
    Directions are summed, so layers after the first see width H."""
    g, h = n_gates(model), model.rnn_hidden
    flops, d = 0, d_in
    for _ in range(model.rnn_layers):
        flops += n_dirs(model) * (2 * t * d * g * h + 2 * t * h * g * h)
        d = h
    return flops


def forward_flops(model, frames: int, num_features: int = 161) -> int:
    """Forward operations for one utterance of ``frames`` raw frames."""
    conv, t, d = conv_frontend_flops(model, frames, num_features)
    fwd = conv + rnn_stack_flops(model, t, d)
    if model.lookahead_context > 0:
        fwd += 2 * t * model.rnn_hidden * model.lookahead_context
    fwd += 2 * t * model.rnn_hidden * model.vocab_size
    return fwd


def ds2_step_flops(model, batch: int, frames: int,
                   num_features: int = 161) -> int:
    """One training step (forward + backward) at ``batch`` utterances
    of ``frames`` frames each."""
    return 3 * forward_flops(model, frames, num_features) * batch


def train_flops_valid(model, valid_frames, num_features: int = 161) -> int:
    """Forward + backward operations a step NEEDS: every utterance at
    its own valid length, so padding to the bucket counts for nothing."""
    return 3 * sum(forward_flops(model, int(t), num_features)
                   for t in valid_frames)


# -- recurrent scan kernels: operations and bytes -----------------------

def scan_is_blocked(model, dot_bytes: int = 2) -> bool:
    """Whether one direction's recurrent matrix misses the VMEM budget
    (the program's ``fits_vmem``): then it is streamed every step."""
    h = model.rnn_hidden
    return h * n_gates(model) * h * dot_bytes > VMEM_WEIGHT_BUDGET


def _padded_cols(cols: int) -> int:
    return -(-cols // LANE) * LANE


def gru_scan_cost(model, batch: int, steps: int, *, backward: bool,
                  dot_bytes: int = 2) -> dict:
    """Operations and HBM bytes of ONE direction of ONE layer's scan
    kernel over ``steps`` time steps at ``batch`` rows.

    Forward, per step: the recurrent matmul 2*b*H*3H; reads xproj
    [b,3H] f32 and the mask [b] f32, writes h [b,H] f32. Backward, per
    step: the gate recompute and dgates @ W^T, 4*b*H*3H; reads xproj,
    mask, h_prev [b,H], dy [b,H], writes dxproj and dgates [b,3H] f32.
    The dW contraction is an XLA einsum outside the kernel and is not
    counted here.

    ``bytes`` is what the call NEEDS: every operand read once, every
    result written once — the recurrent matrix [H,3H] (padded to the
    lane width, in the dot type) and its bias once per call, which is
    what the roofline is taken against. ``restream_bytes`` is what the
    blocked kernel would move if its weight blocks came from HBM at
    every time step (ROADMAP S3's hypothesis); the first traces showed
    the forward scan running 2.5 times faster than that allows, so the
    weights are not coming from HBM per step (PERF.md, PR 22).
    """
    g, h, b = n_gates(model), model.rnn_hidden, batch
    w_bytes = h * _padded_cols(g * h) * dot_bytes + _padded_cols(g * h) * 4
    blocked = scan_is_blocked(model, dot_bytes)
    if backward:
        flops = 4 * b * h * g * h
        act = (b * g * h + b + 2 * b * h + 2 * b * g * h) * 4
    else:
        flops = 2 * b * h * g * h
        act = (b * g * h + b + b * h) * 4
    return {"flops": flops * steps, "bytes": act * steps + w_bytes,
            "weight_bytes": w_bytes, "blocked": blocked,
            "restream_bytes": act * steps
            + w_bytes * (steps if blocked else 1)}


def roofline_seconds(cost: dict, peak_flops: float, peak_bytes: float
                     ) -> tuple:
    """(least seconds the chip could take, which peak bounds it)."""
    t_f = cost["flops"] / peak_flops
    t_b = cost["bytes"] / peak_bytes
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")
