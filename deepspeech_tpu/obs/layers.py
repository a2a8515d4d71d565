"""Device time under the program's own layer names.

The device trace names an event by its HLO instruction (``%fusion.638``)
and knows nothing of the model. The program does: every instruction of a
program it compiled carries ``metadata={op_name="jit(step_fn)/
transpose(jvp(DeepSpeech2))/rnn/rnn0/wx/dot_general"}``, flax's module
path plus the ``jax.named_scope`` s the code opens and the direction.
This module turns that into a table a reader of the trace can join:

  LAYERS                      the closed vocabulary of device layers
  layer_of(op_name)           -> (layer, direction), by the path's
                                 segments, never by a shape
  instruction_scopes(text)    -> {instruction: Scope(op_name, shape,
                                 opcode)} of an optimised HLO module,
                                 keyed as the trace names its events
  watch / programs / reset    which programs this process dispatched
                                 while the tracer was on, and their
                                 tables when a reader asks

``watch`` keeps a program's jitted function (until its table is
resolved, or ``reset``) and the ABSTRACT values of
its arguments (shape, dtype, weak type, the sharding of a committed
array: no device buffer) the first time it is dispatched with the
tracer on. ``programs()`` resolves each with
``jitted.lower(*abstract).compile().as_text()`` when first asked, after
the window: jax memoises the lowering and its executable, so that is the
executable that ran (its instruction names are the trace's) and nothing
is traced, lowered or compiled again. With the tracer off ``watch`` is
never reached: its call sites sit behind the ``obs.tracer.enabled`` reads
that already guard ``train.wait`` and ``infer.prefill.wait``.

Readers: ``benchmark/layer_metrics/_layers.py`` (a traced run's
``op_seconds`` by layer) and ``tools/layer_sums.py`` (a kept trace and an
HLO text, for an operator).
"""

from __future__ import annotations

import re
import time
from typing import Dict, NamedTuple, Tuple

LAYERS = (
    "conv_frontend", "rnn_wx", "rnn_scan", "rnn_dw_h", "norm", "head",
    "ctc_loss", "rnnt_joint", "rnnt_lattice", "optimizer", "grad_norm",
    "attention", "latent_attention", "attn_out", "short_conv",
    "ssm_mixer", "sparse_select", "sparse_attention", "linear_attention",
    "mlp", "moe_route", "moe_dispatch", "moe_gmm",
    "moe_combine", "moe_shared", "mhc", "lm_head", "embed", "draft",
    "cache_update", "collective",
)
UNNAMED = "(unnamed)"
DIRECTIONS = ("fwd", "bwd", "recompute")

# A segment of an op_name's path (a flax module's name, a method scope
# or a ``jax.named_scope``) -> the layer it opens. The path is read left
# to right and the innermost segment that names a layer wins, but
# nothing inside a SEALED layer is looked at: the conv frontend's batch
# norms are the frontend's, the optimizer's products the optimizer's.
_SEGMENTS = {
    # scopes the code opens (jax.named_scope)
    "optimizer": "optimizer", "grad_norm": "grad_norm",
    "ctc_loss": "ctc_loss", "rnnt_joint": "rnnt_joint",
    "rnnt_lattice": "rnnt_lattice", "rnn_scan": "rnn_scan",
    "dw_h": "rnn_dw_h", "moe_route": "moe_route",
    "moe_route_pre_attn": "moe_route", "moe_dispatch": "moe_dispatch",
    "moe_gmm": "moe_gmm", "moe_combine": "moe_combine",
    "moe_shared": "moe_shared", "attn_out": "attn_out",
    "latent_attention": "latent_attention", "lm_head": "lm_head",
    "embed": "embed", "cache_update": "cache_update",
    "gqa_attn_window": "attention", "gqa_attn_global": "attention",
    "mhc": "mhc", "ssm_mixer": "ssm_mixer", "ssd_scan": "ssm_mixer",
    "ssd_step": "ssm_mixer", "mtp_draft": "draft",
    "sparse_select": "sparse_select", "sparse_attention": "sparse_attention",
    "linear_attention": "linear_attention",
    # flax modules and their method scopes
    "head": "head", "bn": "norm", "bn_out": "norm", "op_norm": "norm",
    "ffn_norm": "norm", "op_post_norm": "norm", "ffn_post_norm": "norm",
    "out_norm": "norm", "embed_norm": "norm", "hidden_norm": "norm",
    "ln": "norm", "wx": "rnn_wx", "moe.route": "moe_route",
    "router": "moe_route", "moe": "moe_dispatch", "ffn": "mlp",
    "mixer": "ssm_mixer", "sparse": "sparse_attention",
    "lin": "linear_attention", "prefix": "embed", "joint": "rnnt_joint",
    "op_hc": "mhc", "ffn_hc": "mhc", "eh_proj": "draft",
    "lookahead": "conv_frontend",
}
_NUMBERED = (
    (re.compile(r"^(rnn|lstmp)\d+$"), "rnn_scan"),
    (re.compile(r"^draft\d+$"), "draft"),
)
_SEALED = frozenset({
    "conv_frontend", "optimizer", "grad_norm", "ctc_loss", "rnnt_lattice",
    "rnn_dw_h", "rnn_wx", "norm", "moe_route", "moe_shared", "moe_gmm",
    "moe_combine", "attn_out", "ssm_mixer", "mhc", "lm_head",
    "cache_update"})
_MIXERS = ("attention", "latent_attention", "sparse_attention",
           "sparse_select", "linear_attention")
_LATENT_WEIGHTS = ("q_a", "q_b", "kv_a", "kv_b", "kv_norm")
_DECODER_LAYER = re.compile(r"^layer\d*$")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all")
_BRACKET = re.compile(r"\[\\?'([^'\\\]]+)\\?'\]")
_SPLIT = re.compile(r"[/()]+")


def check(layer: str) -> str:
    """``layer`` if it is one of :data:`LAYERS`; else it raises."""
    if layer not in LAYERS:
        raise ValueError(f"{layer!r} is not a layer of obs.layers.LAYERS")
    return layer


def _segments(path: str) -> list:
    """The path's segments. An argument's own name
    (``state.params['rnn']['rnn0']['wx']['kernel']``: a layout copy or a
    cast of it) reads as the path of the module that owns it."""
    if "[" in path and "/" not in path:
        keys = _BRACKET.findall(path)
        return (["optimizer"] if "opt_state" in path else []) + keys
    return [s for s in _SPLIT.split(path) if s]


def _one(path: str) -> Tuple[str, str]:
    segments = _segments(path)
    direction = ("recompute" if "rematted_computation" in segments
                 else "bwd" if "transpose(" in path else "fwd")
    layer, decoder = None, False
    for seg in segments:
        if layer in _SEALED:
            break
        new = _SEGMENTS.get(seg)
        if seg == "conv":  # DS2's frontend, or a decoder layer's operator
            new = "short_conv" if decoder else "conv_frontend"
        elif seg == "attn":  # one module name, two kinds: told by scope
            new = layer if layer == "latent_attention" else "attention"
        elif seg in ("w13", "w2") and layer == "moe_dispatch":
            new = "moe_gmm"  # the experts' matrices, by their names
        elif seg in _LATENT_WEIGHTS and layer == "attention":
            new = "latent_attention"  # an argument's path has no scope
        elif seg == "o" and layer in _MIXERS:
            new = "attn_out"
        elif seg in ("ssd_scan", "ssd_step") and layer == "linear_attention":
            new = layer  # the recurrence is the layer's, not a mixer's
        elif new is None:
            decoder = decoder or bool(_DECODER_LAYER.match(seg))
            new = next((to for rx, to in _NUMBERED if rx.match(seg)), None)
        if new is not None:
            layer = new
    return (check(layer) if layer else UNNAMED), direction


def layer_of(op_name: str) -> Tuple[str, str]:
    """``(layer, direction)`` of an instruction's ``op_name``: a name of
    :data:`LAYERS` or ``"(unnamed)"``, and ``fwd``, ``bwd`` (the path
    holds a ``transpose(``) or ``recompute`` (a rematerialised forward
    inside the backward). A fusion that merged several paths (joined by
    ``;``) is the first of them that has a name."""
    first = None
    for path in op_name.split(";"):
        found = _one(path)
        if found[0] != UNNAMED:
            return found
        first = first or found
    return first


def layer_of_instruction(opcode: str, op_name: str) -> Tuple[str, str]:
    """:func:`layer_of`, but a collective instruction is ``collective``
    wherever the partitioner put it."""
    layer, direction = layer_of(op_name)
    if opcode.replace("-start", "").replace("-done", "") in _COLLECTIVES:
        return "collective", direction
    return layer, direction


class Scope(NamedTuple):
    op_name: str
    shape: str      # the result's, as the module prints it
    opcode: str


_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_OPCODE = re.compile(r"\s*([\w\-]+)\(")


def _result_shape(rest: str) -> Tuple[str, str]:
    """(shape, what follows it) of an instruction's text after `` = ``;
    a tuple's shape runs to its closing parenthesis."""
    if not rest.startswith("("):
        shape, _, tail = rest.partition(" ")
        return shape, " " + tail
    depth = 0
    for i, ch in enumerate(rest):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            return rest[:i + 1], rest[i + 1:]
    return rest, ""


_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?(%?[\w.\-]+)$")


def instruction_scopes(hlo_text: str) -> Dict[str, Scope]:
    """``{instruction: Scope(op_name, result shape, opcode)}`` over
    every computation of an optimised module's text (the entry, ``while``
    bodies, called and fused computations), keyed as the device trace
    names its events (``%fusion.638``). An instruction without
    ``metadata`` has the empty ``op_name``. A Mosaic call's text runs
    over several lines (its ``kernel_metadata``, one fact a line, comes
    before its ``metadata``): they are read as one."""
    out = {}
    name = shape = opcode = None
    tail = []

    def close():
        if name is not None:
            op_name = _OP_NAME.search("".join(tail))
            out[name] = Scope(op_name.group(1) if op_name else "", shape,
                              opcode)

    for line in hlo_text.splitlines():
        head, sep, rest = line.partition(" = ")
        starts = _INSTRUCTION.match(head) if sep else None
        if starts is None:
            if line[:1] in (" ", '"') or line.startswith("}}"):
                tail.append(line)     # more of the instruction before
            else:
                close()               # a computation's first or last line
                name = None
            continue
        close()
        name = starts.group(1)
        if not name.startswith("%"):
            name = "%" + name
        shape, rest = _result_shape(rest)
        found = _OPCODE.match(rest)
        if found is None:
            name = None
            continue
        opcode, tail = found.group(1), [rest]
    close()
    return out


# -- the programs this process dispatched ------------------------------------

class Program(NamedTuple):
    name: str
    scopes: Dict[str, Scope]
    seconds: float          # what resolving it took, after the window


_watched: dict = {}         # name -> (jitted, abstract args)
_resolved: Dict[str, Program] = {}


def _abstract(x):
    """A ``jax.Array``'s shape, dtype and weak type, and its sharding
    where it is committed to one (an uncommitted array lowers as jit
    itself places it); anything else (a Python or numpy scalar, None) as
    it is."""
    import jax

    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, weak_type=x.weak_type,
            sharding=x.sharding if x.committed else None)
    if hasattr(x, "shape") and hasattr(x, "dtype") and x.shape:
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    return x


def watch(program: str, jitted, args: tuple) -> None:
    """Remember ``program`` (a jitted function and the arguments of one
    of its calls: donated ones may be given as the call's results of the
    same shapes) the first time it is seen. Call sites reach this only
    with the tracer on."""
    if program in _watched or program in _resolved:
        return
    import jax

    _watched[program] = (jitted, jax.tree.map(_abstract, args))


def programs() -> Dict[str, Program]:
    """The watched programs' tables, each resolved when first asked
    for: the compiled module's text of the executable that ran."""
    while _watched:  # a resolved program's function is let go
        name, (jitted, args) = _watched.popitem()
        t0 = time.perf_counter()
        text = jitted.lower(*args).compile().as_text()
        _resolved[name] = Program(name, instruction_scopes(text),
                                  time.perf_counter() - t0)
    return dict(_resolved)


def reset() -> None:
    """Forget every watched program and table."""
    _watched.clear()
    _resolved.clear()
