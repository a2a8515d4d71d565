"""What the readers of device time BY LAYER share (the underscore keeps
``--detail`` from taking this module for a reader).

The trace names a device event by its HLO instruction; the program that
compiled the instruction knows which layer of the model it belongs to
(``deepspeech_tpu/obs/layers.py``: the instruction's ``op_name`` is the
module path plus the ``jax.named_scope`` s the code opens, read by its
segments into a closed vocabulary of layers and a direction). This
module joins the two: ``record["trace"]["op_seconds"]`` (key =
instruction, opcode, result shape: ``reduce/xplane.short_name``) with
the table of the programs the run dispatched while it was traced:

  record["counters"]["layer_table"]   where a driver stores one:
                                      {program: {instruction:
                                      [op_name, shape, opcode]}}
  obs.layers.programs()               else: this process's, resolved
                                      here, after the window, from the
                                      executables that ran (nothing is
                                      lowered or compiled again)

One name per metric across drivers; no shape is looked at but to tell
two programs' instructions of one name apart:

  containers   ``while``, ``conditional`` and ``call`` events span their
               bodies' events, which are events of their own: left out
  (ambiguous)  an instruction name that two watched programs hold with
               the same opcode and shape under DIFFERENT layers
  (unmatched)  an event no watched program holds (an eager operation
               between two programs, a program that was not watched)
  (unnamed)    an instruction whose ``op_name`` is outside every scope

A record without a trace, a program without ``obs/layers.py`` (the
parent of the PR that added it) or a run that watched nothing has no
table: ``by_layer`` is None and every reader returns None. A reader of
one layer is None too where no instruction of the run's programs is in
that layer (the cell's model has none).

The join is kept under ``record["trace"]["layers"]`` (ms a unit and
chip by ``layer.direction``, the Mosaic kernels' share of each layer,
what resolving the tables took), so ``--detail`` writes it out.
"""

import re
from typing import Dict, Optional, Tuple

CONTAINERS = ("while", "conditional", "call")
AMBIGUOUS, UNMATCHED, UNNAMED = "(ambiguous)", "(unmatched)", "(unnamed)"
_LAYOUT = re.compile(r"\{[^}]*\}")
_COMMENT = re.compile(r"/\*.*?\*/|/\*.*$")
_MOSAIC = "[mosaic] "
_LOWER, _COMPILE = "jaxpr_to_mlir_module_duration", "backend_compile_duration"


def _shape(text: str) -> str:
    """A result shape as an ``op_seconds`` key holds it: no layouts, no
    ``/*index=5*/`` marks, no blanks."""
    return _COMMENT.sub("", _LAYOUT.sub("", text)).replace(" ", "")


def parse(key: str) -> Tuple[str, str, str, bool]:
    """(instruction, opcode, shape as far as the key holds it, whether
    it is a Mosaic kernel) of an ``op_seconds`` key."""
    head, _, rest = key.partition(" ")
    opcode, _, shape = rest.partition(" ")
    mosaic = shape.startswith(_MOSAIC)
    return head, opcode, _shape(shape[len(_MOSAIC):] if mosaic else shape), \
        mosaic


def tables(record) -> Tuple[Optional[Dict[str, dict]], dict]:
    """{program: {instruction: (op_name, shape, opcode)}} of the run
    (None where there is none), and what resolving them cost: seconds a
    program and the lowerings and backend compiles jax reported
    meanwhile (0 and 0: the executables that ran were read)."""
    stored = (record.get("counters") or {}).get("layer_table")
    if stored:
        return {p: {k: tuple(v) for k, v in t.items()}
                for p, t in stored.items()}, {}
    try:
        from deepspeech_tpu.obs import layers
    except ImportError:
        return None, {}
    import jax.monitoring

    heard = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, duration, **kw: heard.append(event))
    programs = layers.programs()
    cost = {name: p.seconds for name, p in programs.items()}
    cost["lowerings"] = sum(e.endswith(_LOWER) for e in heard)
    cost["compiles"] = sum(e.endswith(_COMPILE) for e in heard)
    return {name: p.scopes for name, p in programs.items()} or None, cost


def layer_of(key: str, programs: Dict[str, dict]
             ) -> Optional[Tuple[str, str]]:
    """(layer, direction) of one ``op_seconds`` key; None for a
    container."""
    from deepspeech_tpu.obs.layers import layer_of_instruction

    head, opcode, shape, _ = parse(key)
    if opcode in CONTAINERS:
        return None
    found = set()
    for scopes in programs.values():
        scope = scopes.get(head)
        if scope is None:
            continue
        op_name, full, code = scope
        if code == opcode and _shape(full).startswith(shape):
            found.add(layer_of_instruction(code, op_name))
    if not found:
        return UNMATCHED, "fwd"
    if len({layer for layer, _ in found}) > 1:
        return AMBIGUOUS, "fwd"
    return sorted(found)[0]


def by_layer(record) -> Optional[dict]:
    """The join, made once a record: ms a unit and chip by
    ``layer.direction`` (``ms``), of which in Mosaic kernels by layer
    (``mosaic_ms``), all leaf time (``leaf_ms``), the layers the run's
    programs hold at all (``layers``), the seconds the tables took
    (``table_s``) and the largest events outside every layer
    (``top_outside``: ms, why, key)."""
    tr = record.get("trace")
    if tr is None or not record.get("units"):
        return None
    if "layers" in tr:
        return tr["layers"]
    tr["layers"] = None
    programs, table_s = tables(record)
    if not programs:
        return None
    from deepspeech_tpu.obs import layers

    ms, mosaic, outside, leaf = {}, {}, [], 0.0
    per_unit = 1e3 / record["units"]
    for key, seconds in tr["op_seconds"].items():
        where = layer_of(key, programs)
        if where is None:
            continue
        name = ".".join(where)
        ms[name] = ms.get(name, 0.0) + seconds * per_unit
        leaf += seconds * per_unit
        if where[0] in (AMBIGUOUS, UNMATCHED, UNNAMED):
            outside.append([seconds * per_unit, where[0], key])
        if _MOSAIC in key:
            mosaic[where[0]] = mosaic.get(where[0], 0.0) \
                + seconds * per_unit
    held = sorted({layers.layer_of_instruction(code, op_name)[0]
                   for scopes in programs.values()
                   for op_name, _, code in scopes.values()})
    tr["layers"] = {
        "ms": dict(sorted(ms.items(), key=lambda kv: -kv[1])),
        "mosaic_ms": mosaic, "leaf_ms": leaf, "layers": held,
        "table_s": table_s, "top_outside": sorted(outside, reverse=True)[:12]}
    return tr["layers"]


def ms(record, names, directions=None) -> Optional[float]:
    """Milliseconds a unit and chip in the layers ``names`` (all
    ``directions`` or the given ones); None where the record has no
    table or its programs none of these layers."""
    joined = by_layer(record)
    if joined is None:
        return None
    from deepspeech_tpu.obs import layers

    names = [layers.check(n) for n in names]   # an unknown name raises
    if not set(names) & set(joined["layers"]):
        return None
    return sum(v for k, v in joined["ms"].items()
               if k.rsplit(".", 1)[0] in names
               and (directions is None
                    or k.rsplit(".", 1)[1] in directions))


def named_pct(record) -> Optional[float]:
    """Leaf device time the table puts under a layer's name, in % of
    all leaf device time."""
    joined = by_layer(record)
    if joined is None or not joined["leaf_ms"]:
        return None
    out = (AMBIGUOUS, UNMATCHED, UNNAMED)
    named = sum(v for k, v in joined["ms"].items()
                if k.rsplit(".", 1)[0] not in out)
    return 100.0 * named / joined["leaf_ms"]
