"""Shared plumbing for the AOT-oracle tools (aot_tpu / aot_kernels /
aot_multichip): v5e topology env, stderr logging, and the HLO
collective counter — one copy so the three tools cannot drift."""

from __future__ import annotations

import os
import re
import sys

# The tools run as plain scripts (`python tools/aot_*.py`): make the
# checkout importable without PYTHONPATH.
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "collective-permute", "all-to-all")


# What libtpu needs in the environment to describe a topology with no
# chip attached (tests/test_tpu_compile.py sets the same through
# monkeypatch).
AOT_ENV = {"TPU_ACCELERATOR_TYPE": "v5litepod-1",
           "TPU_WORKER_HOSTNAMES": "localhost",
           "TPU_SKIP_MDS_QUERY": "1"}


def setup_aot_env() -> None:
    """libtpu topology construction needs these before jax import."""
    for key, value in AOT_ENV.items():
        os.environ.setdefault(key, value)


def log(tag: str, msg: str) -> None:
    print(f"[{tag}] {msg}", file=sys.stderr, flush=True)


def count_collectives(hlo: str, keep_zero: bool = True) -> dict:
    """Count op DEFINITIONS (an op name followed by its operand list),
    not textual mentions — value-name references (%all-reduce.5) and
    async -done halves would otherwise inflate the counts. The left
    anchor keeps a hyphenated superstring op (ragged-all-to-all) from
    counting as its suffix (all-to-all)."""
    out = {}
    for op in COLLECTIVE_OPS:
        n = len(re.findall(rf"(?<![-\w]){op}(?:-start)?\(", hlo))
        if n or keep_zero:
            out[op] = n
    return out


def cycles_by_op(hlo: str) -> dict:
    """``{op_name: [cycles, instructions]}`` over the optimized HLO's
    instructions that carry the TPU compiler's own
    ``"estimated_cycles"`` (fusions, copies, pads: what the device
    runs), keyed by the ``op_name`` of their metadata, i.e. the line of
    the program they come from (``""`` for those without). An estimate
    is not a time: on a v5e the convolution fusions of ``ds2_full`` ran
    within 12% of it at 1.5 GHz, fusions with a reduction epilogue up
    to 3.4 x over it, layout copies at a third of it (PERF.md,
    PR 34)."""
    out = {}
    for line in hlo.splitlines():
        cycles = re.search(r'"estimated_cycles":"(\d+)"', line)
        if cycles:
            op = re.search(r'op_name="([^"]*)"', line)
            row = out.setdefault(op.group(1) if op else "", [0, 0])
            row[0] += int(cycles.group(1))
            row[1] += 1
    return out


def shape_tree(tree):
    """ShapeDtypeStructs mirroring a pytree of arrays (for lowering)."""
    import jax
    import numpy as np

    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype),
        tree)
