"""Are two trees' recurrent scan kernels the same program? No chip.

A Mosaic custom call carries its kernel as MLIR bytecode, and the
bytecode holds the source locations of the Python that traced it, so
two dumps differ in every call after any edit of ``ops/``. This tool
decodes the bytecode and prints it without locations, so that what is
left to differ is the kernel.

  # the compiled step programs of two trees (tools/aot_tpu.py
  # --preset P --batch B --frames F --hlo-out FILE, run in each):
  JAX_PLATFORMS=cpu python tools/scan_calls.py hlo PARENT.hlo CHANGE.hlo

  # every routed build of the scan kernels, lowered only (seconds):
  # one file a build under OUT; run in each tree, then `diff -r`
  JAX_PLATFORMS=cpu python tools/scan_calls.py lower OUT

``hlo`` prints, for the calls named ``*_scan_*``: their count per
kernel, whether result shapes and layouts, operand layout constraints,
``kernel_metadata``, the backend config and the decoded Mosaic modules
are identical (as a multiset and in program order), and whether the
rest of the two programs is identical outside source locations. Exit
code 1 if the scan calls differ.
"""

from __future__ import annotations

import base64
import collections
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_BODY = re.compile(r'(\\22|")body\1:\s*\1([A-Za-z0-9+/=]+)\1')
_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def _decoder():
    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    ctx = jax_mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True  # the stable_mosaic wrapper

    def decode(match):
        with ctx:
            module = ir.Module.parse(base64.b64decode(match.group(2)))
            return "body<<\n" + module.operation.get_asm(
                enable_debug_info=False) + "\n>>"
    return decode


def without_locations(hlo: str) -> str:
    """A compiled program's text less its stack-frame index, the
    ``metadata=`` of its instructions and the kernels' bytecode."""
    kept, skipping = [], False
    for line in hlo.split("\n"):
        if line in _TABLES:
            skipping = True
        elif not (skipping and (re.match(r"\d+ ", line) or not line)):
            skipping = False
            kept.append(line)
    text = re.sub(r", metadata=\{[^{}]*\}", "", "\n".join(kept))
    return _BODY.sub("body", text)


def scan_calls(hlo: str, decode) -> list:
    calls = []
    for ins in re.split(r"\n(?=  (?:ROOT )?%[\w.\-]+ = )", hlo):
        m = re.match(r"\s*(?:ROOT )?%(\w+_scan_\w+?)(?:\.\d+)? = (.*?) "
                     r"custom-call\(", ins, re.S)
        if m and "tpu_custom_call" in ins:
            rest = re.sub(r", metadata=\{[^{}]*\}", "",
                          ins[ins.index("custom_call_target"):])
            calls.append((m.group(1), m.group(2), _BODY.sub(decode, rest)))
    return calls


def compare_hlo(parent: str, change: str) -> int:
    decode = _decoder()
    texts = [open(p).read() for p in (parent, change)]
    a, b = (scan_calls(t, decode) for t in texts)
    print("scan custom calls:", dict(collections.Counter(c[0] for c in a)),
          "/", dict(collections.Counter(c[0] for c in b)))
    print("identical as a multiset:", sorted(a) == sorted(b))
    print("identical in program order:", a == b)
    print("rest of the program identical outside source locations:",
          without_locations(texts[0]) == without_locations(texts[1]))
    return 0 if sorted(a) == sorted(b) else 1


def lower_builds(out: str) -> None:
    """Every case of ``aot_kernels.kernel_cases()`` that holds a scan
    kernel, lowered for the TPU: its Mosaic calls, decoded."""
    import jax

    from aot_kernels import kernel_cases

    decode = _decoder()
    os.makedirs(out, exist_ok=True)
    for name, builder in kernel_cases().items():
        if not name.startswith(("gru", "bigru", "lstm")):
            continue
        fn, args = builder()
        text = jax.jit(fn).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
        with open(os.path.join(out, name + ".txt"), "w") as f:
            for line in text.splitlines():
                if "tpu_custom_call" in line:
                    f.write(re.sub(r" loc\(.*\)$", "",
                                   _BODY.sub(decode, line)) + "\n")
        print(name, flush=True)


if __name__ == "__main__":
    if len(sys.argv) == 4 and sys.argv[1] == "hlo":
        sys.exit(compare_hlo(*sys.argv[2:]))
    if len(sys.argv) == 3 and sys.argv[1] == "lower":
        sys.exit(lower_builds(sys.argv[2]))
    sys.exit(__doc__)
