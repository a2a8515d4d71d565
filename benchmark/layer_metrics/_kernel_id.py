"""What the readers of named kernels share (the underscore keeps
``--detail`` from taking this module for a reader).

The program builds every Pallas kernel under an identity
(``deepspeech_tpu/ops/kernel_id.py``): its Mosaic custom call carries
``frontend_attributes={kernel_metadata={<JSON object, one pair per
line>}}``, and a trace event is named by its instruction's text, that
attribute included. A program from before the identities carries
``kernel_metadata={}``: every function here then finds nothing, and
the readers return None."""

import json

_MARK = "kernel_metadata="
_JSON = json.JSONDecoder()


def kernel_facts(text: str) -> dict:
    """The facts in an instruction's ``kernel_metadata`` (``kernel``,
    ``variant``, ``reverse``, ``t``, ``b``, ``h``, ``gates``, ...; all
    strings), or {} where the text carries none."""
    at = text.find(_MARK)
    if at < 0:
        return {}
    try:
        facts, _ = _JSON.raw_decode(text, at + len(_MARK))
    except ValueError:
        return {}
    return facts if isinstance(facts, dict) else {}


def is_scan_fwd(kernel: str) -> bool:
    """A forward recurrent scan: ``*_scan_fwd``, ``*_scan_stream``,
    ``*_scan_q_fwd``, ``*_scan_q_stream``."""
    return "_scan_" in kernel and not kernel.endswith("_bwd")


def is_scan_bwd(kernel: str) -> bool:
    return kernel.endswith("_scan_bwd")


def is_ctc(kernel: str) -> bool:
    return kernel.startswith("ctc_")


def named_kernels(record):
    """[(facts, seconds, instruction text)] of the window's Mosaic
    events that carry a ``kernel``, every chip's in its own order of
    time, or None where no event does (an untraced or CPU run, or a
    program that names no kernel)."""
    tr = record["trace"]
    if tr is None:
        return None
    out = []
    for text, seconds in tr["kernels"]:
        facts = kernel_facts(text)
        if "kernel" in facts:
            out.append((facts, seconds, text))
    return out or None


def ms_per_step(record, wanted):
    """Milliseconds a step and chip in the named kernels for which
    ``wanted(kernel)`` holds; 0.0 where kernels are named and none
    matches."""
    named = named_kernels(record)
    if named is None or not record["units"]:
        return None
    seconds = sum(s for facts, s, _ in named if wanted(facts["kernel"]))
    return 1e3 * seconds / record["chips"] / record["units"]
