"""Backend detection + kernel-implementation resolution.

Shared by the RNN stack (models/rnn.py) and the CTC loss
(train.select_loss_fn): both expose an 'auto' | <oracle> | 'pallas'
knob whose 'auto' value resolves to the Pallas kernel on real TPU
(what every cell of BENCHMARK.json runs and ``correct`` checks;
PERF.md section 6 has what was measured against the oracles), the
XLA/jnp oracle elsewhere so CPU CI and virtual-device meshes never
crawl through the Pallas interpreter.
"""

from __future__ import annotations

import os

import jax


def on_tpu() -> bool:
    """True when jax dispatches to a real TPU backend.

    ``DS2N_ASSUME_TPU=1`` overrides to True for ahead-of-time
    compilation against an abstract TPU topology (tools/aot_tpu.py):
    there the RUNTIME backend is cpu but the lowering target is a real
    v5e, so 'auto' must resolve exactly as it would on the chip
    (Pallas kernels, interpret=False -> Mosaic).
    """
    if os.environ.get("DS2N_ASSUME_TPU") == "1":
        return True
    return jax.default_backend() == "tpu"


def interpret_default() -> bool:
    """Run Pallas kernels in interpreter mode off-TPU (CPU CI)."""
    return not on_tpu()


def resolve_impl(impl: str, oracle: str) -> str:
    """Resolve an implementation knob ('auto' | oracle | 'pallas').

    Unknown values raise instead of silently falling back, so a typo
    can never quietly benchmark the wrong implementation.
    """
    if impl not in ("auto", oracle, "pallas"):
        raise ValueError(f"unknown impl {impl!r}; "
                         f"use 'auto', {oracle!r}, or 'pallas'")
    if impl == "auto":
        return "pallas" if on_tpu() else oracle
    return impl
