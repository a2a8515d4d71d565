"""Seconds of set-up in which jax traced a function to a jaxpr or
lowered one to a StableHLO module: the union of the program's
``jax.trace`` and ``jax.lower`` spans (``deepspeech_tpu/obs/trace.py``)
that end before the window opens. The persistent cache holds
executables, not lowerings, so this is paid on every start; only a
program change shortens it.

The driver turns the program's tracer on just before ``Trainer.fit``,
so this sees the warm-up steps inside ``fit`` only, not the
``Trainer``'s construction or the reference check, and only in a
traced run.

A ``jax.compile`` span inside the window is the program's own witness
of ``compiles_in_window``; how many spans of each phase lie in the
window is kept in the run's counters (``compiled_in_window``; the
record's spans keep names and times, not ``fun``)."""

from benchmark.reduce import xplane

DRIVERS = ("train",)

PHASES = ("jax.trace", "jax.lower", "jax.compile")


def read(record):
    lo, hi = record["t_window_start"], record["t_window_end"]
    phases = [s for s in record["spans"] if s[0] in PHASES]
    if not phases:
        return None
    record["counters"]["compiled_in_window"] = {
        p: sum(1 for n, a, b in phases if n == p and a < hi and b > lo)
        for p in PHASES}
    # A function traced inside another's trace lies inside its span.
    return xplane.total(xplane.union(
        [(a, b) for n, a, b in phases
         if n != "jax.compile" and b <= lo]))
