"""Seconds of a served run's set-up in which jax traced or lowered a
function (``setup_trace_lower_s``'s reading, for the driver
``transcribe_hybrid``): the union of the program's ``jax.trace`` and
``jax.lower`` spans that end before the window opens. The driver turns
the tracer on before it makes the weights, so this sees their
initialisation, the reference check (where the cell's two programs are
traced, lowered and compiled) and the warm-up call, in a traced run."""

from benchmark.layer_metrics import setup_trace_lower_s

DRIVERS = ("transcribe_hybrid",)

read = setup_trace_lower_s.read
