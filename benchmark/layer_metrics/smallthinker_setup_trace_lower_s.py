"""Seconds of a long-recording training run's set-up in which jax
traced or lowered a function (``setup_trace_lower_s``'s reading, for
the driver ``train_long``): the union of the program's ``jax.trace``
and ``jax.lower`` spans that end before the window opens. The driver
turns the tracer on before it builds the ``Trainer``, so this sees the
jitted initialisation, the reference check (where this cell's step is
traced, lowered and compiled, with the comparison's own programs) and
the warm-up steps, in a traced run."""

from benchmark.layer_metrics import setup_trace_lower_s

DRIVERS = ("train_long",)

read = setup_trace_lower_s.read
