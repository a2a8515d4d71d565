"""Median of the program's ``train.step`` span inside the window of a
long-recording training run (``train_step_ms``'s reading, for the
driver ``train_long``). With the tracer on the loop blocks on the loss
inside that span, so it is dispatch plus the device's whole step."""

from benchmark.layer_metrics import train_step_ms

DRIVERS = ("train_long",)

read = train_step_ms.read
