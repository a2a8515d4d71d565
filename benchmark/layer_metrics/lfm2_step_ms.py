"""Median of the program's ``train.step`` span inside the window of a
decoder-only training run (``train_step_ms``'s reading, for the driver
``train_lfm2``). With the tracer on the loop blocks on the loss inside
that span, so it is dispatch plus the device's whole step."""

from benchmark.layer_metrics import train_step_ms

DRIVERS = ("train_lfm2",)

read = train_step_ms.read
