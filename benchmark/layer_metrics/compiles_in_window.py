"""Backend compile requests jax reported inside the measured window
(jax.monitoring listener). Must be 0: every shape is warmed up in
set-up."""


def read(record):
    return record["counters"]["window"]["compiles"]
