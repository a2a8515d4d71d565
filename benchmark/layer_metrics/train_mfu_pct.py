"""Model FLOP utilisation of the window: forward + backward operations
of the VALID frames of the steps completed (``costs/ds2.py``; padding
and recomputation count for nothing) per second, over chips times the
device's published bf16 peak (``peaks.json``)."""

from benchmark.costs import ds2

DRIVERS = ("train",)


def read(record):
    if record["peaks"] is None:
        return None
    c = record["counters"]
    pool = c["valid_frames"]
    flops = sum(
        ds2.train_flops_valid(
            record["model"],
            pool[(record["warmup_steps"] + i) % len(pool)],
            c["num_features"])
        for i in range(record["units"]))
    window = record["t_window_end"] - record["t_window_start"]
    return 100.0 * flops / window / (
        record["chips"] * record["peaks"]["bf16_flops"])
