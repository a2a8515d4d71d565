"""Model FLOP utilisation of a transducer training window: forward +
backward operations of the VALID frames, labels and lattice nodes of
the steps completed (``costs/rnnt.py``; padding and recomputation count
for nothing) per second, over chips times the device's published bf16
peak (``peaks.json``)."""

from benchmark.costs import rnnt

DRIVERS = ("train_rnnt",)


def read(record):
    if record["peaks"] is None:
        return None
    c = record["counters"]
    frames, labels = c["valid_frames"], c["label_lens"]
    flops = 0
    for i in range(record["units"]):
        k = (record["warmup_steps"] + i) % len(frames)
        flops += rnnt.train_flops_valid(record["model"], frames[k],
                                        labels[k], c["num_features"])
    window = record["t_window_end"] - record["t_window_start"]
    return 100.0 * flops / window / (
        record["chips"] * record["peaks"]["bf16_flops"])
