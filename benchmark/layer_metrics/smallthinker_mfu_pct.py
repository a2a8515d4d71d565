"""Model FLOP utilisation of a long-recording training window: forward
+ backward operations of the VALID positions of the steps completed,
their (query, key) pairs IN REACH by layer kind, and the (position,
expert) pairs their routing sent to experts held here
(``costs/smallthinker.train_flops_valid``; padding, the absent
experts' share and recomputation count for nothing) per second, over
chips times the device's published bf16 peak (``peaks.json``)."""

from benchmark.costs import smallthinker
from benchmark.layer_metrics import _lfm2, _smallthinker

DRIVERS = _smallthinker.DRIVERS


def read(record):
    steps = _smallthinker.window_routing(record)
    if record["peaks"] is None or not steps:
        return None
    c = record["counters"]
    frames, labels = c["valid_frames"], c["label_lens"]
    flops = 0
    for i, step in enumerate(steps):
        k = (record["warmup_steps"] + i) % len(frames)
        flops += smallthinker.train_flops_valid(
            record["model"], frames[k], labels[k],
            _lfm2.pairs_held(step), c["num_features"])
    window = record["t_window_end"] - record["t_window_start"]
    return 100.0 * flops / window / (
        record["chips"] * record["peaks"]["bf16_flops"])
