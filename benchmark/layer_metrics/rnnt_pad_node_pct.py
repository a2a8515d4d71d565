"""Share of the lattice nodes a step computes that are padding: one
minus the valid nodes T'_b * (U_b + 1) of the steps completed over
rows * T' * (max_label_len + 1), from the lengths of the batches the
run served (``costs/rnnt.py``). Padded nodes cost the joint's matmul
time and earn nothing."""

from benchmark.costs import rnnt

DRIVERS = ("train_rnnt",)


def read(record):
    c = record["counters"]
    frames, labels = c["valid_frames"], c["label_lens"]
    valid = 0
    for i in range(record["units"]):
        k = (record["warmup_steps"] + i) % len(frames)
        valid += rnnt.lattice_nodes(record["model"], frames[k], labels[k])
    computed = record["units"] * rnnt.padded_nodes(
        record["model"], c["rows_per_step"], c["bucket_frames"],
        c["max_label_len"])
    return 100.0 * (1.0 - valid / computed) if computed else None
