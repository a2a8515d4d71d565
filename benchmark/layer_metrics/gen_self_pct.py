"""Share of the window the benchmark's own generator took (building
ticks or batches). A starved generator must not read as a fast server:
this should stay a few per cent at most."""


def read(record):
    window = record["t_window_end"] - record["t_window_start"]
    return 100.0 * record["gen_s"] / window
