"""Peak device memory on the fullest chip, GB (10^9 bytes): live
buffers plus what the runtime reserved for running programs'
temporaries (``harness.memory_peak_bytes``)."""


def read(record):
    return record["memory_peak_bytes"] / 1e9
