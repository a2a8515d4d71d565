"""Seconds jax spent in backend compile requests during set-up (a
persistent-cache read counts with its read time): near 0 once the
checkout's cache is warm."""


def read(record):
    return record["counters"]["setup"]["compile_s"]
