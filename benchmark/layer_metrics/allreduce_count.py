"""All-reduce definitions in the compiled step's HLO (structure, not
time): how many separate reductions the gradient exchange is."""

DRIVERS = ("train",)


def read(record):
    if record["chips"] < 2:
        return None
    coll = record["counters"].get("collectives")
    return None if coll is None else coll["all-reduce"]
