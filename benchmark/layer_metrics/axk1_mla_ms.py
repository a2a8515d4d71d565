"""Device milliseconds a call in latent attention, both forms, without
its output projection: the low-rank paths and their norms, rotations,
the expansion to per-head keys and values (prefill) or the absorption
into query and output (decode), scores, softmax, mixing and the cache's
update, told from the rest by result shape (``_axk1.is_mla``)."""

from benchmark.layer_metrics import _axk1

DRIVERS = _axk1.DRIVERS


def read(record):
    return _axk1.classified_ms_per_call(record, _axk1.is_mla)
