"""Device milliseconds a call in the attention's own work of the
PREFILL program (what the program holds under the scope ``gqa_attn_*``:
scores, softmax and mixing of each query block against the keys in its
reach, five layers, eight sub-batches), told from the rest by result
shape (``_trinity.is_attn``); without projections, norms, rotation,
gate and output projection."""

from benchmark.layer_metrics import _trinity

DRIVERS = _trinity.DRIVERS


def read(record):
    return _trinity.classified_ms_per_call(
        record, lambda shapes, r: _trinity.is_attn(shapes, r, "prefill"))
