"""Device milliseconds a call in the attention's own work of the DECODE
loop (what the program holds under the scope ``gqa_attn_*``: the new
row's write into ring or full cache, scores against the rows in reach,
softmax, mixing; five layers, every step), told from the rest by result
shape (``_trinity.is_attn``)."""

from benchmark.layer_metrics import _trinity

DRIVERS = _trinity.DRIVERS


def read(record):
    return _trinity.classified_ms_per_call(
        record, lambda shapes, r: _trinity.is_attn(shapes, r, "decode"))
