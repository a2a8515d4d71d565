"""Leaf device time that the program's own layer table
(``deepspeech_tpu/obs/layers.py``) puts under a layer's name, in % of
all leaf device time of the window (``_layers.py``: containers left
out; ``(unnamed)``, ``(unmatched)`` and ``(ambiguous)`` are the rest).
A guard: it falls when a PR adds device work outside every scope, and
the by-layer readers then see less than there is."""

from benchmark.layer_metrics import _layers


def read(record):
    return _layers.named_pct(record)
