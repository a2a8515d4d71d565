"""How unevenly the router loads the 32 experts held here: the fullest
held expert's pairs over the held experts' mean, median over the
window's calls and expert layers, PREFILL only (10,500 positions a
sub-batch; a decode step's 8 pairs on 32 experts say nothing about the
router) (1.0 = even). The grouped products are dropless, so the
fullest expert costs rows, not accuracy."""

import statistics

from benchmark.layer_metrics import _trinity

DRIVERS = _trinity.DRIVERS


def read(record):
    ratios = [max(layer) * len(layer) / sum(layer)
              for c in _trinity.window_calls(record)
              for layer in c["prefill"].get("expert_pairs") or []
              if sum(layer)]
    return statistics.median(ratios) if ratios else None
