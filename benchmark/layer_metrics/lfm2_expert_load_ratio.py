"""How unevenly the router loads the experts held here: the fullest
held expert's pairs over the mean of the held experts, median over the
window's steps and expert layers (1.0 = even). The grouped products
are dropless, so the fullest expert costs rows, not accuracy."""

import statistics

from benchmark.layer_metrics import _lfm2

DRIVERS = _lfm2.DRIVERS


def read(record):
    ratios = [max(layer) * len(layer) / sum(layer)
              for step in _lfm2.window_routing(record)
              for layer in step["expert_pairs"] if sum(layer)]
    return statistics.median(ratios) if ratios else None
