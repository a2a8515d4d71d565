"""How unevenly the router loads the experts held here: the fullest
held expert's pairs over the mean of the held experts, median over the
window's calls, their two programs (prefill, decode) and expert layers
(1.0 = even). The grouped products are dropless, so the fullest expert
costs rows, not accuracy."""

import statistics

from benchmark.layer_metrics import _axk1

DRIVERS = _axk1.DRIVERS


def read(record):
    ratios = [max(layer) * len(layer) / sum(layer)
              for c in _axk1.window_calls(record)
              for p in _axk1.parts(c)
              for layer in p["expert_pairs"] if sum(layer)]
    return statistics.median(ratios) if ratios else None
