"""How unevenly the router loads the 64 experts, all held here: the
fullest expert's pairs over the mean, median over the window's calls,
their two programs (prefill, decode) and expert layers, the draft
module's included (1.0 = even). The grouped products are dropless, so
the fullest expert costs rows, not accuracy."""

import statistics

from benchmark.layer_metrics import _xing4

DRIVERS = _xing4.DRIVERS


def read(record):
    ratios = [max(layer) * len(layer) / sum(layer)
              for c in _xing4.window_calls(record)
              for p in _xing4.parts(c)
              for layer in p["expert_pairs"] if sum(layer)]
    return statistics.median(ratios) if ratios else None
