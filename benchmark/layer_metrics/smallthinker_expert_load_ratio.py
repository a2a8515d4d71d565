"""How unevenly the router loads the experts held here
(``lfm2_expert_load_ratio``'s reading, for the driver ``train_long``):
the fullest held expert's pairs over the mean of the held experts,
median over the window's steps and layers (1.0 = even). This family's
router has no selection bias and its training no balancing term, so a
seeded router's unevenness is the model's own."""

from benchmark.layer_metrics import _smallthinker, lfm2_expert_load_ratio

DRIVERS = _smallthinker.DRIVERS


def read(record):
    return lfm2_expert_load_ratio.read(_smallthinker.as_lfm2(record))
