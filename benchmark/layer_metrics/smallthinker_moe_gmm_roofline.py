"""The grouped products' share of their roofline
(``lfm2_moe_gmm_roofline``'s reading, for the driver ``train_long``):
the least time the chip could take for the operations and bytes the
window's steps need of them (``costs/lfm2.gmm_call_cost``: each kind
of call, told by its facts ``k``, ``n``, ``groups``,
``transpose_rhs``, once per layer and step over the rows that step's
routing sent to that layer; the rows of the static capacity past them,
and a call made twice, need nothing) over the device time of
``moe_gmm`` + ``moe_tgmm`` by name."""

from benchmark.layer_metrics import _smallthinker, lfm2_moe_gmm_roofline

DRIVERS = _smallthinker.DRIVERS


def read(record):
    return lfm2_moe_gmm_roofline.read(_smallthinker.as_lfm2(record))
