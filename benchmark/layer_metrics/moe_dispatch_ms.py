"""Device milliseconds a unit (a training step, a served call) and
chip in the expert layer OUTSIDE its grouped products and its shared
expert: the router (``moe_route``), the sort and the row gather
(``moe_dispatch``) and the weighted scatter-add back (``moe_combine``),
all directions, by the program's layer table (``_layers.py``). Unlike
``*_moe_route_ms`` it knows an operation by its scope, not by its
result's shape."""

from benchmark.layer_metrics import _layers


def read(record):
    return _layers.ms(record, ["moe_route", "moe_dispatch", "moe_combine"])
