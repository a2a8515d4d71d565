"""Share of the window the program's host spent in garbage collections:
the ``host.gc`` spans (``deepspeech_tpu/obs/trace.py``, one a
collection, any generation, any thread) over the window. 0 where the
program has the hook and nothing was collected; None where the record
holds none of the host-turn spans, so no hook either."""

from benchmark import harness
from benchmark.layer_metrics import _host_turn

DRIVERS = _host_turn.DRIVERS


def read(record):
    if not _host_turn.units(record):
        return None
    window = record["t_window_end"] - record["t_window_start"]
    return 100.0 * harness.span_seconds(record, "host.gc") / window
