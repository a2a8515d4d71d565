#!/usr/bin/env python3
"""A kept device trace + the compiled program's HLO text -> ms a unit by
layer, direction, instruction base and shape.

    python3 benchmark/run.py --workload ds2_full.train_1chip --trace 1 \\
        --keep-trace chiprun_out/t.xplane.pb --detail chiprun_out/d.json
    python3 tools/aot_tpu.py --preset ds2_full --batch 32 --frames 1700 \\
        --hlo-out chiprun_out/step.hlo
    python3 tools/layer_sums.py chiprun_out/t.xplane.pb chiprun_out/step.hlo \\
        --units 21 [--top 60] [--json OUT.json]

The trace names an event by its HLO instruction; the HLO text (any
number of files: a served cell has a prefill and a decode program)
carries each instruction's ``op_name``, which
``deepspeech_tpu/obs/layers.py`` reads into a layer and a direction.
``--units`` is the number of completed steps or calls the trace holds
(``units`` of the run's ``--detail`` file); every event of the trace is
summed, the warm-up's too if it was traced. A compile for a described
v5e gives the chip's instruction names (PERF.md section 7). Containers
(``while``, ``conditional``, ``call``) are left out: their bodies'
events are events of their own.

The benchmark's readers (``benchmark/layer_metrics/_layers.py``) make
the same join inside a traced run, from the executables that ran.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.layer_metrics import _layers  # noqa: E402
from benchmark.reduce import xplane  # noqa: E402
from deepspeech_tpu.obs import layers  # noqa: E402

_NUMBER = re.compile(r"[.\d]+$")


def sums(events, programs: dict, units: int, chips: int) -> dict:
    """``events``: (instruction text, seconds) of every device event;
    ``programs``: {name: ``instruction_scopes`` of its HLO text}.
    Returns ms a unit and chip by layer and direction, and the rows
    under them by instruction base and shape."""
    by_key = collections.Counter()
    calls = collections.Counter()
    for text, seconds in events:
        key = xplane.short_name(text)
        by_key[key] += seconds
        calls[key] += 1
    scale = 1e3 / units / chips
    by_layer = collections.Counter()
    rows = collections.defaultdict(lambda: [0.0, 0.0])
    for key, seconds in by_key.items():
        where = _layers.layer_of(key, programs)
        if where is None:
            continue
        head, opcode, shape, mosaic = _layers.parse(key)
        base = _NUMBER.sub("", head.lstrip("%"))
        by_layer[where] += seconds * scale
        row = rows[where + (base + (" [mosaic]" if mosaic else ""), shape)]
        row[0] += seconds * scale
        row[1] += calls[key] / units / chips
    return {
        "units": units, "chips": chips,
        "ms_a_unit": sum(by_layer.values()),
        "layers": [{"layer": layer, "direction": direction, "ms": ms}
                   for (layer, direction), ms in by_layer.most_common()],
        "rows": [{"layer": k[0], "direction": k[1], "instruction": k[2],
                  "shape": k[3], "ms": ms, "calls": n}
                 for k, (ms, n) in sorted(rows.items(),
                                          key=lambda kv: -kv[1][0])]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="a kept .xplane.pb")
    ap.add_argument("hlo", nargs="+", help="compiled HLO text file(s)")
    ap.add_argument("--units", type=int, required=True,
                    help="completed steps or calls in the trace")
    ap.add_argument("--top", type=int, default=40,
                    help="rows to print under the layers")
    ap.add_argument("--json", default="", help="also write everything here")
    args = ap.parse_args(argv)
    programs = {}
    for path in args.hlo:
        with open(path) as f:
            programs[os.path.basename(path)] = layers.instruction_scopes(
                f.read())
    tr = xplane.load(args.trace)
    events = [(name, (b - a) / 1e9) for dev in tr.devices.values()
              for a, b, name in dev.ops]
    out = sums(events, programs, args.units, max(1, len(tr.devices)))
    print(f"{out['ms_a_unit']:10.3f} ms a unit and chip, "
          f"{out['units']} units, {out['chips']} chip(s)")
    for r in out["layers"]:
        print(f"{r['ms']:10.3f}  {r['layer']}.{r['direction']}")
    print()
    for r in out["rows"][:args.top]:
        print(f"{r['ms']:10.3f} {r['calls']:7.2f}  "
              f"{r['layer']}.{r['direction']}  {r['instruction']} "
              f"{r['shape'][:80]}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
