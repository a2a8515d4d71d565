"""The benchmark's one command.

    python3 benchmark/run.py --workload W --seed N --seconds S --trace 0|1

Runs one cell of ``BENCHMARK.json`` in this process on the machine it
is started on, and prints as the last line of stdout one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and,
traced, ``breakdown``. With ``--trace 0`` the metrics are the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics.

Everything that belongs to one cell is data, found by name:

  BENCHMARK.json                      cells, metrics, bounds
  benchmark/configs/<config>.json     the configuration as it is run
  benchmark/traffic/<traffic>.json    ``driver`` + the mix's parameters
  benchmark/drivers/<driver>.py       run(ctx) -> record
  benchmark/layer_metrics/<name>.py   read(record) -> value or None

so a later PR adds a cell, a mix or a per-layer metric with new files
and appended entries, and edits nothing here.

``--rehearse`` runs the configuration's ``rehearsal`` sizes on the CPU
for control flow only: the result names the platform it ran on and is
never a device measurement. Without it, anything but a TPU with the
cell's chip count ends the run with a non-zero code and no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    # A cell kept for later (PERF.md, Open questions) or a one-time
    # sweep: "<config>.<traffic>", chips from the traffic file.
    config, _, traffic = name.partition(".")
    if traffic and os.path.exists(
            os.path.join(HERE, "traffic", traffic + ".json")):
        chips = load_json("benchmark", "traffic",
                          traffic + ".json").get("chips", 1)
        return {"name": name, "config": config, "traffic": traffic,
                "chips": chips}
    raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, the configuration's rehearsal sizes, "
                         "control flow only")
    ap.add_argument("--set", action="append", default=[], metavar="K=V",
                    help="override one traffic parameter (one-time "
                         "sweeps; never used by a cell)")
    ap.add_argument("--detail", default="",
                    help="also write the whole record (checks, spans "
                         "summary, every metric) to this JSON file")
    ap.add_argument("--keep-trace", default="",
                    help="copy the traced run's .xplane.pb here")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    bench = load_json("BENCHMARK.json")
    cell = find_cell(bench, args.workload)
    config = load_json("benchmark", "configs", cell["config"] + ".json")
    traffic = load_json("benchmark", "traffic", cell["traffic"] + ".json")
    for kv in args.set:
        k, _, v = kv.partition("=")
        traffic[k] = json.loads(v)
    seconds = (args.seconds if args.seconds is not None
               else float(bench["run_seconds"]))
    chips = int(cell["chips"])

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={chips}"
            ).strip()
        # An unreadable entry of a chip's cache only makes noise here.
        os.environ["DS2_COMPILE_CACHE"] = "0"

    import jax

    from benchmark import harness

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if args.rehearse:
        device["rehearsal"] = True
    elif device["platform"] != "tpu" or device["count"] != chips:
        print(f"run.py: {args.workload} needs {chips} TPU chip(s); "
              f"jax.devices() reports {device}", file=sys.stderr)
        return 3
    peaks = None
    if not args.rehearse:
        peaks = harness.peaks_for(device["kind"])  # unknown kind: error

    # The program's own switch: JAX_COMPILATION_CACHE_DIR if set, else
    # <checkout>/.jax_cache — a fixed path inside the checkout.
    from deepspeech_tpu.utils.cache import enable_compilation_cache

    enable_compilation_cache()

    ctx = harness.Context(
        cell=cell, config=config, traffic=traffic, seed=args.seed,
        seconds=seconds, trace=bool(args.trace), rehearse=args.rehearse,
        chips=chips, t_process=T_PROCESS, peaks=peaks,
        compiles=harness.CompileCounter(),
        trace_dir=os.path.join(ROOT, "chiprun_out", "trace",
                               cell["name"]),
        keep_trace=args.keep_trace)
    driver = importlib.import_module(
        f"benchmark.drivers.{traffic['driver']}")
    record = driver.run(ctx)
    record["device_info"] = device
    harness.finish_record(ctx, record)

    # A cell of BENCHMARK.json reports the metrics that list it (or
    # list no cell); a cell kept for later reports whatever reads.
    want = bench["per_layer"] if args.trace else bench["end_to_end"]
    listed = cell in bench["workloads"]
    metrics = {}
    for m in want:
        if listed and not applies(m, cell["name"]):
            continue
        value = harness.metric_value(m, record, traced=bool(args.trace))
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device["memory_peak_bytes"] = record["memory_peak_bytes"]
    result = {"correct": bool(record["correct"]),
              "attempted": int(record["attempted"]),
              "failed": int(record["failed"]),
              "metrics": metrics, "device": device}
    if args.trace and record.get("trace"):
        device["busy_s"] = record["trace"]["busy_s"]
        device["window_s"] = record["trace"]["window_s"]
        result["breakdown"] = record["trace"]["breakdown"]
    if args.detail:
        harness.write_detail(args.detail, ctx, record, result, bench)
    if not record["correct"]:
        print("run.py: checks failed: "
              + json.dumps(record["checks"], default=str),
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
