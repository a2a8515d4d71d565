"""What every driver shares: the run's context, the compile counter,
the table of peaks, the traced window, and the step from a run's
record to the metrics ``BENCHMARK.json`` names.

A driver's ``run(ctx)`` returns a record (a dict). Keys every record
has:

  correct, attempted, failed, checks   the outcome and what was checked
  t_window_start, t_window_end         perf_counter readings
  units                                completed steps or ticks in it
  audio_s                              valid audio seconds completed
  latencies_ms                         per-tick samples (serving only)
  spans                                flat host spans (name, start,
                                       end) on perf_counter
  gen_s                                seconds the generator itself took
  counters                             anything counted (see drivers)

``finish_record`` adds compile counts, memory and, traced, the trace's
reduction under ``trace``.
"""

from __future__ import annotations

import dataclasses
import glob
import importlib
import json
import os
import re
import shutil
import statistics
import time
from typing import Any, Optional

HERE = os.path.dirname(os.path.abspath(__file__))


# -- observation ----------------------------------------------------------

class CompileCounter:
    """Counts what jax reports about compilation (copied from
    ``chip_smoke.py``): backend compile requests, their seconds, and
    how many the persistent cache answered."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration_secs, **_):
        if event == self.COMPILE:
            self.compiles += 1
            self.compile_s += duration_secs

    def _on_event(self, event, **_):
        if event == self.HIT:
            self.cache_hits += 1

    def snapshot(self) -> tuple:
        return self.compiles, self.compile_s, self.cache_hits

    def since(self, snap: tuple) -> dict:
        return {"compiles": self.compiles - snap[0],
                "compile_s": self.compile_s - snap[1],
                "cache_hits": self.cache_hits - snap[2]}


def peaks_for(device_kind: str) -> dict:
    """The published peaks of this device; an unknown kind is an error,
    never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise SystemExit(
            f"benchmark/peaks.json has no entry for device_kind "
            f"{device_kind!r}; add one with its source")
    return table[device_kind]


@dataclasses.dataclass
class Context:
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    chips: int
    t_process: float
    peaks: Optional[dict]
    compiles: CompileCounter
    trace_dir: str
    keep_trace: str = ""
    _trace_on: bool = False
    anchor: Optional[float] = None

    # The measured window: a traced run measures (and traces) only the
    # mix's ``trace_seconds``; traces are large and tracing slows the
    # host, so the end-to-end numbers come from untraced runs.
    def window_seconds(self) -> float:
        if self.trace:
            return min(self.seconds,
                       float(self.traffic.get("trace_seconds", 4.0)))
        return self.seconds

    def param(self, key: str, default=None):
        """A traffic parameter; under --rehearse the ``rehearsal``
        group of the mix wins."""
        if self.rehearse and key in self.traffic.get("rehearsal", {}):
            return self.traffic["rehearsal"][key]
        if default is None and key not in self.traffic:
            raise SystemExit(f"traffic file lacks {key!r}")
        return self.traffic.get(key, default)

    def start_trace(self) -> None:
        """Start the profiler (traced runs only) and tie its clock to
        ``perf_counter`` with one annotation."""
        if not self.trace or self._trace_on:
            return
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._trace_on = True
        with jax.profiler.TraceAnnotation("bench.anchor"):
            self.anchor = time.perf_counter()

    def stop_trace(self) -> Optional[str]:
        if not self._trace_on:
            return None
        import jax

        jax.profiler.stop_trace()
        self._trace_on = False
        paths = glob.glob(os.path.join(self.trace_dir, "**",
                                       "*.xplane.pb"), recursive=True)
        return max(paths, key=os.path.getmtime) if paths else None


def model_config(ctx: Context):
    """The program's preset named by the configuration file, checked
    against every size the file states: the file is the configuration
    as it is run, so a drift between the two ends the run."""
    from deepspeech_tpu.config import get_config

    cfg = get_config(ctx.config["preset"])
    for key, want in ctx.config["model"].items():
        got = getattr(cfg.model, key)
        got = [list(x) if isinstance(x, tuple) else x for x in got] \
            if isinstance(got, tuple) else got
        if got != want:
            raise SystemExit(
                f"configs/{ctx.cell['config']}.json says model.{key}="
                f"{want!r}, preset {ctx.config['preset']!r} has {got!r}")
    if ctx.rehearse:
        cfg = dataclasses.replace(cfg, model=dataclasses.replace(
            cfg.model, **ctx.config.get("rehearsal", {})))
    return cfg


def kernel_route_checks(cfg) -> dict:
    """'auto' must have resolved to the compiled Pallas kernels (copied
    from ``chip_smoke.py``): a run on the oracles or on interpreted
    kernels looks the same from outside."""
    from deepspeech_tpu.utils.impl import interpret_default, resolve_impl

    return {
        "rnn_impl_pallas":
            resolve_impl(cfg.model.rnn_impl, oracle="xla") == "pallas",
        "loss_impl_pallas":
            resolve_impl(cfg.train.loss_impl, oracle="jnp") == "pallas",
        "kernels_compiled": not interpret_default(),
    }


COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "collective-permute", "all-to-all")


def count_collectives(hlo: str) -> dict:
    """Op definitions in compiled HLO text, not mentions (copied from
    ``tools/_aot_common.py``)."""
    return {op: len(re.findall(rf"(?<![-\w]){op}(?:-start)?\(", hlo))
            for op in COLLECTIVE_OPS}


def memory_now() -> list:
    """Per chip: bytes the runtime holds right now. Live buffers are
    ``bytes_in_use``; a running program's temporaries are not in it —
    the TPU runtime accounts them under ``bytes_reserved`` (PERF.md,
    findings of PR 22) — so the chip's use is the sum."""
    import jax

    out = []
    for d in jax.devices():
        s = d.memory_stats() or {}
        out.append({"in_use": s.get("bytes_in_use", 0),
                    "reserved": s.get("bytes_reserved", 0),
                    "peak_in_use": s.get("peak_bytes_in_use", 0),
                    "peak_reserved": s.get("peak_bytes_reserved", 0)})
    return out


def memory_peak_bytes(samples: list) -> int:
    """The fullest chip's peak over the samples a driver took: the
    larger of the runtime's own peak of live buffers and the largest
    live + reserved seen at a sample."""
    peak = 0
    for sample in samples:
        for chip in sample:
            peak = max(peak, chip["peak_in_use"],
                       chip["in_use"] + chip["reserved"])
    return int(peak)


# -- from record to metrics -------------------------------------------------

def percentile(xs, q: float) -> float:
    """Nearest-rank percentile of the samples."""
    xs = sorted(xs)
    k = max(0, min(len(xs) - 1, int(-(-q * len(xs) // 100)) - 1))
    return float(xs[k])


def end_to_end(name: str, record: dict) -> Optional[float]:
    window = record["t_window_end"] - record["t_window_start"]
    lat = record.get("latencies_ms") or []
    if name == "setup_s":
        return record["setup_s"]
    if name == "audio_s_per_s_chip":
        return record["audio_s"] / window / record["chips"]
    if name == "latency_p50_ms" and lat:
        return float(statistics.median(lat))
    if name.startswith("latency_p") and name.endswith("_ms") and lat:
        return percentile(lat, float(name[len("latency_p"):-3]))
    return None


def metric_value(metric: dict, record: dict, traced: bool):
    if not traced:
        return end_to_end(metric["name"], record)
    path = os.path.join(HERE, "layer_metrics", metric["name"] + ".py")
    if not os.path.exists(path):
        return None
    reader = importlib.import_module(
        f"benchmark.layer_metrics.{metric['name']}")
    drivers = getattr(reader, "DRIVERS", None)
    if drivers is not None and record["driver"] not in drivers:
        return None
    value = reader.read(record)
    return None if value is None else float(value)


def finish_record(ctx: Context, record: dict) -> None:
    record["chips"] = ctx.chips
    record["setup_s"] = record["t_window_start"] - ctx.t_process
    record["memory_peak_bytes"] = memory_peak_bytes(
        record["memory_samples"])
    record["peaks"] = ctx.peaks
    trace_path = record.pop("trace_path", None)
    record["trace"] = None
    if trace_path and ctx.anchor is not None:
        from benchmark.reduce import xplane

        if ctx.keep_trace:
            os.makedirs(os.path.dirname(os.path.abspath(ctx.keep_trace)),
                        exist_ok=True)
            shutil.copy(trace_path, ctx.keep_trace)
        tr = xplane.load(trace_path)
        if ctx.rehearse and not tr.devices:
            return  # a CPU trace has no device plane to reduce
        anchor_ns = tr.anchor_ns()
        if anchor_ns is None:
            raise SystemExit("the trace holds no bench.anchor")
        to_ns = lambda t: anchor_ns + (t - ctx.anchor) * 1e9  # noqa: E731
        window = (to_ns(record["t_window_start"]),
                  to_ns(record["t_window_end"]))
        spans = [(n, to_ns(a), to_ns(b)) for n, a, b in record["spans"]]
        red = xplane.reduce_trace(tr, window, spans)
        red["kernels"] = xplane.kernel_events(tr, window)
        record["trace"] = red
        if not red["busy_s"] > 0:
            record["checks"]["device_ran"] = False
            record["correct"] = False
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)


def span_seconds(record: dict, *names: str) -> float:
    """Seconds of the window covered by the named flat spans."""
    lo, hi = record["t_window_start"], record["t_window_end"]
    return sum(max(0.0, min(b, hi) - max(a, lo))
               for n, a, b in record["spans"] if n in names)


def write_detail(path: str, ctx: Context, record: dict, result: dict,
                 bench: dict) -> None:
    """Everything a person may want after a run; the driver never
    reads it."""
    every = {}
    for m in bench["end_to_end"]:
        v = end_to_end(m["name"], record)
        if v is not None:
            every[m["name"]] = v
    if ctx.trace:  # every reader there is, listed in a cell or not
        for reader in sorted(glob.glob(
                os.path.join(HERE, "layer_metrics", "[a-z]*.py"))):
            name = os.path.basename(reader)[:-3]
            v = metric_value({"name": name}, record, traced=True)
            if v is not None:
                every[name] = v
    lat = record.get("latencies_ms") or []
    out: dict[str, Any] = {
        "workload": ctx.cell["name"], "seed": ctx.seed,
        "seconds": ctx.seconds, "traced": ctx.trace, "result": result,
        "every_metric": every, "checks": record["checks"],
        "counters": record["counters"], "units": record["units"],
        "window_s": record["t_window_end"] - record["t_window_start"],
        "setup_s": record["setup_s"], "gen_s": record["gen_s"],
        "setup_phases": record.get("setup_phases"),
        "memory_samples": record["memory_samples"],
    }
    if lat:
        out["latency_ms"] = {
            "n": len(lat), "p50": statistics.median(lat),
            "p90": percentile(lat, 90), "p95": percentile(lat, 95),
            "p99": percentile(lat, 99), "max": max(lat)}
    if record["trace"] is not None:
        out["trace"] = {k: v for k, v in record["trace"].items()
                        if k not in ("op_seconds", "kernels")}
        out["trace"]["top_ops"] = sorted(
            record["trace"]["op_seconds"].items(),
            key=lambda kv: -kv[1])[:60]
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1, default=str)
