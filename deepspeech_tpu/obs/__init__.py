"""Unified observability: one metrics registry + span tracing for
train/infer/serve/bench.

Three disjoint mechanisms grew up in this repo — the gateway-only
``serving/telemetry.py``, train's ``utils/logging.py`` JSONL stream,
and ad-hoc bench prints — none of which could answer "where did this
step's time go?". This package is the shared substrate:

- :class:`MetricsRegistry` (``obs.registry()`` is the process-wide
  default): thread-safe counters / gauges / bounded-reservoir
  histograms / per-(B, T)-rung usage, with optional Prometheus-style
  labels. ``ServingTelemetry`` is now a thin shim over it.
- :func:`span`: ``with obs.span("train.step", step=i): ...`` — nested
  spans on a monotonic clock (injectable for tests), written as JSONL
  records ``{"event": "span", "name", "ts", "dur_ms", "id",
  "parent", ...attrs}``. Disabled by default; when off a span costs
  one attribute read and a shared no-op context manager.
- compile events: ``ShapeBucketCache`` reports every fresh (B, T)
  compile here, counted per rung in the registry and — when tracing —
  emitted as a ``{"event": "compile", "rung", "site"}`` record
  attributing the recompile to its call site.
- export: ``emit_jsonl()`` (one schema shared by train/infer/serve/
  bench; ``tools/check_obs_schema.py`` lints it) and
  ``render_text()`` (Prometheus text exposition for scraping).

- per-request observability (PR 9): :class:`TraceContext` phase
  ledgers + the :class:`FlightRecorder` ring (``obs/context.py``),
  the :class:`SloBurnEngine` multi-window burn-rate alerting over
  ``slo_ok``/``slo_miss`` (``obs/slo.py``), and the
  :class:`StatusServer` live ops surface (``obs/status.py``:
  ``/metrics`` ``/healthz`` ``/slo`` ``/traces`` ``/timeline``
  ``/incidents``).
- fleet incident timeline (PR 18): the :class:`EventLog` causal event
  ledger + :class:`IncidentCorrelator` + :class:`MetricSeries`
  (``obs/timeline.py``), and the ``postmortem_link`` seam resilience
  registers its recorder through (:func:`set_postmortem_recorder`)
  so obs never imports resilience at module load.

- device time by layer (PR 51): ``obs/layers.py`` maps a compiled
  program's instructions to the model's layers through their
  ``op_name`` (module path + ``jax.named_scope``), for the readers of a
  device trace; the dispatch sites ``watch`` their programs while the
  tracer is on.

Enable tracing with ``obs.configure(jsonl_path=...)`` or by exporting
``DS2_TRACE=/path/to/trace.jsonl``; read traces with
``tools/trace_report.py`` and request breakdowns with
``tools/slo_report.py``.
"""

from __future__ import annotations

from .context import FlightRecorder, TraceContext, flight_recorder
from .metrics import Histogram, MetricsRegistry, registry
from .postmortem_link import (postmortem_record, postmortem_recorder,
                              set_postmortem_recorder)
from .routing import check_dropless, observe_lm_call, observe_routing
from .slo import SloBurnEngine
from .status import StatusServer
from .timeline import EventLog, IncidentCorrelator, MetricSeries
from .trace import Tracer, tracer
from . import layers, timeline

__all__ = ["Histogram", "MetricsRegistry", "Tracer", "registry",
           "tracer", "span", "configure", "compile_event",
           "render_text", "emit_jsonl", "TraceContext",
           "FlightRecorder", "flight_recorder", "SloBurnEngine",
           "StatusServer", "EventLog", "IncidentCorrelator",
           "MetricSeries", "timeline", "layers", "set_postmortem_recorder",
           "postmortem_recorder", "postmortem_record",
           "observe_routing", "observe_lm_call", "check_dropless"]


def span(name: str, **attrs):
    """Context manager timing one named phase on the default tracer."""
    return tracer.span(name, **attrs)


def configure(**kwargs) -> None:
    """Configure the default tracer (see :meth:`Tracer.configure`)."""
    tracer.configure(**kwargs)


def compile_event(batch: int, frames: int, site: str = None,
                  labels: dict = None) -> None:
    """Report one fresh (B, T) compile (see
    :meth:`Tracer.compile_event`)."""
    tracer.compile_event(batch, frames, site=site, labels=labels)


def render_text(prefix: str = "ds2") -> str:
    """Prometheus text exposition of the process-wide registry."""
    return registry().render_text(prefix=prefix)


def emit_jsonl(fh, event: str = "metrics", **extra) -> dict:
    """Append the process-wide registry snapshot as one JSONL record."""
    return registry().emit_jsonl(fh, event=event, **extra)
