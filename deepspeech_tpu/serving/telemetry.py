"""Gateway observability: counters, gauges, histograms, JSONL emission.

Historically this module owned the only metrics sink in the repo; the
implementation now lives in ``deepspeech_tpu/obs/metrics.py`` as the
shared, thread-safe :class:`~deepspeech_tpu.obs.MetricsRegistry`, and
this module is a thin compatibility shim: the scheduler/session
manager keep their ``telemetry.count(...)`` call sites and the
output shape stays (``snapshot()`` dict and the
``"serving_telemetry"`` JSONL event),
while gaining the registry's labels, ``render_text()`` exposition and
the drift-free reservoir ``Histogram``.

Conventions (unchanged):
- counters are monotone event counts (``admitted``, ``rejected``, ...);
- gauges are last-observed values (``queue_depth``, ``capacity``);
- histograms keep a bounded reservoir and report count/mean/p50/p95/max
  — request latency and batch occupancy are the headline ones;
- per-rung usage is a counter keyed by the padded ``(B, T)`` shape, the
  live-traffic complement of ``ShapeBucketCache.rung_usage()``.

``snapshot()`` returns one JSON-ready dict; ``emit_jsonl()`` appends it
as one line, the format ``tools/check_obs_schema.py`` lints.
"""

from __future__ import annotations

from typing import IO

from ..obs.metrics import Histogram, MetricsRegistry

__all__ = ["Histogram", "ServingTelemetry"]


class ServingTelemetry(MetricsRegistry):
    """One sink shared by the scheduler and the session manager — a
    per-run :class:`MetricsRegistry` whose JSONL event keeps the
    historical ``"serving_telemetry"`` name."""

    def emit_jsonl(self, fh: IO[str], event: str = "serving_telemetry",
                   **extra) -> dict:
        """Append one JSONL record of the current snapshot; returns it."""
        return super().emit_jsonl(fh, event=event, **extra)
