"""Postmortem records: durable evidence for every automatic recovery.

Self-healing only earns trust when each intervention leaves a record a
human can audit afterwards: which utterance was quarantined and why,
which step tripped the guardian, what the thread stacks looked like
when the watchdog fired. A :class:`PostmortemWriter` appends one JSONL
line per intervention and keeps a bounded in-memory tail for callers
(tests) that never configure a file.

Record schema (linted by ``tools/check_obs_schema.py``, which knows
``event == "postmortem"`` as its own record type)::

    {"event": "postmortem", "ts": <wall s>, "kind": <str>,
     "trigger": <str>, ...evidence}

``kind`` names the intervention class — the wired producers:

- ``corrupt_sample``      — data/pipeline.py quarantine (utt, stats)
- ``anomaly``             — guardian skip/backoff/rollback (step, loss,
  grad_norm, update_norm)
- ``rollback``            — guardian restore of a last-good snapshot
- ``stall``               — watchdog fire (all-thread stacks, metrics
  snapshot)
- ``quarantined_request`` — serving/scheduler.py poison isolation (rid,
  rung, attempts)
- ``rollout``             — serving/rollout.py rolling-swap rollback
  (replica, from/to version, trigger = ``canary_regression`` with the
  WER delta or ``swap_fault`` with the error; evidence includes the
  flight recorder's recent request traces)
- ``slo_burn``            — obs/slo.py burn-rate alert (window,
  burn_rate, threshold, and the slowest recent requests from the
  flight recorder with their attributed causes; linted shape —
  ``check_obs_schema`` requires ``window`` + numeric ``burn_rate``)
- ``breaker_open``        — serving/scheduler.py circuit-breaker
  rising edge (the failure that tripped it, plus recent traces)
- ``warm_start``          — serving/warmstore.py ladder preload at
  replica init / autoscale scale-up / rollout re-admission (replica,
  tier, version, rung counts; linted shape — ``check_obs_schema``
  requires numeric ``warm_pct`` + ``compiles_avoided``)
- ``incident``            — obs/timeline.py correlated incident close
  (root event, ordered causal chain, resolution, replicas touched;
  linted shape — ``check_obs_schema`` requires numeric
  ``duration_s`` + ``n_events`` and a ``root_kind`` string)

``trigger`` is the specific condition inside the kind (``nan_features``,
``nonfinite_loss``, ``no_heartbeat`` ...). Everything else is
kind-specific evidence; keep values JSON-native.

Every write is counted in the metrics registry as
``postmortems_written{kind=...}`` plus the bare total. Configuration
mirrors the other env hooks: export ``DS2_POSTMORTEM=/path/pm.jsonl``
or call :func:`configure`; without a path, records still count and
stay readable via :meth:`PostmortemWriter.recent`.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Callable, IO, List, Optional

from .. import obs


class PostmortemWriter:
    """Thread-safe JSONL postmortem sink with a bounded recent tail."""

    def __init__(self, path: Optional[str] = None,
                 sink: Optional[IO[str]] = None,
                 registry=None,
                 wall: Callable[[], float] = time.time,
                 max_recent: int = 256):
        self._lock = threading.Lock()
        self._registry = registry
        self._wall = wall
        self._recent: deque = deque(maxlen=max_recent)
        self._sink = sink
        self._owns_sink = False
        if path:
            self._sink = open(path, "a")
            self._owns_sink = True

    def _reg(self):
        return self._registry if self._registry is not None \
            else obs.registry()

    def write(self, kind: str, trigger: str = "", **evidence) -> dict:
        """Record one intervention; returns the record written."""
        rec = {"event": "postmortem", "ts": round(self._wall(), 6),
               "kind": kind, "trigger": trigger, **evidence}
        line = json.dumps(rec, ensure_ascii=False, default=str)
        with self._lock:
            self._recent.append(rec)
            if self._sink is not None:
                self._sink.write(line + "\n")
                self._sink.flush()
        self._reg().count("postmortems_written")
        self._reg().count("postmortems_written", labels={"kind": kind})
        return rec

    def recent(self, kind: Optional[str] = None) -> List[dict]:
        with self._lock:
            recs = list(self._recent)
        return recs if kind is None else \
            [r for r in recs if r.get("kind") == kind]

    def written(self) -> int:
        return int(self._reg().counter("postmortems_written"))

    def close(self) -> None:
        with self._lock:
            if self._sink is not None and self._owns_sink:
                try:
                    self._sink.close()
                except Exception:
                    pass
            self._sink, self._owns_sink = None, False


# -- process-wide default ----------------------------------------------
_DEFAULT: Optional[PostmortemWriter] = None
_DEFAULT_LOCK = threading.Lock()


def writer() -> PostmortemWriter:
    """The process-wide writer (created lazily; honors
    ``DS2_POSTMORTEM`` at first use)."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        if _DEFAULT is None:
            _DEFAULT = PostmortemWriter(
                path=os.environ.get("DS2_POSTMORTEM") or None)
        return _DEFAULT


def configure(path: Optional[str] = None, sink: Optional[IO[str]] = None,
              registry=None) -> PostmortemWriter:
    """Replace the process-wide writer (tests)."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        if _DEFAULT is not None:
            _DEFAULT.close()
        _DEFAULT = PostmortemWriter(path=path, sink=sink,
                                    registry=registry)
        return _DEFAULT


def record(kind: str, trigger: str = "", **evidence) -> dict:
    """Convenience: write through the process-wide writer."""
    return writer().write(kind, trigger, **evidence)


# Register into the obs-side seam (obs/postmortem_link.py): obs
# callers (SLO alerts, the incident correlator) reach the writer
# through it without importing resilience at module load.
obs.set_postmortem_recorder(record)
