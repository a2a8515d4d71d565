"""Serving gateway: request scheduling in front of the compiled core.

The model side of serving has been static-shape disciplined since PR 1
(shape ladder, ``decode_batch_bucketed``, ``ShapeBucketCache``); this
package is the layer that turns *independent, concurrently arriving*
work into those ladder-shaped batches:

- :mod:`.scheduler` — deadline-aware dynamic micro-batcher for offline
  transcribe requests (admission control, rung-full / oldest-deadline
  flush, free-slot fill, per-request retry + timeout);
- :mod:`.session` — streaming session manager: live streams join and
  leave a running padded batch mid-flight, slots are reused instead of
  recompiling when the connection count churns;
- :mod:`.replica` / :mod:`.pool` — the multi-replica serving plane:
  N :class:`Replica` executors (own backend, own shape-cache ladder,
  own breaker, labeled telemetry) behind a :class:`ReplicaPool` with
  consistent-hash session pinning, least-loaded spill, breaker-driven
  drain/re-pin, and brownout replica parking;
  :class:`PooledSessionRouter` runs streaming sessions across the
  pool's per-replica session managers;
- :mod:`.rollout` — zero-downtime rolling model swap:
  :class:`RolloutController` drains one replica at a time behind the
  existing window, swaps its backend (new checkpoint or quantization
  tier), shadow-canaries old vs new transcripts under a WER guardrail,
  and rolls back + halts (postmortem included) on regression or
  mid-swap fault;
- :mod:`.autoscale` / :mod:`.trafficmodel` — closed-loop fleet
  sizing: :class:`AutoscaleController` reads the ``obs`` signals the
  plane already publishes (queue fill, occupancy, dispatch p95,
  brownout level, SLO burn) and resizes the pool through a hysteresis
  state machine with drain-before-remove; :class:`TrafficModel`
  generates the deterministic diurnal/bursty/heavy-tailed arrival
  schedules ``tests/test_autoscale.py``
  ``test_scenario_modeled_day_scales_up_and_down_losing_nothing``
  replays it against;
- :mod:`.registry` / :mod:`.tenancy` — the multi-model multi-tenant
  gateway: :class:`ModelRegistry` maps ``model_id`` to a
  :class:`ModelGroup` (its own pool, rung ladder, controller scope;
  :class:`GroupState` holds the factored-out controller bookkeeping),
  while :class:`AdmissionController` enforces per-tenant quotas,
  priority-class deadlines/shed order, and weighted-fair dequeue —
  one serving plane routing N models under per-tenant quotas;
- :mod:`.migration` — live session migration: a
  :class:`StreamSnapshot` captures one session's slot-sliced recurrent
  state (plus decoder rows and a config fingerprint) and a
  :class:`MigrationController` hands it off between replicas —
  breaker re-pins, autoscale scale-downs and rollout victims move
  mid-utterance sessions with bit-identical transcripts and zero
  drain wait, falling back to the segment drain on incompatibility;
- :mod:`.sessionstore` — crash durability for those same snapshots: a
  versioned CRC-checksummed wire codec
  (:func:`snapshot_to_bytes`/:func:`snapshot_from_bytes`), an
  append-only segment-rotated :class:`SessionJournal` the session
  manager checkpoints into, and a :class:`RecoveryController` that
  replays the journal at boot (torn-tail tolerant) so a killed serve
  process restarts with zero lost sessions;
- :mod:`.transport` — cross-process session handoff over those same
  snapshot bytes: a handshake-gated (codec version / fingerprint /
  model version), two-phase idempotent transfer plane with
  :class:`LoopbackTransport` (in-memory, deterministic) and
  :class:`SocketTransport`/:class:`HandoffListener` (stdlib TCP,
  CRC-framed) under retry + per-peer circuit breaking, and a
  :class:`RemoteMigrationController` whose degradation ladder —
  remote handoff → local journal-recovery re-pin → legacy drain
  re-pin — never loses a session;
- :mod:`.rescoring` — the async LM second pass (fast-path/slow-path
  split): first-pass results return at today's latency; results
  carrying an n-best are enqueued into a bounded
  :class:`RescoringQueue` drained by a pump-driven
  :class:`RescoringPool` (per-worker LMs, batch-class tenancy, a
  dedicated brownout rung that sheds rescoring before any first-pass
  degradation) which emits :class:`RevisionEvent` streams — the
  ``{"revision": ...}`` JSONL lines beside the original transcripts;
- :mod:`.telemetry` — counters/gauges/histograms for all of it,
  emitted as JSONL (linted by ``tools/check_obs_schema.py``);
- :mod:`.ladder` — tier-aware rung-ladder sizing: converts measured
  parameter footprints (bf16 vs int8 PTQ) plus a per-row cost into
  per-tier max-B heights under an HBM budget.
"""

from .autoscale import AutoscaleController
from .ladder import (max_batch_for_budget, recurrent_stream_bytes,
                     tier_max_batches)
from .migration import (MigrationController, SnapshotIncompatible,
                        StreamSnapshot)
from .pool import PooledSessionRouter, ReplicaPool
from .registry import GroupState, ModelGroup, ModelRegistry
from .replica import Replica, synthetic_replicas
from .rescoring import RescoringPool, RescoringQueue, RevisionEvent
from .rollout import RolloutController
from .scheduler import (GatewayResult, MicroBatch, MicroBatchScheduler,
                        OverloadRejected)
from .session import StreamingSessionManager
from .sessionstore import (CODEC_VERSION, RecoveryController,
                           SessionJournal, SnapshotDecodeError,
                           snapshot_from_bytes, snapshot_to_bytes)
from .telemetry import Histogram, ServingTelemetry
from .tenancy import (AdmissionController, TenantConfig,
                      TenantQuotaExceeded)
from .transport import (HandoffListener, HandoffReceiver,
                        HandshakeRejected, LoopbackTransport,
                        RemoteMigrationController, SocketTransport,
                        TransportError)
from .trafficmodel import Arrival, Schedule, SessionPlan, TrafficModel
from .warmstore import WarmStore

__all__ = [
    "AdmissionController",
    "Arrival",
    "AutoscaleController",
    "CODEC_VERSION",
    "GatewayResult",
    "GroupState",
    "HandoffListener",
    "HandoffReceiver",
    "HandshakeRejected",
    "Histogram",
    "LoopbackTransport",
    "MicroBatch",
    "MicroBatchScheduler",
    "MigrationController",
    "ModelGroup",
    "ModelRegistry",
    "OverloadRejected",
    "PooledSessionRouter",
    "RecoveryController",
    "RemoteMigrationController",
    "Replica",
    "ReplicaPool",
    "RescoringPool",
    "RescoringQueue",
    "RevisionEvent",
    "RolloutController",
    "Schedule",
    "ServingTelemetry",
    "SessionJournal",
    "SessionPlan",
    "SnapshotDecodeError",
    "SnapshotIncompatible",
    "SocketTransport",
    "StreamSnapshot",
    "StreamingSessionManager",
    "TenantConfig",
    "TenantQuotaExceeded",
    "TransportError",
    "TrafficModel",
    "WarmStore",
    "max_batch_for_budget",
    "recurrent_stream_bytes",
    "snapshot_from_bytes",
    "snapshot_to_bytes",
    "synthetic_replicas",
    "tier_max_batches",
]
