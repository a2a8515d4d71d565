"""Fault tolerance: chaos injection, retry/breaker, degradation.

The production north-star (ROADMAP) serves heavy traffic on
preemptible accelerators behind a flaky remote backend; the recorded
bench history already shows every failure mode this package exists
for. Four modules, one per concern:

- :mod:`.faults` — deterministic fault *injection*: a process-wide
  :class:`FaultPlan` (env/JSON-configurable, seeded, injectable clock)
  fires scheduled faults at named points in the gateway, data
  pipeline, checkpointing, and backend init. Near-zero cost when no
  plan is installed.
- :mod:`.retry` — :class:`Retry` (exponential backoff + jitter,
  budget-capped) and :class:`CircuitBreaker` (closed/open/half-open
  with cooldown), both metered through ``obs``.
- :mod:`.brownout` — :class:`BrownoutController`: sustained queue
  pressure degrades the gateway (smaller rungs, beam→greedy, load
  shedding) and surfaces a ``degraded`` gauge.
- :mod:`.preempt` — :class:`PreemptionGuard`: SIGTERM latches a flag,
  ``train.fit`` writes an emergency checkpoint and exits cleanly;
  resume is bit-identical.
- :mod:`.guardian` — :class:`TrainingGuardian` +
  :class:`StallWatchdog`: per-step health classification (loss
  finiteness, grad/update norms vs rolling stats), the skip/backoff/
  rollback policy ladder over the ``CheckpointManager`` last-good
  ring, and a heartbeat watchdog that dumps stacks and triggers the
  preemption path when a step wedges.
- :mod:`.postmortem` — :class:`PostmortemWriter`: one JSONL record per
  automatic intervention (quarantined sample/request, anomaly,
  rollback, stall), shared by the data pipeline, the guardian, and the
  serving scheduler.

End-to-end validation (``tests/test_resilience.py``):
``test_scenario_traffic_under_fault_plan_loses_nothing`` replays
modeled traffic under an injected fault schedule (nothing lost, breaker
opens and recovers, transcripts unchanged);
``test_scenario_training_survives_poison_and_leaves_no_trace`` runs a
seeded divergence/corruption plan through the guarded trainer and
asserts rollback bit-identity.
"""

from . import faults, postmortem
from .brownout import (LEVEL_BROWNOUT, LEVEL_DEGRADED, LEVEL_NORMAL,
                       LEVEL_REPLICA_DRAIN, BrownoutController)
from .faults import (FaultPlan, FaultSpec, InjectedFault,
                     validate_plan_dict)
from .guardian import (GuardianConfig, GuardianDecision, GuardianHalt,
                       StallWatchdog, TrainingGuardian)
from .postmortem import PostmortemWriter
from .preempt import PreemptionGuard
from .retry import CircuitBreaker, CircuitOpen, Retry

__all__ = [
    "BrownoutController",
    "CircuitBreaker",
    "CircuitOpen",
    "FaultPlan",
    "FaultSpec",
    "GuardianConfig",
    "GuardianDecision",
    "GuardianHalt",
    "InjectedFault",
    "LEVEL_BROWNOUT",
    "LEVEL_DEGRADED",
    "LEVEL_NORMAL",
    "LEVEL_REPLICA_DRAIN",
    "PostmortemWriter",
    "PreemptionGuard",
    "Retry",
    "StallWatchdog",
    "TrainingGuardian",
    "faults",
    "postmortem",
    "validate_plan_dict",
]
