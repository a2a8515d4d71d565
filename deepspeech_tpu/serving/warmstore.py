"""Zero-compile restarts: preload the rung ladder from a warm store.

A process restart, an autoscale scale-up, and a rolling-swap
re-admission all used to serve degraded while jit re-compiled the
``(B, T)`` ladder rung by live rung. :class:`WarmStore` closes that
gap against a :class:`~deepspeech_tpu.utils.aotstore.AotStore`:

- **preload** (:meth:`preload_replica`) — at ``Replica.from_inferencer``
  (and again at autoscale scale-up / rollout re-admission, which build
  or re-version replicas), deserialize every stored rung for the
  replica's ``(preset, tier, version)`` under the host fingerprint and
  install the executables on the inferencer
  (``Inferencer.preloaded_forwards``) BEFORE admission. Every rung is
  counted ``compile_cache_{hit,miss,reject}{rung=...,tier=...,
  replica=...}`` — a *reject* is an entry that exists only under a
  foreign fingerprint (a foreign-host artifact would abort; downgraded
  to a counter) or whose argument signature no longer matches. Misses
  and rejects fall back to jit; preload is never fatal. A ``warm_pct``
  gauge and one ``kind="warm_start"`` postmortem (numeric ``warm_pct``
  + ``compiles_avoided``; linted by ``tools/check_obs_schema.py``)
  record how warm the replica came up.
- **export** (:meth:`install_export_hook`) — the
  ``ShapeBucketCache.export_hook`` fires on each first-compile; the
  hook lowers the same rung through the AOT path the offline tools
  use (``Inferencer.compile_rung``) and serializes it into the store
  (background thread by default; ``background=False`` for
  deterministic benches/tests — call :meth:`flush` either way before
  asserting on store contents).

The store's tier key is the replica's quality tier when it has one
(``premium``/``bulk``); untiered replicas key by numeric family —
``int8`` for a PTQ-quantized backend, ``fp`` otherwise — so an int8
executable is never loaded into a full-precision replica or vice
versa. ``DS2_WARMSTORE_DIR`` (or ``serve.py --warm-store``) makes a
store the process default: ``Replica.from_inferencer`` preloads and
exports through it with no further wiring.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Dict, List, Optional, Tuple

import jax

from ..data.infer_bucket import ladder_shapes
from ..obs import timeline as _timeline
from ..resilience import postmortem
from ..utils import aotstore
from ..utils.aotstore import AotStore, StoreKey

logger = logging.getLogger(__name__)

DEFAULT_VERSION = "base"


def store_tier(inferencer, tier: Optional[str]) -> str:
    """The store/counter tier key (module docstring): the replica's
    quality tier, else the numeric family of its backend."""
    if tier:
        return str(tier)
    return "int8" if getattr(inferencer, "_quantized", False) else "fp"


def default_store() -> Optional["WarmStore"]:
    """Process-default store from ``DS2_WARMSTORE_DIR`` (None when
    unset) — the env hook ``serve.py --warm-store`` sets."""
    root = os.environ.get("DS2_WARMSTORE_DIR")
    return WarmStore(root) if root else None


class WarmStore:
    """See module docstring."""

    def __init__(self, root: str, *, preset: str = "",
                 fingerprint: Optional[str] = None,
                 background: bool = True,
                 postmortem_fn=postmortem.record):
        # Entries the offline tools emitted for THIS platform live
        # under the portable (machine-free) fingerprint — accept them
        # as hits rather than rejecting over the missing machine axis.
        portable = aotstore.fingerprint_for(jax.default_backend())
        self.store = AotStore(root, fingerprint=fingerprint,
                              fallback_fingerprints=(portable,))
        # Preset key override; '' = each inferencer's own cfg.preset.
        self.preset = preset
        self.background = background
        self._postmortem = postmortem_fn
        self._threads: List[threading.Thread] = []
        self._lock = threading.Lock()

    # -- key helpers -----------------------------------------------------
    def _preset_of(self, inferencer) -> str:
        return self.preset or getattr(inferencer.cfg, "preset", "") \
            or "default"

    def _key(self, inferencer, tier: Optional[str],
             version: Optional[str], b: int, t: int) -> StoreKey:
        return StoreKey(self._preset_of(inferencer),
                        store_tier(inferencer, tier),
                        version or DEFAULT_VERSION, int(b), int(t))

    @staticmethod
    def _labels(replica, tier_key: str, rung: str) -> Dict[str, str]:
        # The compile_cache_* family ALWAYS carries rung + tier (the
        # schema lint rejects bare series) — tierless replicas carry
        # their numeric-family tier key, never an empty label.
        lab = dict(replica.labels)
        lab["tier"] = tier_key
        lab["rung"] = rung
        return lab

    # -- preload ---------------------------------------------------------
    def preload_replica(self, replica, *, trigger: str = "replica_init",
                        shapes: Optional[List[Tuple[int, int]]] = None
                        ) -> dict:
        """Load the replica's ladder from the store before admission.

        Returns a summary dict (also written as the ``warm_start``
        postmortem). Replicas without an inferencer backend (streaming
        session factories, synthetic test replicas) are ineligible and
        skipped silently — this hook must be safe to call on any
        replica the autoscaler or rollout hands it."""
        inf = getattr(replica, "inferencer", None)
        if inf is None or not hasattr(inf, "preloaded_forwards"):
            return {"eligible": False, "hits": 0}
        if shapes is None:
            shapes = ladder_shapes(inf.cfg.data.bucket_frames,
                                   inf.cfg.data.batch_size)
        tier_key = store_tier(inf, replica.tier)
        version = replica.version or DEFAULT_VERSION
        sig = inf.forward_signature()
        hits = misses = rejects = 0
        loaded: List[Tuple[int, int]] = []
        for b, t in shapes:
            key = self._key(inf, replica.tier, version, b, t)
            status, meta, payload = self.store.lookup(key)
            if status == "hit" and meta is not None \
                    and meta.get("sig") and meta["sig"] != sig:
                # Same version label, different weights shape/dtype —
                # calling the stored executable would crash; reject
                # like a fingerprint mismatch.
                status, payload = "reject", None
            if status == "hit":
                try:
                    fn = aotstore.deserialize_entry(meta, payload)
                except Exception as e:
                    logger.warning(
                        "warm store: deserialize failed for %s (%s: "
                        "%s) — falling back to jit", key.filename(),
                        type(e).__name__, e)
                    status = "reject"
                else:
                    inf.preloaded_forwards[(int(b), int(t))] = fn
                    loaded.append((int(b), int(t)))
                    hits += 1
                    replica.telemetry.count(
                        "compile_cache_hit",
                        labels=self._labels(replica, tier_key,
                                            key.rung))
                    continue
            if status == "reject":
                rejects += 1
                replica.telemetry.count(
                    "compile_cache_reject",
                    labels=self._labels(replica, tier_key, key.rung))
            else:
                misses += 1
                replica.telemetry.count(
                    "compile_cache_miss",
                    labels=self._labels(replica, tier_key, key.rung))
        if loaded:
            inf.shape_cache.preload(loaded)
        warm_pct = round(100.0 * hits / max(len(shapes), 1), 3)
        gauge_labels = dict(replica.labels)
        gauge_labels["tier"] = tier_key
        replica.telemetry.gauge("warm_pct", warm_pct,
                                labels=gauge_labels)
        summary = {"eligible": True, "replica": replica.rid,
                   "tier": tier_key, "version": version,
                   "rungs": len(shapes), "hits": hits,
                   "misses": misses, "rejects": rejects,
                   "warm_pct": warm_pct, "compiles_avoided": hits}
        self._postmortem(
            "warm_start", trigger=trigger, replica=replica.rid,
            tier=tier_key, version=version, rungs=len(shapes),
            warm_pct=warm_pct, compiles_avoided=hits,
            misses=misses, rejects=rejects)
        _timeline.publish(
            "warm_preload", "warmstore", replica=replica.rid,
            tier=tier_key, cause_seq=_timeline.last_for(replica.rid),
            trigger=trigger, warm_pct=warm_pct,
            compiles_avoided=hits, rungs=len(shapes))
        return summary

    # -- export ----------------------------------------------------------
    def install_export_hook(self, replica) -> bool:
        """First-compile -> serialize: arm the replica's shape-cache
        hook so every rung jit compiles at runtime lands in the store
        (the next restart preloads it)."""
        inf = getattr(replica, "inferencer", None)
        if inf is None or not hasattr(inf, "compile_rung"):
            return False

        def hook(b: int, t: int) -> None:
            if self.background:
                th = threading.Thread(
                    target=self._export_rung, args=(replica, b, t),
                    name=f"warmstore-export-{b}x{t}", daemon=True)
                with self._lock:
                    self._threads.append(th)
                th.start()
            else:
                self._export_rung(replica, b, t)

        inf.shape_cache.export_hook = hook
        return True

    def _export_rung(self, replica, b: int, t: int) -> None:
        inf = getattr(replica, "inferencer", None)
        if inf is None:
            return
        tier_key = store_tier(inf, replica.tier)
        key = self._key(inf, replica.tier,
                        replica.version or DEFAULT_VERSION, b, t)
        try:
            comp = inf.compile_rung(b, t)
            blob = aotstore.serialize_compiled(comp)
            self.store.put(key, blob, aotstore.FORMAT_EXECUTABLE,
                           sig=inf.forward_signature())
        except Exception as e:
            # Serialization is opportunistic: a backend whose
            # executables can't serialize (or a full disk) must never
            # take the serving path down.
            logger.warning("warm store: export failed for %s (%s: %s)",
                           key.filename(), type(e).__name__, e)
            return
        replica.telemetry.count(
            "compile_cache_export",
            labels=self._labels(replica, tier_key, key.rung))

    def export_ladder(self, replica,
                      shapes: Optional[List[Tuple[int, int]]] = None
                      ) -> int:
        """Eagerly serialize a replica's whole ladder (offline
        populate — the runtime twin of ``aot_infer --emit-store``).
        Returns how many rungs were written."""
        inf = getattr(replica, "inferencer", None)
        if inf is None or not hasattr(inf, "compile_rung"):
            return 0
        if shapes is None:
            shapes = ladder_shapes(inf.cfg.data.bucket_frames,
                                   inf.cfg.data.batch_size)
        n0 = len(self.store.keys())
        for b, t in shapes:
            self._export_rung(replica, b, t)
        return len(self.store.keys()) - n0

    def flush(self, timeout: float = 60.0) -> None:
        """Join pending background exports (benches/tests assert on
        store contents; the serving loop never needs to call this)."""
        with self._lock:
            threads, self._threads = self._threads, []
        for th in threads:
            th.join(timeout)
