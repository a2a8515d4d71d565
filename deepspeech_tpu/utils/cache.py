"""Persistent XLA compilation cache + compiled-shape accounting.

The flagship ds2_full training-step graph costs minutes to compile
cold on a TPU host; a persistent on-disk cache makes every later
`train`/`infer`/bench invocation on the same machine reuse the
serialized executables (SURVEY.md §7 hard-parts #4: per-bucket
executables without recompilation storms — this extends the no-storm
guarantee across processes). Opt out with DS2_COMPILE_CACHE=0.

Where the cache lives: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (jax reads that variable itself, so the program
sets no directory in code), else ``<checkout>/.jax_cache`` — one fixed
path, because the path is part of the cache key and a directory that
moves never hits.
"""

from __future__ import annotations

import json
import logging
import os
import time

logger = logging.getLogger(__name__)

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def resolve_cache_dir() -> str:
    """The directory this process's compile cache uses (markers and
    sidecars written next to the executables resolve it the same
    way)."""
    return os.environ.get(CACHE_DIR_ENV) or _DEFAULT_DIR


def enable_compilation_cache() -> bool:
    """Turn on jax's persistent compile cache at :func:`resolve_cache_dir`.

    Returns True only when the cache is configured — callers asserting
    "a later process will reuse this compile" must not claim warmth
    otherwise.
    """
    if os.environ.get("DS2_COMPILE_CACHE", "1") == "0":
        return False
    import jax

    if not os.environ.get(CACHE_DIR_ENV):
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return True


class ShapeBucketCache:
    """Compiled-shape ledger for the bucketed infer path.

    ``jax.jit`` already memoizes per input shape; what it does NOT give
    the serving loop is (a) visibility — how many executables this
    request actually compiled and how much of the computed volume was
    padding — and (b) a bound — a caller feeding off-ladder shapes
    silently turns the shape ladder into a recompilation storm. This
    ledger provides both: ``note()`` before every jitted forward call
    records the ``(B, T)`` shape and the real-frame count, and when the
    distinct-shape set exceeds ``max_shapes`` (the planner's ladder
    size) it warns once per offending shape — loud enough to catch a
    planner bypass, non-fatal so overflow rungs (long audio beyond the
    largest edge) still serve.

    The working set is additionally *time-decayed* on a logical clock
    (one tick per ``note``): each shape's usage score halves every
    ``half_life`` calls since it was last seen, and when the working
    set outgrows ``max_shapes`` the COLDEST shape is evicted from it
    (and the warning fires, as before). Eviction is ledger-side only —
    ``jax.jit``'s own executable cache is unbounded and nothing gets
    un-compiled — so ``compiles``/``hits`` stay cumulative truths while
    ``rung_usage()``/``live_shapes`` describe the *recently hot* ladder,
    the feedback signal the serving gateway's rung chooser reads
    (serving/scheduler.warm_rung_chooser) and the input a future
    donate-the-executable eviction would act on.

    Counters:
      compiles       distinct shapes ever seen (== XLA compile count for
                     the wrapped jit, since jit caches per shape)
      hits           calls that reused an already-seen shape
      evictions      cold shapes dropped from the working set
      padded_frames  total B*T frames computed
      valid_frames   real (pre-padding) frames among them
      padding_waste  1 - valid/padded, the headline waste fraction
    """

    def __init__(self, max_shapes: int = 0, half_life: int = 256):
        if half_life <= 0:
            raise ValueError(f"half_life must be positive, got {half_life}")
        self.max_shapes = max_shapes
        self.half_life = half_life
        # Extra labels merged into every compile event this ledger
        # reports — a pooled replica sets {"replica": rid} so compiles
        # attribute per replica (serving/replica.py).
        self.labels: "dict[str, str] | None" = None
        # First-compile export hook (serving/warmstore.py): called as
        # ``export_hook(batch, frames)`` right after a fresh shape is
        # recorded, so the executable jit is about to build gets
        # serialized into the warm store. Never fatal (see note()).
        self.export_hook = None
        self._tick = 0
        self._use: "dict[tuple, float]" = {}   # decayed usage score
        self._last: "dict[tuple, int]" = {}    # last-seen tick
        self._ever: "set[tuple]" = set()
        # Shapes whose executables were installed from the warm store
        # BEFORE any traffic: they are hits from call one and never
        # fire a compile event — but they are not counted in
        # ``compiles`` either, because no runtime compile happened
        # (the whole point of preloading).
        self._preloaded: "set[tuple]" = set()
        self.hits = 0
        self.evictions = 0
        self.padded_frames = 0
        self.valid_frames = 0

    def _decayed(self, key: tuple) -> float:
        return self._use[key] * 0.5 ** (
            (self._tick - self._last[key]) / self.half_life)

    def note(self, batch: int, frames: int, valid_frames: int) -> bool:
        """Record one forward call; returns True on a shape hit."""
        key = (int(batch), int(frames))
        self._tick += 1
        hit = key in self._ever or key in self._preloaded
        if hit:
            self.hits += 1
        else:
            self._ever.add(key)
            # First sight of this (B, T) == one fresh XLA compile for
            # the wrapped jit: attribute it (rung + call site) via the
            # observability layer. Never fatal — the ledger must keep
            # counting even if obs is mid-teardown.
            try:
                from .. import obs

                obs.compile_event(*key, labels=self.labels)
            except Exception:
                pass
            if self.export_hook is not None:
                try:
                    self.export_hook(*key)
                except Exception:
                    logger.debug("shape-cache export hook failed for "
                                 "B=%d T=%d", *key, exc_info=True)
        self._use[key] = (self._decayed(key) if key in self._use
                          else 0.0) + 1.0
        self._last[key] = self._tick
        if self.max_shapes and len(self._use) > self.max_shapes:
            cold = min((k for k in self._use if k != key),
                       key=self._decayed)
            logger.warning(
                "infer shape cache grew past the ladder: %d shapes > "
                "max_shapes=%d (new shape B=%d T=%d) — off-ladder "
                "batches recompile; route requests through "
                "data/infer_bucket.plan_infer_buckets "
                "(evicting cold rung B=%d T=%d, usage %.3f)",
                len(self._use), self.max_shapes, *key, *cold,
                self._decayed(cold))
            del self._use[cold]
            del self._last[cold]
            self.evictions += 1
        self.padded_frames += int(batch) * int(frames)
        self.valid_frames += int(valid_frames)
        return hit

    def preload(self, shapes, score: float = 1.0) -> int:
        """Mark ``(B, T)`` shapes as already-compiled (their
        executables were installed from the warm store): their first
        ``note()`` is a hit, fires no compile event, and ``compiles``
        stays at the number of RUNTIME compiles — zero for a fully
        preloaded ladder. Returns how many shapes were newly marked."""
        added = 0
        for b, t in shapes:
            key = (int(b), int(t))
            if key in self._preloaded or key in self._ever:
                continue
            self._preloaded.add(key)
            if key not in self._use:
                self._use[key] = float(score)
                self._last[key] = self._tick
            added += 1
        return added

    @property
    def compiles(self) -> int:
        return len(self._ever)

    @property
    def preloaded(self) -> int:
        return len(self._preloaded)

    @property
    def padding_waste(self) -> float:
        if not self.padded_frames:
            return 0.0
        return 1.0 - self.valid_frames / self.padded_frames

    def rung_usage(self) -> "dict[tuple, float]":
        """Decayed usage score per live ``(B, T)`` rung — the warm-set
        feedback the gateway's rung chooser consumes."""
        return {k: round(self._decayed(k), 6) for k in self._use}

    def stats(self) -> dict:
        """JSONL-ready counter snapshot."""
        return {
            "compiles": self.compiles,
            "hits": self.hits,
            "evictions": self.evictions,
            "preloaded": self.preloaded,
            "max_shapes": self.max_shapes,
            "shapes": sorted(self._ever),
            "live_shapes": sorted(self._use),
            "padded_frames": self.padded_frames,
            "valid_frames": self.valid_frames,
            "padding_waste": round(self.padding_waste, 6),
        }


# -- rung-usage persistence (warm_rung_chooser restart seeding) ----------

USAGE_SIDECAR = "rung_usage.jsonl"


def usage_sidecar_path() -> str:
    """The rung-usage sidecar lives next to the compiled executables
    it describes (same resolution as the compile cache)."""
    return os.path.join(resolve_cache_dir(), USAGE_SIDECAR)


def save_rung_usage(cache: ShapeBucketCache, path: str,
                    **extra) -> dict:
    """Append one JSONL snapshot of ``cache.rung_usage()`` — a restart
    seeds ``warm_rung_chooser`` from it (:func:`load_rung_usage`) so
    the hot-rung routing signal survives the process. Appending (not
    rewriting) keeps earlier eras readable for forensics; the loader
    merges last-wins."""
    usage = {f"{b}x{t}": score
             for (b, t), score in cache.rung_usage().items()}
    rec = {"event": "rung_usage", "ts": round(time.time(), 3),
           "usage": usage, **extra}
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "a") as fh:
        fh.write(json.dumps(rec) + "\n")
    return rec


def load_rung_usage(path: str) -> "dict[tuple, float]":
    """Merged ``{(B, T): score}`` from a sidecar, newest era winning
    per rung. Tolerant by contract: an absent file, a torn tail line,
    or mixed-era records (an older writer's shapes) must never block a
    restart — unreadable lines are skipped, unparseable rungs dropped.
    """
    usage: "dict[tuple, float]" = {}
    try:
        with open(path) as fh:
            lines = fh.readlines()
    except OSError:
        return usage
    for line in lines:
        if not line.strip():
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if not isinstance(rec, dict) \
                or not isinstance(rec.get("usage"), dict):
            continue
        for rung, score in rec["usage"].items():
            try:
                b, t = str(rung).split("x", 1)
                usage[(int(b), int(t))] = float(score)
            except (TypeError, ValueError):
                continue
    return usage


def seed_usage(cache: ShapeBucketCache,
               usage: "dict[tuple, float]") -> int:
    """Seed a fresh ledger's working set from persisted usage — the
    routing signal ONLY: seeded rungs are not marked compiled (a cold
    jit will still genuinely compile them and must be counted), they
    just rank as warm for the chooser. Bounded by ``max_shapes`` (top
    scores win) so a stale fat sidecar can't trigger evictions."""
    ranked = sorted(usage.items(), key=lambda kv: -kv[1])
    if cache.max_shapes:
        ranked = ranked[:cache.max_shapes]
    seeded = 0
    for (b, t), score in ranked:
        key = (int(b), int(t))
        if key in cache._use:
            continue
        cache._use[key] = float(score)
        cache._last[key] = cache._tick
        seeded += 1
    return seeded
