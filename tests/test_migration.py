"""Live session migration: snapshot/handoff bit-identity + fallbacks.

Covers the ISSUE-17 contracts: a mid-utterance session exported from
one StreamingSessionManager and imported into another (different
clock, including a COLDER one — negative re-based ``raw_start``)
continues bit-identically to the never-migrated stream, greedy and
beam, padded tail included; draining sessions refuse to export; a
fingerprint mismatch rejects the import with the source left intact;
and the pool-level MigrationController hands sessions off on breaker
re-pins (same segment, zero drain wait, counted + postmortemed) while
version/config/manager incompatibility falls back to the legacy
segment drain with no lost chunks.

Model-backed tests reuse the tiny ds2_streaming config idiom from
tests/test_serving.py; pool-level fallback tests ride duck-typed
managers and a virtual clock — no model, deterministic.
"""

import dataclasses

import numpy as np
import pytest

from deepspeech_tpu.resilience import CircuitBreaker
from deepspeech_tpu.serving import (MigrationController,
                                    PooledSessionRouter, Replica,
                                    ReplicaPool, ServingTelemetry,
                                    SnapshotIncompatible,
                                    StreamingSessionManager)

NF = 13


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _mgr(tiny_streaming, **kw):
    cfg, tok, params, stats = tiny_streaming
    return StreamingSessionManager(cfg, params, stats, tok,
                                   chunk_frames=64, **kw)


def _chunks(f, k=64):
    n = f.shape[0] // k
    return [f[i * k:(i + 1) * k] for i in range(n)], f[n * k:]


def _feat(n, seed):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, NF)).astype(np.float32)


def _solo(tiny_streaming, feat, decode="greedy"):
    """Never-migrated reference: one manager, one slot, same chunks."""
    mgr = _mgr(tiny_streaming, capacity=1, decode=decode)
    mgr.join("ref")
    chunks, tail = _chunks(feat)
    for c in chunks:
        mgr.step({"ref": c})
    mgr.leave("ref", tail=tail if tail.shape[0] else None)
    mgr.flush()
    return mgr.final("ref")


# -- manager-level export/import ------------------------------------------

def test_export_import_greedy_bit_identical_cold_target(tiny_streaming):
    """Migrate mid-utterance into a FRESH manager (clock 0 < fed):
    the re-based raw_start goes negative and the continuation is
    still bit-identical to the never-migrated stream."""
    f = _feat(256, seed=10)
    chunks, _ = _chunks(f)
    src = _mgr(tiny_streaming, capacity=2)
    dst = _mgr(tiny_streaming, capacity=2)
    src.join("x")
    src.step({"x": chunks[0]})
    src.step({"x": chunks[1]})
    snap = src.export_session("x")
    # The source is quiet the moment the export returns: no drain.
    assert src.stats()["active"] == 0 and src.stats()["draining"] == 0
    assert dst.clock == 0 and snap.fed == 128
    dst.import_session(snap)
    assert dst._sessions["x"].raw_start == -128
    dst.step({"x": chunks[2]})
    dst.step({"x": chunks[3]})
    dst.leave("x")
    dst.flush()
    assert dst.final("x") == _solo(tiny_streaming, f)
    assert int(src.telemetry.counters.get("sessions_exported", 0)) == 1
    assert int(dst.telemetry.counters.get("sessions_imported", 0)) == 1


def test_export_import_greedy_warm_target_padded_tail(tiny_streaming):
    """Migrate into a manager whose clock is AHEAD of the source
    (another session has been streaming there), then finish with a
    padded tail chunk — still bit-identical."""
    f = _feat(64 * 3 + 37, seed=11)         # padded tail of 37 frames
    g = _feat(64 * 4, seed=12)              # the target's own session
    chunks, tail = _chunks(f)
    gchunks, _ = _chunks(g)
    src = _mgr(tiny_streaming, capacity=2)
    dst = _mgr(tiny_streaming, capacity=2)
    dst.join("w")
    dst.step({"w": gchunks[0]})
    dst.step({"w": gchunks[1]})             # dst.clock = 128
    src.join("x")
    src.step({"x": chunks[0]})              # src.clock = 64
    snap = src.export_session("x")
    dst.import_session(snap)
    assert dst._sessions["x"].raw_start == 128 - 64
    dst.step({"x": chunks[1], "w": gchunks[2]})
    dst.step({"x": chunks[2], "w": gchunks[3]})
    dst.leave("x", tail=tail)
    dst.leave("w")
    dst.flush()
    assert dst.final("x") == _solo(tiny_streaming, f)
    assert dst.final("w") == _solo(tiny_streaming, g)


def test_export_import_beam_bit_identical(tiny_streaming):
    """Beam mode: the carried dense beam state rows travel with the
    snapshot, so the migrated stream's beam search is bit-identical
    to the never-migrated one."""
    f = _feat(256, seed=13)
    chunks, _ = _chunks(f)
    src = _mgr(tiny_streaming, capacity=2, decode="beam")
    dst = _mgr(tiny_streaming, capacity=2, decode="beam")
    src.join("x")
    src.step({"x": chunks[0]})
    src.step({"x": chunks[1]})
    snap = src.export_session("x")
    assert snap.decoder is not None
    dst.import_session(snap)
    dst.step({"x": chunks[2]})
    dst.step({"x": chunks[3]})
    dst.leave("x")
    dst.flush()
    assert dst.final("x") == _solo(tiny_streaming, f, decode="beam")


def test_export_refuses_draining_session(tiny_streaming):
    """A mid-drain session cannot export — its remaining work is a
    local flush — and the refusal leaves the drain to finalize
    normally."""
    f = _feat(128, seed=14)
    chunks, _ = _chunks(f)
    mgr = _mgr(tiny_streaming, capacity=1)
    mgr.join("x")
    for c in chunks:
        mgr.step({"x": c})
    mgr.leave("x")
    with pytest.raises(ValueError, match="draining"):
        mgr.export_session("x")
    mgr.flush()
    assert mgr.final("x") == _solo(tiny_streaming, f)


def test_import_fingerprint_mismatch_rejects(tiny_streaming):
    """A snapshot whose fingerprint does not match the target raises
    SnapshotIncompatible BEFORE touching any slot, and the snapshot
    can still restore into a compatible manager."""
    f = _feat(128, seed=15)
    chunks, _ = _chunks(f)
    src = _mgr(tiny_streaming, capacity=1)
    src.join("x")
    src.step({"x": chunks[0]})
    snap = src.export_session("x")
    bad = dataclasses.replace(snap, fingerprint=snap.fingerprint + "|v2")
    dst = _mgr(tiny_streaming, capacity=1)
    with pytest.raises(SnapshotIncompatible):
        dst.import_session(bad)
    assert dst.stats()["active"] == 0
    # The untampered snapshot restores fine — nothing was lost.
    dst.import_session(snap)
    dst.step({"x": chunks[1]})
    dst.leave("x")
    dst.flush()
    assert dst.final("x") == _solo(tiny_streaming, f)


# -- pool-level handoff ---------------------------------------------------

def _breaker(clock, tel, name):
    return CircuitBreaker(name=name, failure_threshold=2,
                          cooldown_s=1.0, clock=clock, registry=tel)


def _trip(breaker):
    while breaker.state != "open":
        breaker.record_failure()


def _streaming_pool(tiny_streaming, clock, tel, n=2, decode="greedy",
                    handoff=True):
    def factory():
        return _mgr(tiny_streaming, capacity=2, decode=decode,
                    telemetry=tel)
    reps = [Replica(f"r{k}", telemetry=tel, clock=clock,
                    breaker=_breaker(clock, tel, f"b{k}"),
                    session_factory=factory)
            for k in range(n)]
    return ReplicaPool(reps, clock=clock, telemetry=tel,
                       drain_window_s=0.25, handoff=handoff)


def test_pool_breaker_handoff_bit_identical_zero_drain(tiny_streaming):
    """Breaker trips on the home replica mid-utterance: the session
    hands off by snapshot — SAME segment, no drain wait — and the
    final transcript is bit-identical to the never-migrated stream."""
    f = _feat(256, seed=16)
    chunks, _ = _chunks(f)
    clock = Clock()
    tel = ServingTelemetry()
    pm = []
    pool = _streaming_pool(tiny_streaming, clock, tel)
    mig = MigrationController(
        telemetry=tel, clock=clock,
        postmortem_fn=lambda kind, trigger="", **kw:
            pm.append((kind, trigger, kw)))
    router = PooledSessionRouter(pool, migrator=mig)
    home = router.join("a")
    router.step({"a": chunks[0]})
    router.step({"a": chunks[1]})
    old = pool.replica(home)
    _trip(old.breaker)
    router.step({"a": chunks[2]})       # maintain -> handoff, mid-step
    assert router.home_of("a") != home
    router.step({"a": chunks[3]})
    router.leave("a")
    router.flush()
    assert router.final("a") == _solo(tiny_streaming, f)
    # One topology change, one migration, zero fallbacks, no segment
    # split (a drain re-pin would have produced two segments).
    assert mig.stats() == {"migrations": 1, "fallbacks": 0,
                           "max_per_session": 1}
    assert len(router._segments["a"]) == 1
    assert router.stats()["migrations"] == 1
    # The tripped replica's manager went quiet at export time — no
    # draining slot is flushing behind the drain window.
    old_mgr = old.peek_session_manager()
    assert old_mgr.stats()["active"] == 0
    assert old_mgr.stats()["draining"] == 0
    # Counters + postmortem: reason-labeled migration families and
    # the kind="migration" handoff record.
    fams = [k for k in tel.counters if
            k.startswith("session_migrations{")]
    assert fams and 'reason="breaker"' in fams[0] \
        and 'replica="' in fams[0]
    kinds = [(k, kw.get("outcome")) for k, _, kw in pm if
             k == "migration"]
    assert ("migration", "handoff") in kinds


def test_pool_beam_handoff_bit_identical(tiny_streaming):
    """Same handoff path in beam mode — decoder rows travel too."""
    f = _feat(192, seed=17)
    chunks, _ = _chunks(f)
    clock = Clock()
    tel = ServingTelemetry()
    pool = _streaming_pool(tiny_streaming, clock, tel, decode="beam")
    mig = MigrationController(telemetry=tel, clock=clock,
                              postmortem_fn=lambda *a, **k: None)
    router = PooledSessionRouter(pool, migrator=mig)
    home = router.join("a")
    router.step({"a": chunks[0]})
    _trip(pool.replica(home).breaker)
    router.step({"a": chunks[1]})
    router.step({"a": chunks[2]})
    router.leave("a")
    router.flush()
    assert router.final("a") == _solo(tiny_streaming, f, decode="beam")
    assert mig.migrations == 1 and mig.fallbacks == 0
    assert len(router._segments["a"]) == 1


# -- fallbacks (duck-typed managers, no model) ----------------------------

class FakeMgr:
    """Duck-typed manager WITHOUT the snapshot surface: migration
    must fall back to the legacy segment drain."""

    def __init__(self, log):
        self.log = log
        self.active = {}
        self.done = {}

    def join(self, sid, raw_len=None):
        self.active[sid] = []

    def leave(self, sid, tail=None):
        self.done[sid] = " ".join(self.active.pop(sid))

    def step(self, chunks):
        assert set(chunks) == set(self.active)
        for sid, c in chunks.items():
            self.active[sid].append(str(c))
            self.log.append((sid, str(c)))
        return {sid: " ".join(v) for sid, v in self.active.items()}

    def flush(self):
        pass

    def final(self, sid):
        return self.done[sid]

    def stats(self):
        return {"active": len(self.active), "draining": 0}


class PortableFakeMgr(FakeMgr):
    """FakeMgr plus the snapshot surface — a model-free handoff."""

    fingerprint = "fake"

    def snapshot_fingerprint(self):
        return self.fingerprint

    def export_session(self, sid):
        return ("snap", sid, self.active.pop(sid))

    def import_session(self, snap, sid=None):
        _, sid0, seen = snap
        self.active[sid0] = seen


def _fake_pool(clock, tel, factory, n=2, handoff=True):
    reps = [Replica(f"r{k}", telemetry=tel, clock=clock,
                    breaker=_breaker(clock, tel, f"b{k}"),
                    session_factory=factory)
            for k in range(n)]
    return ReplicaPool(reps, clock=clock, telemetry=tel,
                       drain_window_s=0.25, handoff=handoff)


def test_unsupported_manager_falls_back_to_drain_no_lost_chunks():
    """Managers without the export surface (duck-typed doubles, the
    availability bench's _LogMgr shape) degrade to the segment-drain
    re-pin — counted as a fallback, zero chunks lost."""
    clock = Clock()
    tel = ServingTelemetry()
    log = []
    pm = []
    pool = _fake_pool(clock, tel, lambda: FakeMgr(log))
    mig = MigrationController(
        telemetry=tel, clock=clock,
        postmortem_fn=lambda kind, trigger="", **kw:
            pm.append((kind, kw)))
    router = PooledSessionRouter(pool, migrator=mig)
    home = router.join("a")
    router.step({"a": "c0"})
    _trip(pool.replica(home).breaker)
    out = router.step({"a": "c1"})
    assert out == {"a": "c0 c1"}
    assert router.home_of("a") != home
    router.leave("a")
    router.flush()
    assert router.final("a") == "c0 c1"
    assert log == [("a@0", "c0"), ("a@1", "c1")]
    assert mig.migrations == 0 and mig.fallbacks == 1
    assert int(tel.counters.get(
        'session_migration_fallbacks{reason="unsupported_manager"}',
        0)) == 1
    assert [kw["outcome"] for k, kw in pm if k == "migration"] \
        == ["fallback_drain"]


def test_fingerprint_mismatch_falls_back_to_drain():
    """Snapshot-capable managers whose fingerprints disagree (config
    skew across replicas) fall back to the drain re-pin."""
    clock = Clock()
    tel = ServingTelemetry()
    log = []
    made = []

    def factory():
        m = PortableFakeMgr(log)
        m.fingerprint = f"fake-v{len(made)}"   # every replica differs
        made.append(m)
        return m

    pool = _fake_pool(clock, tel, factory)
    mig = MigrationController(telemetry=tel, clock=clock,
                              postmortem_fn=lambda *a, **k: None)
    router = PooledSessionRouter(pool, migrator=mig)
    home = router.join("a")
    router.step({"a": "c0"})
    _trip(pool.replica(home).breaker)
    assert router.step({"a": "c1"}) == {"a": "c0 c1"}
    router.leave("a")
    router.flush()
    assert router.final("a") == "c0 c1"
    assert mig.fallbacks == 1 and mig.migrations == 0
    assert int(tel.counters.get(
        'session_migration_fallbacks{reason="fingerprint_mismatch"}',
        0)) == 1


def test_version_mismatch_falls_back_to_drain():
    """Replicas serving different model versions never exchange
    snapshots, whatever their fingerprints say."""
    clock = Clock()
    tel = ServingTelemetry()
    log = []
    pool = _fake_pool(clock, tel, lambda: PortableFakeMgr(log))
    pool.replicas[0].version = "v1"
    pool.replicas[1].version = "v2"
    mig = MigrationController(telemetry=tel, clock=clock,
                              postmortem_fn=lambda *a, **k: None)
    router = PooledSessionRouter(pool, migrator=mig)
    home = router.join("a")
    router.step({"a": "c0"})
    _trip(pool.replica(home).breaker)
    router.step({"a": "c1"})
    router.leave("a")
    router.flush()
    assert router.final("a") == "c0 c1"
    assert mig.fallbacks == 1 and mig.migrations == 0
    assert int(tel.counters.get(
        'session_migration_fallbacks{reason="version_mismatch"}',
        0)) == 1


def test_codec_mismatch_falls_back_to_drain():
    """Replicas whose snapshot WIRE codecs disagree (a mid-rollout
    fleet where one side already speaks codec v2) never exchange
    snapshots — the ISSUE-19 gate in ``_incompatibility``."""
    clock = Clock()
    tel = ServingTelemetry()
    log = []
    pool = _fake_pool(clock, tel, lambda: PortableFakeMgr(log))
    pool.replicas[1].codec_version = 99
    mig = MigrationController(telemetry=tel, clock=clock,
                              postmortem_fn=lambda *a, **k: None)
    router = PooledSessionRouter(pool, migrator=mig)
    home = router.join("a")
    router.step({"a": "c0"})
    _trip(pool.replica(home).breaker)
    router.step({"a": "c1"})
    router.leave("a")
    router.flush()
    assert router.final("a") == "c0 c1"
    assert mig.fallbacks == 1 and mig.migrations == 0
    assert int(tel.counters.get(
        'session_migration_fallbacks{reason="codec_mismatch"}',
        0)) == 1


def test_live_resize_move_migrates_without_drain():
    """A healthy live-resize pin move (add_replica) hands off by
    snapshot when a migrator is wired — reason="resize", the source
    replica never drains."""
    clock = Clock()
    tel = ServingTelemetry()
    log = []
    pool = _fake_pool(clock, tel, lambda: PortableFakeMgr(log), n=2)
    mig = MigrationController(telemetry=tel, clock=clock,
                              postmortem_fn=lambda *a, **k: None)
    router = PooledSessionRouter(pool, migrator=mig)
    # Enough sessions that the resize moves at least one pin.
    sids = [f"s{i}" for i in range(8)]
    for s in sids:
        router.join(s)
    router.step({s: "c0" for s in sids})
    pool.add_replica(
        Replica("r2", telemetry=tel, clock=clock,
                breaker=_breaker(clock, tel, "b2"),
                session_factory=lambda: PortableFakeMgr(log)))
    moved = [s for s in sids if pool.pin_of(s) == "r2"]
    assert moved, "resize moved no pins; enlarge the session set"
    router.step({s: "c1" for s in sids})
    assert mig.migrations == len(moved) and mig.fallbacks == 0
    assert all(router.home_of(s) == "r2" for s in moved)
    fams = [k for k in tel.counters
            if k.startswith("session_migrations{")]
    assert any('reason="resize"' in k for k in fams)
    for s in sids:
        router.leave(s)
    router.flush()
    for s in sids:
        assert router.final(s) == "c0 c1"


# -- crash durability (model-backed, ISSUE 19) ----------------------------

def test_crash_recovery_bit_identical(tiny_streaming, tmp_path):
    """Journal-fed manager killed mid-utterance; a cold restart
    (fresh journal handle + RecoveryController into a FRESH manager)
    continues to the exact never-crashed transcript — the journal
    captured complete recurrent state, not an approximation."""
    from deepspeech_tpu.serving import (RecoveryController,
                                        SessionJournal)

    f = _feat(64 * 4, seed=61)
    chunks, _ = _chunks(f)
    ref = _solo(tiny_streaming, f)

    j1 = SessionJournal(str(tmp_path / "wal"))
    mgr1 = _mgr(tiny_streaming, capacity=1, journal=j1)
    mgr1.join("x")
    for c in chunks[:2]:
        mgr1.step({"x": c})
    j1.close()                      # crash: appends already flushed
    del mgr1

    j2 = SessionJournal(str(tmp_path / "wal"))
    mgr2 = _mgr(tiny_streaming, capacity=1, journal=j2)
    report = RecoveryController(j2).recover(mgr2)
    assert report["recovered"] == 1 and report["torn"] == 0
    assert mgr2._sessions["x"].fed == 2 * 64
    for c in chunks[2:]:
        mgr2.step({"x": c})
    mgr2.leave("x")
    mgr2.flush()
    assert mgr2.final("x") == ref
    # Finalizing tombstones the sid: the journal quiesces.
    scan = j2.scan()
    assert not scan.live and scan.tombstoned == ["x"]
    j2.close()


def test_router_adopt_restores_into_pool(tiny_streaming, tmp_path):
    """PooledSessionRouter.adopt: a recovered snapshot re-enters the
    POOLED plane (routed like a fresh join, registered for future
    migrations) and continues bit-identically."""
    from deepspeech_tpu.serving import (RecoveryController,
                                        SessionJournal)

    f = _feat(64 * 3, seed=62)
    chunks, _ = _chunks(f)
    ref = _solo(tiny_streaming, f)

    j1 = SessionJournal(str(tmp_path / "wal"))
    mgr1 = _mgr(tiny_streaming, capacity=1, journal=j1)
    mgr1.join("x")
    mgr1.step({"x": chunks[0]})
    j1.close()
    del mgr1

    clock = Clock()
    tel = ServingTelemetry()
    pool = _streaming_pool(tiny_streaming, clock, tel)
    router = PooledSessionRouter(pool)
    j2 = SessionJournal(str(tmp_path / "wal"))
    report = RecoveryController(j2).recover(router)
    j2.close()
    assert report["recovered"] == 1
    assert router.home_of("x") is not None
    for c in chunks[1:]:
        router.step({"x": c})
    router.leave("x")
    router.flush()
    assert router.final("x") == ref


# -- scenario: a cohort re-pinned twice, handoff against drain ------------

@pytest.mark.parametrize("handoff", [True, False])
def test_scenario_mass_repin_twice(tiny_streaming, obs_lint, postmortems, handoff):
    """Two live streams pinned to one replica, whose breaker trips;
    then their new home trips too. With the handoff plane every stream
    moves by snapshot both times: one migration a stream a topology
    change, no fallback, ONE segment, and the final transcripts equal
    the never-migrated reference bit for bit (so no chunk was lost).
    The legacy drain splits each stream into trips + 1 segments and
    migrates nothing. Telemetry and ``migration`` postmortems lint
    clean."""

    trips, per_trip = 2, 2
    clock = Clock()
    tel = ServingTelemetry()
    pm = postmortems
    pool = _streaming_pool(tiny_streaming, clock, tel, handoff=handoff)
    mig = MigrationController(telemetry=tel, clock=clock,
                              postmortem_fn=pm.write)
    router = PooledSessionRouter(pool, migrator=mig if handoff else None)
    sids, k = [], 0
    while len(sids) < 2:                 # both homed on r0
        if pool.ring_owner(f"m{k}") == "r0":
            sids.append(f"m{k}")
        k += 1
    feats = {sid: _feat(64 * (1 + trips * per_trip), seed=30 + j)
             for j, sid in enumerate(sids)}
    step = 0

    def feed():
        nonlocal step
        router.step({sid: feats[sid][64 * step:64 * (step + 1)]
                     for sid in sids})
        step += 1

    for sid in sids:
        assert router.join(sid) == "r0"
    feed()
    for _ in range(trips):
        clock.t += 2.0                   # past the last trip's cooldown
        pool.maintain()
        victim = pool.replica(router.home_of(sids[0]))
        assert {router.home_of(sid) for sid in sids} == {victim.rid}
        victim.breaker.allow()           # half-open, so a failure re-opens
        _trip(victim.breaker)
        for _ in range(per_trip):
            feed()
        assert victim.rid not in {router.home_of(sid) for sid in sids}
    for sid in sids:
        router.leave(sid)
    router.flush()

    finals = {sid: router.final(sid) for sid in sids}
    # final() closes each session's trace; its flight-recorder summary
    # says how many segments the transcript was joined from.
    segments = {rec["segments"]
                for rec in router.flight_recorder.recent(len(sids))}
    if handoff:
        for sid in sids:
            assert finals[sid] == _solo(tiny_streaming, feats[sid])
        assert segments == {1}
        assert mig.stats() == {"migrations": len(sids) * trips,
                               "fallbacks": 0, "max_per_session": trips}
        pms = pm.recent("migration")
        assert len(pms) == len(sids) * trips
        assert {p["outcome"] for p in pms} == {"handoff"}
        assert any(k.startswith("session_migrations{")
                   for k in tel.counters)
        assert obs_lint(tel, pm) == []
    else:
        assert segments == {trips + 1}
        assert mig.migrations == 0 and not pm.recent("migration")
        assert all(isinstance(t, str) for t in finals.values())
        assert obs_lint(tel) == []
