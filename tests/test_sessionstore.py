"""Crash-durable sessions: wire codec + journal + recovery (ISSUE 19).

Pure-host coverage of ``serving/sessionstore.py``: the versioned
CRC-checksummed snapshot codec (round-trip fidelity, version-skew and
corruption rejection), the append-only segment-rotated
:class:`SessionJournal` (supersede/tombstone semantics, seq
monotonicity across reopen, rotation, torn-tail truncation, the
``partial_write`` fault point), and :class:`RecoveryController`
outcome accounting with its timeline/postmortem publications.

The contract tests ride synthetic :class:`StreamSnapshot` payloads and
duck-typed recovery targets — no model build. The scenarios at the end
kill real (tiny) streaming sessions mid-stream and cold-restart them
from the journal (tests/test_migration.py has the single-session
greedy case beside the handoff tests).
"""

import struct

import numpy as np
import pytest

from deepspeech_tpu.serving import (CODEC_VERSION, RecoveryController,
                                    ServingTelemetry, SessionJournal,
                                    SnapshotDecodeError,
                                    SnapshotIncompatible,
                                    StreamSnapshot, snapshot_from_bytes,
                                    snapshot_to_bytes)
from deepspeech_tpu.serving.sessionstore import scan_segment_bytes


def _snap(sid="s0", fingerprint="fp", fed=128, raw_len=None,
          beam=False, seed=7):
    rng = np.random.default_rng(seed)
    acoustic = {
        "raw_hist": rng.standard_normal((12, 13)).astype(np.float32),
        "h": tuple(rng.standard_normal((2, 32)).astype(np.float32)
                   for _ in range(2)),
        "la_buf": rng.standard_normal((3, 32)).astype(np.float32),
    }
    decoder = None
    if beam:
        from deepspeech_tpu.decode.beam import BeamState
        decoder = BeamState(
            prefixes=np.arange(8 * 4, dtype=np.int32).reshape(8, 4),
            lens=np.ones((8,), np.int32),
            hashes=np.arange(8, dtype=np.uint32),
            p_b=np.zeros((8,), np.float32),
            p_nb=np.full((8,), -1.5, np.float32),
            ctx=np.zeros((8,), np.int32),
            bonus=np.zeros((8,), np.float32))
    return StreamSnapshot(sid=sid, fingerprint=fingerprint, fed=fed,
                          raw_len=raw_len, acoustic=acoustic,
                          decoder=decoder, prev_ids=3, text="hel")


def _trees_equal(a, b):
    if isinstance(a, np.ndarray):
        return (isinstance(b, np.ndarray) and a.dtype == b.dtype
                and a.shape == b.shape and np.array_equal(a, b))
    if isinstance(a, dict):
        return (isinstance(b, dict) and set(a) == set(b)
                and all(_trees_equal(a[k], b[k]) for k in a))
    if isinstance(a, tuple):
        return (type(a) is type(b) and len(a) == len(b)
                and all(_trees_equal(x, y) for x, y in zip(a, b)))
    return a == b


# -- the wire codec -------------------------------------------------------

def test_codec_roundtrip_greedy():
    snap = _snap(raw_len=640)
    out = snapshot_from_bytes(snapshot_to_bytes(snap))
    assert (out.sid, out.fingerprint, out.fed, out.raw_len,
            out.prev_ids, out.text) == ("s0", "fp", 128, 640, 3, "hel")
    assert out.decoder is None
    assert _trees_equal(snap.acoustic, out.acoustic)


def test_codec_roundtrip_beam_namedtuple():
    """The BeamState NamedTuple survives the wire: same type, fields,
    dtypes and values (the ``ntup`` structure marker + importlib)."""
    from deepspeech_tpu.decode.beam import BeamState
    snap = _snap(beam=True)
    out = snapshot_from_bytes(snapshot_to_bytes(snap))
    assert type(out.decoder) is BeamState
    assert _trees_equal(tuple(snap.decoder), tuple(out.decoder))


def test_codec_version_skew_is_incompatible_not_decode_error():
    """A frame from a FUTURE codec must be refused as incompatible
    (the fallback-to-drain signal) before any CRC math — future
    codecs may reframe everything past the version field."""
    buf = bytearray(snapshot_to_bytes(_snap()))
    struct.pack_into("<H", buf, 4, CODEC_VERSION + 1)
    with pytest.raises(SnapshotIncompatible):
        snapshot_from_bytes(bytes(buf))


def test_codec_corruption_is_decode_error():
    raw = snapshot_to_bytes(_snap())
    flipped = bytearray(raw)
    flipped[len(raw) // 2] ^= 0xFF
    with pytest.raises(SnapshotDecodeError):
        snapshot_from_bytes(bytes(flipped))
    with pytest.raises(SnapshotDecodeError):
        snapshot_from_bytes(raw[:len(raw) - 3])     # truncated
    with pytest.raises(SnapshotDecodeError):
        snapshot_from_bytes(b"XXXX" + raw[4:])      # bad magic
    assert issubclass(SnapshotDecodeError, ValueError)


def test_codec_rejects_object_dtype():
    snap = _snap()
    snap.acoustic["bad"] = np.array([object()], dtype=object)
    with pytest.raises(ValueError):
        snapshot_to_bytes(snap)


# -- the journal ----------------------------------------------------------

def test_journal_supersede_and_tombstone(tmp_path):
    j = SessionJournal(str(tmp_path / "wal"))
    s1 = j.append("a", snapshot_to_bytes(_snap(sid="a")))
    s2 = j.append("b", snapshot_to_bytes(_snap(sid="b")))
    s3 = j.append("a", snapshot_to_bytes(_snap(sid="a", fed=256)))
    s4 = j.forget("b")
    assert [s1, s2, s3, s4] == [1, 2, 3, 4]
    scan = j.scan()
    assert sorted(scan.live) == ["a"]
    assert scan.live["a"].seq == s3
    assert snapshot_from_bytes(scan.live["a"].data).fed == 256
    # b's snapshot AND a's superseded one both count as stale.
    assert scan.stale == 2
    assert scan.tombstoned == ["b"]
    assert not scan.torn
    j.close()


def test_journal_seq_resumes_across_reopen(tmp_path):
    path = str(tmp_path / "wal")
    j = SessionJournal(path)
    for k in range(3):
        j.append("a", snapshot_to_bytes(_snap()))
    j.close()
    j2 = SessionJournal(path)
    assert j2.append("a", snapshot_to_bytes(_snap())) == 4
    # The reopened journal writes a FRESH segment, never the
    # predecessor's tail.
    assert len(j2.segments()) == 2
    assert len(j2.scan().entries) == 4
    j2.close()


def test_journal_rotation_and_compaction(tmp_path):
    j = SessionJournal(str(tmp_path / "wal"), segment_bytes=256)
    blob = snapshot_to_bytes(_snap())
    for k in range(6):
        j.append(f"s{k % 2}", blob)
    assert len(j.segments()) > 1
    assert j.stats()["rotations"] >= 1
    scan = j.scan()
    assert len(scan.entries) == 6 and len(scan.live) == 2
    reclaimed = j.compact()
    assert reclaimed > 0
    scan2 = j.scan()
    assert sorted(scan2.live) == ["s0", "s1"] and scan2.stale == 0
    # Compaction preserves the original seqs (recovery ordering).
    assert scan2.live["s0"].seq == scan.live["s0"].seq
    j.close()


def test_journal_torn_tail_truncates_cleanly(tmp_path):
    path = str(tmp_path / "wal")
    j = SessionJournal(path)
    j.append("a", snapshot_to_bytes(_snap(sid="a")))
    j.append("b", snapshot_to_bytes(_snap(sid="b")))
    j.close()
    seg = j.segments()[-1]
    data = open(seg, "rb").read()
    open(seg, "wb").write(data[:-7])      # tear mid-record
    j2 = SessionJournal(path)
    scan = j2.scan()
    assert sorted(scan.live) == ["a"]     # b's record was the tail
    assert len(scan.torn) == 1
    # The tear costs ONE record, never the journal: appends continue
    # in a fresh segment and the next scan sees old + new.
    j2.append("c", snapshot_to_bytes(_snap(sid="c")))
    assert sorted(j2.scan().live) == ["a", "c"]
    j2.close()


def _fuzz(data, name, stride):
    starts, pos = [], 6
    while pos + 8 <= len(data):
        starts.append(pos)
        pos += 8 + struct.unpack_from("<I", data, pos)[0]
    ends = [starts[i + 1] if i + 1 < len(starts) else len(data)
            for i in range(len(starts))]
    for t in range(0, len(data) + 1, stride):
        entries, torn_at = scan_segment_bytes(data[:t], name)
        assert len(entries) == sum(1 for e in ends if e <= t), t
        boundary = t == 0 or t == 6 or t in ends
        assert (torn_at is None) == boundary, t


def _fuzz_segment(tmp_path, stride):
    j = SessionJournal(str(tmp_path / "wal"))
    for k in range(4):
        j.append(f"s{k}", snapshot_to_bytes(_snap(sid=f"s{k}")))
    j.close()
    seg = j.segments()[-1]
    _fuzz(open(seg, "rb").read(), "seg", stride)


def test_torn_tail_fuzz_strided(tmp_path):
    """Truncation at (strided) byte offsets never raises and yields
    exactly the records the prefix still contains."""
    _fuzz_segment(tmp_path, stride=17)


@pytest.mark.slow
def test_torn_tail_fuzz_every_offset(tmp_path):
    """The full-coverage version: EVERY byte offset."""
    _fuzz_segment(tmp_path, stride=1)


def test_partial_write_fault_tears_then_rotates(tmp_path):
    """The ``journal.append``/``partial_write`` fault point: the torn
    frame is invisible to scans, the segment rotates, and later
    appends land recoverable — the mid-write crash drill."""
    from deepspeech_tpu.resilience import FaultPlan, FaultSpec, faults
    tel = ServingTelemetry()
    j = SessionJournal(str(tmp_path / "wal"), telemetry=tel)
    j.append("a", snapshot_to_bytes(_snap(sid="a")))
    faults.install(FaultPlan([FaultSpec("journal.append",
                                        "partial_write", prob=1.0,
                                        count=1)], registry=tel))
    try:
        j.append("b", snapshot_to_bytes(_snap(sid="b")))
    finally:
        faults.clear()
    j.append("c", snapshot_to_bytes(_snap(sid="c")))
    assert j.torn_writes == 1
    scan = j.scan()
    assert sorted(scan.live) == ["a", "c"]
    assert len(scan.torn) == 1
    assert int(tel.counters.get("journal_torn_writes", 0)) == 1
    j.close()


# -- recovery -------------------------------------------------------------

class DuckTarget:
    """Recovery target double: records imports and drain-resumes."""

    def __init__(self):
        self.imported = {}
        self.left = []

    def import_session(self, snap, sid=None):
        self.imported[sid or snap.sid] = snap

    def leave(self, sid, tail=None):
        self.left.append(sid)


def test_recovery_outcome_accounting(tmp_path):
    """One boot replay over a journal holding an ok record, a
    superseded record, an unreadable record and a future-codec
    record: each lands in its own outcome, recovery never aborts,
    and the timeline/postmortem/counter publications agree."""
    from deepspeech_tpu.obs import timeline as tl_mod
    from deepspeech_tpu.obs.timeline import EventLog

    j = SessionJournal(str(tmp_path / "wal"))
    j.append("ok", snapshot_to_bytes(_snap(sid="ok", fed=64)))
    j.append("ok", snapshot_to_bytes(_snap(sid="ok", fed=128)))
    j.append("garbled", b"not a snapshot frame at all")
    skew = bytearray(snapshot_to_bytes(_snap(sid="skew")))
    struct.pack_into("<H", skew, 4, CODEC_VERSION + 7)
    j.append("skew", bytes(skew))

    tel = ServingTelemetry()
    pm = []
    log = tl_mod.install(EventLog(registry=tel))
    try:
        target = DuckTarget()
        rc = RecoveryController(
            j, telemetry=tel,
            postmortem_fn=lambda kind, trigger="", **kw:
                pm.append((kind, trigger, kw)))
        report = rc.recover(target)
    finally:
        tl_mod.clear()
        j.close()

    assert report["recovered"] == 1 and report["sids"] == ["ok"]
    assert report["torn"] == 1 and report["incompatible"] == 1
    assert report["stale"] == 1
    assert target.imported["ok"].fed == 128
    assert target.left == []                   # raw_len unknown
    for outcome, n in (("ok", 1), ("torn", 1), ("incompatible", 1),
                       ("stale", 1)):
        key = f'sessions_recovered{{outcome="{outcome}"}}'
        assert int(tel.counters.get(key, 0)) == n, key

    events = log.recent()
    kinds = [e["kind"] for e in events]
    assert kinds[0] == "recovery" and kinds[-1] == "recovery_done"
    begin = events[0]
    assert begin["detail"]["phase"] == "begin"
    per_sid = [e for e in events if e["kind"] == "recovery"
               and e["detail"].get("phase") == "session"]
    assert {e["detail"]["sid"]: e["detail"]["outcome"]
            for e in per_sid} == {"ok": "ok", "garbled": "torn",
                                  "skew": "incompatible"}
    assert all(e["cause_seq"] == begin["seq"] for e in per_sid)
    assert events[-1]["cause_seq"] == begin["seq"]
    assert [p[0] for p in pm] == ["crash_recovery"]
    assert pm[0][1] == "boot" and pm[0][2]["recovered"] == 1


def test_recovery_resumes_drain_for_ended_sessions(tmp_path):
    """A session that ended (raw_len known, fully fed) before the
    crash restores AND resumes its drain via leave()."""
    j = SessionJournal(str(tmp_path / "wal"))
    j.append("done", snapshot_to_bytes(
        _snap(sid="done", fed=256, raw_len=256)))
    j.append("mid", snapshot_to_bytes(
        _snap(sid="mid", fed=128, raw_len=256)))
    target = DuckTarget()
    report = RecoveryController(j).recover(target)
    j.close()
    assert report["recovered"] == 2
    assert target.left == ["done"]


def test_scan_segment_bytes_degenerate():
    assert scan_segment_bytes(b"", "s") == ([], None)
    entries, torn = scan_segment_bytes(b"XXXXXXXXXX", "s")
    assert entries == [] and torn == 0


# -- scenarios: real sessions, killed mid-stream --------------------------

_CHUNK, _STEPS, _CRASH_AT = 64, 4, 2


def _cohort(tiny_streaming, tel, sids, seed):
    cfg, tok, params, stats = tiny_streaming
    rng = np.random.default_rng(seed)
    feats = {sid: rng.standard_normal((_STEPS * _CHUNK, 13)).astype(
        np.float32) for sid in sids}

    def mgr(journal=None, decode="greedy", chunk_frames=_CHUNK):
        from deepspeech_tpu.serving import StreamingSessionManager
        return StreamingSessionManager(
            cfg, params, stats, tok, chunk_frames=chunk_frames,
            capacity=len(sids), decode=decode, telemetry=tel,
            journal=journal, journal_every=1)

    def feed(m, k0, k1, join=False, finish=False):
        if join:
            for sid in sids:
                m.join(sid)
        for k in range(k0, k1):
            m.step({sid: feats[sid][k * _CHUNK:(k + 1) * _CHUNK]
                    for sid in sids})
        if finish:
            for sid in sids:
                m.leave(sid)
            m.flush()
            return {sid: m.final(sid) for sid in sids}

    return feats, mgr, feed


@pytest.mark.parametrize("decode", ["greedy", "beam"])
def test_scenario_crash_midstream_cold_restart_is_bit_identical(
        tiny_streaming, tmp_path, obs_lint, postmortems, decode):
    """Two live streams checkpoint every chunk into the journal and die
    at the halfway chunk. A fresh manager recovered from the journal
    resumes each at exactly the crash position and finishes with the
    uninterrupted reference's transcripts, bit for bit; finalizing
    tombstones every sid; the ``crash_recovery`` postmortem, the
    timeline's ``recovery`` events and the journal counters lint
    clean."""
    import json

    from deepspeech_tpu.obs import timeline
    from deepspeech_tpu.obs.timeline import EventLog

    tel = ServingTelemetry()
    sids = ["c0", "c1"]
    _, mgr, feed = _cohort(tiny_streaming, tel, sids, seed=31)
    want = feed(mgr(decode=decode), 0, _STEPS, join=True, finish=True)

    j1 = SessionJournal(str(tmp_path / "wal"), telemetry=tel)
    feed(mgr(j1, decode), 0, _CRASH_AT, join=True)
    assert j1.appends == len(sids) * _CRASH_AT
    j1.close()                          # the process dies here

    pm = postmortems
    log = timeline.install(EventLog(registry=tel))
    lines = []
    log.add_listener(lambda ev: lines.append(
        json.dumps(EventLog.to_record(ev))))
    try:
        j2 = SessionJournal(str(tmp_path / "wal"), telemetry=tel)
        fresh = mgr(j2, decode)
        report = RecoveryController(j2, telemetry=tel,
                                    postmortem_fn=pm.write).recover(fresh)
    finally:
        timeline.clear()
    assert (report["recovered"], report["torn"],
            report["incompatible"]) == (len(sids), 0, 0)
    assert {fresh._sessions[sid].fed for sid in sids} \
        == {_CRASH_AT * _CHUNK}
    assert feed(fresh, _CRASH_AT, _STEPS, finish=True) == want
    scan = j2.scan()
    assert not scan.live and sorted(scan.tombstoned) == sids
    j2.close()
    assert tel.counter("sessions_recovered",
                       labels={"outcome": "ok"}) == len(sids)
    assert int(tel.counters["journal_appends"]) > 0
    pms = pm.recent("crash_recovery")
    assert len(pms) == 1 and pms[0]["trigger"] == "boot"
    assert obs_lint(tel, lines, pm) == []


def test_scenario_torn_tail_and_skew_recover_what_they_can(
        tiny_streaming, tmp_path):
    """The same crash, with the journal damaged. Torn in the middle of
    its last record: the torn session resumes one checkpoint behind,
    the other at the crash position, and after a per-session refeed
    both reach the reference. A version-patched record, and a target
    with another chunk geometry, each recover nothing and are counted
    ``incompatible``."""
    import os

    tel = ServingTelemetry()
    sids = ["c0", "c1"]
    feats, mgr, feed = _cohort(tiny_streaming, tel, sids, seed=31)
    want = feed(mgr(), 0, _STEPS, join=True, finish=True)
    j1 = SessionJournal(str(tmp_path / "wal"), telemetry=tel)
    m1 = mgr(j1)
    feed(m1, 0, _CRASH_AT, join=True)
    snap = m1.snapshot_session(sids[0])
    j1.close()

    (seg,) = j1.segments()
    data = open(seg, "rb").read()
    starts, pos = [], 6
    while pos + 8 <= len(data):
        starts.append(pos)
        pos += 8 + struct.unpack_from("<I", data, pos)[0]
    os.makedirs(tmp_path / "torn")
    with open(tmp_path / "torn" / os.path.basename(seg), "wb") as fh:
        fh.write(data[:starts[-1] + (len(data) - starts[-1]) // 2])
    jt = SessionJournal(str(tmp_path / "torn"), telemetry=tel)
    m2 = mgr()
    report = RecoveryController(jt, telemetry=tel).recover(m2)
    jt.close()
    assert (report["recovered"], report["torn"]) == (len(sids), 1)
    at = {sid: m2._sessions[sid].fed // _CHUNK for sid in sids}
    assert sorted(at.values()) == [_CRASH_AT - 1, _CRASH_AT]
    while at:
        for sid in [s for s in at if at[s] >= _STEPS]:
            m2.leave(sid)
            del at[sid]
        if at:
            m2.step({sid: feats[sid][k * _CHUNK:(k + 1) * _CHUNK]
                     for sid, k in at.items()})
            at = {sid: k + 1 for sid, k in at.items()}
    m2.flush()
    assert {sid: m2.final(sid) for sid in sids} == want

    raw = bytearray(snapshot_to_bytes(snap))
    struct.pack_into("<H", raw, 4, 99)      # version field, before CRC
    for name, blob, target in (
            ("version", bytes(raw), mgr()),
            ("geometry", snapshot_to_bytes(snap), mgr(chunk_frames=32))):
        js = SessionJournal(str(tmp_path / name), telemetry=tel)
        js.append("skew", blob)
        js.close()
        got = RecoveryController(
            SessionJournal(str(tmp_path / name), telemetry=tel),
            telemetry=tel).recover(target)
        assert (got["recovered"], got["incompatible"]) == (0, 1), name
    assert tel.counter("sessions_recovered",
                       labels={"outcome": "incompatible"}) == 2
