"""Cross-process session handoff (serving/transport.py): wire codec,
handshake gate, idempotent transfers, and the migrate_remote
degradation ladder.

Covers the ISSUE-20 contracts: a frame survives the wire or is
detected (every truncation and bit flip raises FrameError, and a
receiver fed that garbage answers MSG_ERR instead of crashing); the
handshake rejects version / codec / fingerprint skew BEFORE any
snapshot bytes ship, with reasons in the existing fallback taxonomy;
transfers keyed by (sid, transfer_id) never double-import on a
retried send; the real TCP listener serves the same protocol; and
``migrate_remote`` lands on exactly one rung — remote release, local
journal-recovery re-pin, or stay — with the session preserved on all
of them. Router adoption conflicts (sid already live, adopt racing a
pin) keep ONE owner and zero lost chunks.

The contract tests are model-free: duck-typed managers that speak
the real snapshot codec (real ``StreamSnapshot`` payloads through
``snapshot_to_bytes``), real routers/pools/breakers, injected clocks.
The scenarios at the end put it together: real (tiny) streaming
sessions cross a real socket and finish bit-identically on the other
host, and scripted ``transport.*`` flaps resolve by retry or down the
ladder (tests/test_migration.py has the in-process plane).
"""

import numpy as np
import pytest

from deepspeech_tpu.resilience import CircuitBreaker
from deepspeech_tpu.serving import (CODEC_VERSION, HandoffListener,
                                    HandoffReceiver, LoopbackTransport,
                                    PooledSessionRouter,
                                    RemoteMigrationController, Replica,
                                    ReplicaPool, ServingTelemetry,
                                    SocketTransport, StreamSnapshot,
                                    TransportError, snapshot_to_bytes)
from deepspeech_tpu.serving.transport import (MSG_ACK, MSG_ERR,
                                              MSG_HELLO, MSG_HELLO_OK,
                                              MSG_HELLO_REJECT, MSG_XFER,
                                              FrameError, decode_frame,
                                              encode_frame)
from deepspeech_tpu.resilience.retry import Retry


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _snap(sid, text="", fingerprint="fake"):
    """A REAL StreamSnapshot (round-trips the real wire codec)."""
    return StreamSnapshot(
        sid=sid, fingerprint=fingerprint, fed=64, raw_len=None,
        acoustic={"h": np.zeros((2,), np.float32)}, prev_ids=0,
        text=text)


class WireMgr:
    """Duck-typed manager speaking the real snapshot surface: session
    text rides the codec, so a transfer's continuation proves zero
    lost chunks without a model."""

    fingerprint = "fake"

    def __init__(self, log=None):
        self.active = {}
        self.done = {}
        self.log = log if log is not None else []

    def join(self, sid, raw_len=None):
        self.active[sid] = []

    def leave(self, sid, tail=None):
        self.done[sid] = " ".join(self.active.pop(sid))

    def step(self, chunks):
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

    def snapshot_fingerprint(self):
        return self.fingerprint

    def snapshot_session(self, sid):
        return _snap(sid, " ".join(self.active[sid]),
                     fingerprint=self.fingerprint)

    def export_session(self, sid, forget=False):
        return ("exported", sid, self.active.pop(sid))

    def import_session(self, snap, sid=None):
        if isinstance(snap, tuple):          # undo path of the ladder
            _, sid0, seen = snap
            self.active[sid if sid is not None else sid0] = list(seen)
        else:                                 # decoded StreamSnapshot
            key = sid if sid is not None else snap.sid
            self.active[key] = snap.text.split() if snap.text else []


def _pool(clock, tel, n=2, factory=None):
    factory = factory if factory is not None else WireMgr
    reps = [Replica(f"r{k}", telemetry=tel, clock=clock,
                    breaker=CircuitBreaker(name=f"b{k}",
                                           failure_threshold=2,
                                           cooldown_s=1.0, clock=clock,
                                           registry=tel),
                    session_factory=factory)
            for k in range(n)]
    return ReplicaPool(reps, clock=clock, telemetry=tel,
                       drain_window_s=0.25, handoff=True)


def _host(n=2):
    clock = Clock()
    tel = ServingTelemetry()
    pool = _pool(clock, tel, n=n)
    return clock, tel, pool, PooledSessionRouter(pool)


def _ctrl(tel, clock, **kw):
    kw.setdefault("retry", Retry(attempts=3, base_s=0.01,
                                 multiplier=2.0, max_s=0.05, jitter=0.0,
                                 budget_s=1.0, name="handoff",
                                 sleep=lambda s: None))
    kw.setdefault("postmortem_fn", lambda *a, **k: None)
    return RemoteMigrationController(telemetry=tel, clock=clock, **kw)


# -- frame codec ----------------------------------------------------------

def test_frame_roundtrip_all_message_types():
    payload = b"\x00\x01\xffdata" * 9
    for mtype in (MSG_HELLO, MSG_HELLO_OK, MSG_HELLO_REJECT, MSG_XFER,
                  MSG_ACK, MSG_ERR):
        hdr = {"sid": "sé0", "n": mtype}      # non-ASCII header
        m, h, p = decode_frame(encode_frame(mtype, hdr, payload))
        assert (m, h, p) == (mtype, hdr, payload)


def test_frame_fuzz_every_truncation_and_bit_flip_detected():
    """No prefix and no single-byte corruption of a frame decodes:
    the preamble length check, CRC, and header bounds catch all of
    it — the property the receiver's never-crash contract rests on."""
    frame = encode_frame(MSG_XFER, {"sid": "x", "transfer_id": "t1"},
                         b"\x07" * 131)
    for cut in range(len(frame)):
        with pytest.raises(FrameError):
            decode_frame(frame[:cut])
    for i in range(len(frame)):
        damaged = bytearray(frame)
        damaged[i] ^= 0x5A
        with pytest.raises(FrameError):
            decode_frame(bytes(damaged))


# -- the receiving peer ---------------------------------------------------

class _Target:
    """Bare manager-shaped adoption target."""

    def __init__(self, fingerprint="fake"):
        self._fp = fingerprint
        self.imported = []

    def snapshot_fingerprint(self):
        return self._fp

    def import_session(self, snap, sid=None):
        self.imported.append((sid, snap))


def _hello(version=None, codec=CODEC_VERSION, fingerprint="fake"):
    return encode_frame(MSG_HELLO, {"version": version,
                                    "codec_version": codec,
                                    "fingerprint": fingerprint})


def test_handshake_accepts_match_and_rejects_skew_with_taxonomy():
    tel = ServingTelemetry()
    rx = HandoffReceiver(_Target(), name="p", telemetry=tel)
    m, h, _ = decode_frame(rx.handle_bytes(_hello()))
    assert m == MSG_HELLO_OK and h["codec_version"] == CODEC_VERSION
    for frame, bucket in (
            (_hello(version="v2"), "version_mismatch"),
            (_hello(codec=99), "codec_mismatch"),
            (_hello(fingerprint="other"), "fingerprint_mismatch")):
        m, h, _ = decode_frame(rx.handle_bytes(frame))
        assert m == MSG_HELLO_REJECT
        # The reason leads with the fallback-taxonomy bucket, so the
        # sender's str(e).split(":")[0] labels the counter directly.
        assert h["reason"].split(":")[0] == bucket
    assert rx.rejects == 3
    assert tel.counter("transport_handshake_rejects",
                       labels={"peer": "p"}) == 3


def test_xfer_idempotent_by_transfer_id_lost_ack_never_reimports():
    target = _Target()
    rx = HandoffReceiver(target, name="p")
    frame = encode_frame(MSG_XFER, {"sid": "a", "transfer_id": "t1"},
                         snapshot_to_bytes(_snap("a", "c0 c1")))
    m, h, _ = decode_frame(rx.handle_bytes(frame))
    assert m == MSG_ACK and h["status"] == "imported"
    assert rx.imports == 1 and rx.imported_sids == ["a"]
    # The retried send (its ACK was lost) replays the cached verdict.
    m, h, _ = decode_frame(rx.handle_bytes(frame))
    assert m == MSG_ACK and h["status"] == "imported"
    assert h["duplicate"] is True
    assert rx.imports == 1 and len(target.imported) == 1
    # A NEW transfer id is a new transfer.
    m, h, _ = decode_frame(rx.handle_bytes(encode_frame(
        MSG_XFER, {"sid": "a", "transfer_id": "t2"},
        snapshot_to_bytes(_snap("a", "c0 c1 c2")))))
    assert h["status"] == "imported" and rx.imports == 2


def test_damaged_snapshot_err_not_cached_clean_retry_lands():
    rx = HandoffReceiver(_Target(), name="p")
    good = snapshot_to_bytes(_snap("a", "c0"))
    torn = good[:len(good) // 2]
    m, h, _ = decode_frame(rx.handle_bytes(encode_frame(
        MSG_XFER, {"sid": "a", "transfer_id": "t1"}, torn)))
    assert m == MSG_ERR and h["error"] == "snapshot_damaged"
    # NOT cached as a verdict: the retry carries a clean copy and
    # imports under the SAME transfer id.
    m, h, _ = decode_frame(rx.handle_bytes(encode_frame(
        MSG_XFER, {"sid": "a", "transfer_id": "t1"}, good)))
    assert m == MSG_ACK and h["status"] == "imported"


def test_receiver_never_raises_on_garbage():
    rx = HandoffReceiver(_Target(), name="p")
    frame = encode_frame(MSG_XFER, {"sid": "z", "transfer_id": "t"},
                         b"\x00" * 64)
    cases = [b"", b"\xffnot-a-frame" * 5, frame[:11], frame[:-3]]
    cases += [bytes(b ^ 0x5A if i == 9 else b
                    for i, b in enumerate(frame))]
    for data in cases:
        reply = rx.handle_bytes(data)
        m, h, _ = decode_frame(reply)
        assert m == MSG_ERR, data[:16]
    assert rx.bad_frames == len(cases)
    assert rx.imports == 0


def test_socket_listener_serves_protocol_and_shrugs_off_garbage():
    """The stdlib-TCP leg end to end: handshake + transfer through a
    real listener, raw garbage on the socket answered (not fatal),
    and the listener keeps serving afterwards."""
    import socket as socket_mod

    target = _Target()
    rx = HandoffReceiver(target, name="p")
    lsn = HandoffListener(rx, port=0)
    try:
        tx = SocketTransport(lsn.host, lsn.port, timeout_s=5.0)
        m, _, _ = decode_frame(tx.roundtrip(_hello()))
        assert m == MSG_HELLO_OK
        # Raw garbage straight onto the wire: the reply is a frame.
        with socket_mod.create_connection((lsn.host, lsn.port),
                                          timeout=5.0) as s:
            s.sendall(b"\xffgarbage-not-a-frame" * 7)
            s.shutdown(socket_mod.SHUT_WR)
            reply = b""
            while True:
                piece = s.recv(65536)
                if not piece:
                    break
                reply += piece
        m, h, _ = decode_frame(reply)
        assert m == MSG_ERR and h["error"] == "bad_frame"
        # Still serving: the transfer lands after the garbage.
        m, h, _ = decode_frame(tx.roundtrip(encode_frame(
            MSG_XFER, {"sid": "a", "transfer_id": "t1"},
            snapshot_to_bytes(_snap("a", "c0")))))
        assert m == MSG_ACK and h["status"] == "imported"
        assert target.imported
    finally:
        lsn.close()
    with pytest.raises(TransportError):
        SocketTransport(lsn.host, lsn.port, timeout_s=0.5).roundtrip(
            _hello())


# -- migrate_remote: the degradation ladder -------------------------------

def test_migrate_remote_success_releases_source_peer_owns_session():
    clock_a, tel, pool_a, router_a = _host()
    _, tel_b, _, router_b = _host()
    rx = HandoffReceiver(router_b, name="host-b", telemetry=tel_b)
    ctrl = _ctrl(tel, clock_a)
    router_a.join("a")
    router_a.step({"a": "c0"})
    router_a.step({"a": "c1"})
    out = ctrl.migrate_remote(router_a, "a",
                              LoopbackTransport(rx, name="host-b"))
    assert out == "remote"
    # Source side: ownership gone — the sid is fully released.
    with pytest.raises(KeyError):
        router_a.home_of("a")
    assert sum(pool_a.replica(r.rid).peek_session_manager()
               .stats()["active"] if r.peek_session_manager() else 0
               for r in pool_a) == 0
    assert ctrl.remote_handoffs == 1 and ctrl.remote_fallbacks == 0
    assert tel.counter(
        "session_migrations",
        labels={"replica": "peer:host-b", "reason": "xhost"}) == 1
    # Peer side: exactly one owner, zero lost chunks — the stream
    # continues from the shipped state.
    assert rx.imports == 1 and rx.imported_sids == ["a"]
    router_b.step({"a": "c2"})
    router_b.leave("a")
    router_b.flush()
    assert router_b.final("a") == "c0 c1 c2"


def test_migrate_remote_handshake_reject_falls_back_local():
    """A fingerprint-skewed peer rejects during HELLO — before any
    snapshot bytes ship — and the ladder lands on the local
    journal-recovery re-pin: same transcript, new home replica."""
    clock, tel, pool, router = _host()
    rx = HandoffReceiver(None, name="skew", fingerprint="other-config")
    ctrl = _ctrl(tel, clock)
    home = router.join("a")
    router.step({"a": "c0"})
    out = ctrl.migrate_remote(router, "a",
                              LoopbackTransport(rx, name="skew"))
    assert out == "local"
    assert rx.rejects == 1 and rx.imports == 0
    assert router.home_of("a") != home
    assert tel.counter("session_migration_fallbacks",
                       labels={"reason": "fingerprint_mismatch"}) == 1
    assert tel.counter(
        "session_migrations",
        labels={"replica": router.home_of("a"),
                "reason": "journal_repin"}) == 1
    # Alive-but-incompatible is breaker SUCCESS: the peer answered.
    assert ctrl.breaker_for("skew").state == "closed"
    router.step({"a": "c1"})
    router.leave("a")
    router.flush()
    assert router.final("a") == "c0 c1"


def test_migrate_remote_unreachable_single_replica_stays_then_opens():
    """No peer and nowhere local to go: every attempt exhausts the
    retry and returns "stay" with the session streaming at home;
    repeated failures open the per-peer breaker, after which the
    ladder short-circuits without touching the wire."""

    class DeadTransport:
        name = "dead"

        def __init__(self):
            self.calls = 0

        def roundtrip(self, data):
            self.calls += 1
            raise TransportError("connection refused")

    clock, tel, pool, router = _host(n=1)
    ctrl = _ctrl(tel, clock)
    dead = DeadTransport()
    router.join("a")
    router.step({"a": "c0"})
    assert ctrl.migrate_remote(router, "a", dead) == "stay"
    assert dead.calls == 3                    # every retry hit the wire
    assert tel.counter("session_migration_fallbacks",
                       labels={"reason": "peer_unavailable"}) == 1
    assert tel.counter("session_migration_fallbacks",
                       labels={"reason": "no_local_destination"}) == 1
    assert ctrl.breaker_for("dead").state == "open"
    assert ctrl.migrate_remote(router, "a", dead) == "stay"
    assert dead.calls == 3                    # breaker ate the attempt
    assert tel.counter("session_migration_fallbacks",
                       labels={"reason": "peer_circuit_open"}) == 1
    # The session never left: it keeps streaming at home to final.
    router.step({"a": "c1"})
    router.leave("a")
    router.flush()
    assert router.final("a") == "c0 c1"


# -- router adoption conflicts (satellite: one owner, always) -------------

def test_adopt_rejects_sid_already_live_original_unharmed():
    clock, tel, pool, router = _host()
    router.join("a")
    router.step({"a": "c0"})
    with pytest.raises(ValueError, match="already attached"):
        router.adopt("a", _snap("a", "imposter"))
    # The refusal left no partial registration and the ORIGINAL
    # stream is untouched — chunks keep flowing to the one owner.
    assert router.local_of("a") == "a@0"
    router.step({"a": "c1"})
    router.leave("a")
    router.flush()
    assert router.final("a") == "c0 c1"
    # The receiver surfaces the same conflict as a rejected verdict,
    # not a crash — the sender falls back, the live session wins.
    router2 = _host()[3]
    router2.join("b")
    rx = HandoffReceiver(router2, name="p")
    m, h, _ = decode_frame(rx.handle_bytes(encode_frame(
        MSG_XFER, {"sid": "b", "transfer_id": "t1"},
        snapshot_to_bytes(_snap("b", "imposter")))))
    assert m == MSG_ACK and h["status"] == "rejected"
    assert h["reason"].startswith("import_failed")
    assert rx.imports == 0


def test_adopt_lands_on_prior_pin_one_owner_zero_lost_chunks():
    """An operator pin raced ahead of the adoption: the adopt routes
    to the pinned replica, exactly one manager owns the session, and
    the continuation includes every pre-handoff chunk."""
    clock, tel, pool, router = _host()
    pool.pin_to("a", "r1")
    home = router.adopt("a", _snap("a", "c0 c1"))
    assert home == "r1" and router.home_of("a") == "r1"
    owners = [r.rid for r in pool
              if r.peek_session_manager() is not None
              and "a@0" in r.peek_session_manager().active]
    assert owners == ["r1"]
    router.step({"a": "c2"})
    router.leave("a")
    router.flush()
    assert router.final("a") == "c0 c1 c2"


# -- scenarios ------------------------------------------------------------

@pytest.mark.parametrize("decode", ["greedy", "beam"])
def test_scenario_live_sessions_cross_a_socket_bit_identical(
        tiny_streaming, obs_lint, postmortems, decode):
    """Two live streams on host A are shipped mid-utterance through a
    real TCP listener to host B (disjoint pools and managers) after the
    listener has been thrown garbage: every transfer ends ``remote``,
    A no longer owns the sids, B imported each once, and the streams
    finish on B with the transcripts of the never-migrated reference,
    bit for bit. Telemetry and ``migration`` postmortems lint clean."""
    import socket

    from deepspeech_tpu.serving import StreamingSessionManager

    cfg, tok, params, stats = tiny_streaming
    tel = ServingTelemetry()
    pm = postmortems

    def mgr():
        return StreamingSessionManager(cfg, params, stats, tok,
                                       chunk_frames=64, capacity=2,
                                       decode=decode, telemetry=tel)

    def host(prefix):
        pool = ReplicaPool([Replica(f"{prefix}0", telemetry=tel,
                                    session_factory=mgr)], telemetry=tel)
        return PooledSessionRouter(pool)

    sids = ["x0", "x1"]
    rng = np.random.default_rng(41)
    feats = {sid: rng.standard_normal((4 * 64, 13)).astype(np.float32)
             for sid in sids}

    def feed(router, k0, k1):
        for k in range(k0, k1):
            router.step({sid: feats[sid][64 * k:64 * (k + 1)]
                         for sid in sids})

    def finish(router):
        for sid in sids:
            router.leave(sid)
        router.flush()
        return {sid: router.final(sid) for sid in sids}

    ref_router = host("ref")
    for sid in sids:
        ref_router.join(sid)
    feed(ref_router, 0, 4)
    want = finish(ref_router)

    router_a, router_b = host("a"), host("b")
    rx = HandoffReceiver(router_b, name="host-b", telemetry=tel)
    lsn = HandoffListener(rx, port=0)
    try:
        with socket.create_connection((lsn.host, lsn.port),
                                      timeout=5.0) as sk:
            sk.sendall(b"\xffgarbage-not-a-frame" * 7)
            sk.shutdown(socket.SHUT_WR)
            while sk.recv(65536):
                pass
        ctrl = RemoteMigrationController(
            telemetry=tel, postmortem_fn=pm.write,
            retry=Retry(attempts=3, base_s=0.01, jitter=0.0,
                        budget_s=1.0, name="handoff",
                        sleep=lambda s: None))
        for sid in sids:
            router_a.join(sid)
        feed(router_a, 0, 2)
        tx = SocketTransport(lsn.host, lsn.port, timeout_s=10.0)
        assert [ctrl.migrate_remote(router_a, sid, tx)
                for sid in sids] == ["remote", "remote"]
        for sid in sids:
            with pytest.raises(KeyError):
                router_a.home_of(sid)
        feed(router_b, 2, 4)
        assert finish(router_b) == want
    finally:
        lsn.close()
    assert rx.imports == len(sids) and sorted(rx.imported_sids) == sids
    assert ctrl.remote_handoffs == len(sids)
    assert ctrl.remote_fallbacks == 0
    assert any(k.startswith("session_migrations{")
               and 'replica="peer:' in k for k in tel.counters)
    assert {p["outcome"] for p in pm.recent("migration")} \
        == {"remote_handoff"}
    assert obs_lint(tel, pm) == []


@pytest.mark.parametrize("point,count,want", [
    ("transport.send", 2, "remote"),     # flaps twice, third try lands
    ("transport.ack", 1, "remote"),      # ACK lost: retry is a duplicate
    ("transport.send", 99, "local"),     # hard down: retry exhausted
])
def test_scenario_transport_flaps_retry_or_fall_down_the_ladder(
        point, count, want):
    """A scripted ``transport.*`` fault under one handoff: a send flap
    is retried to ``remote``; a lost ACK retries onto the idempotent
    duplicate path, so the peer imports exactly once; a peer that stays
    down exhausts the retry (published on the timeline) and the session
    re-pins locally. No chunk is lost on any rung."""
    from deepspeech_tpu.obs import timeline
    from deepspeech_tpu.obs.timeline import EventLog
    from deepspeech_tpu.resilience import FaultPlan, FaultSpec, faults

    clock, tel, _, router_a = _host()
    _, tel_b, _, router_b = _host()
    rx = HandoffReceiver(router_b, name="host-b", telemetry=tel_b)
    ctrl = _ctrl(tel, clock)
    log = timeline.install(EventLog(registry=tel))
    kinds = []
    log.add_listener(lambda ev: kinds.append(
        (EventLog.to_record(ev)["kind"],
         EventLog.to_record(ev).get("detail", {}).get("status"))))
    router_a.join("a")
    router_a.step({"a": "c0"})
    faults.install(FaultPlan([FaultSpec(point, "unavailable",
                                        count=count)], seed=7,
                             clock=clock, registry=tel))
    try:
        out = ctrl.migrate_remote(router_a, "a",
                                  LoopbackTransport(rx, name="host-b"))
    finally:
        faults.clear()
        timeline.clear()
    assert out == want
    owner = router_b if want == "remote" else router_a
    assert rx.imports == (1 if want == "remote" else 0)
    assert (("remote_ack", "duplicate") in kinds) \
        == (point == "transport.ack")
    assert (("retry_exhausted", None) in kinds) == (want == "local")
    owner.step({"a": "c1"})
    owner.leave("a")
    owner.flush()
    assert owner.final("a") == "c0 c1"
