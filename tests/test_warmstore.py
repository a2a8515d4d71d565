"""Executable warm store: zero-compile restarts of the rung ladder.

Covers the ISSUE-16 contracts at both layers:

- utils/aotstore.py — store key <-> filename round trip, atomic
  put/get, the hit/reject/miss lookup semantics (a reject is an entry
  that exists only under a foreign fingerprint), the portable-
  fingerprint fallback the offline AOT emitters rely on, tree
  signatures, and corrupt-entry tolerance (a torn entry is a miss,
  never a crash).
- utils/cache.py sidecar — rung-usage persistence seeding
  ``warm_rung_chooser`` across restarts (mixed-era / torn / absent
  files tolerated), and ``ShapeBucketCache.preload`` semantics
  (preloaded rungs hit from call one, fire no compile event, and are
  NOT counted as runtime compiles).
- serving/warmstore.py — end to end on a real (tiny) Inferencer:
  first-compile export, restart preload with bit-identical decode and
  zero runtime compiles, fingerprint-mismatch rejection falling back
  to jit (``compile_cache_reject`` counted, transcripts unchanged —
  the regression test for the documented SIGABRT class), signature
  mismatch rejection, and ineligible (non-inferencer) replicas being
  skipped silently.
"""

import dataclasses
import io
import json
import os

import numpy as np
import pytest

from scenario import counter_family

from deepspeech_tpu.serving import Replica, ServingTelemetry, WarmStore
from deepspeech_tpu.serving.warmstore import default_store, store_tier
from deepspeech_tpu.utils import aotstore
from deepspeech_tpu.utils.aotstore import (AotStore, StoreKey,
                                           parse_filename)
from deepspeech_tpu.utils.cache import (USAGE_SIDECAR, ShapeBucketCache,
                                        load_rung_usage,
                                        save_rung_usage, seed_usage)

NF = 13
EDGES = (64,)
BS = 2  # ladder = [(1, 64), (2, 64)]


# -- aotstore: keys, layout, lookup ---------------------------------------

def test_storekey_filename_roundtrip():
    key = StoreKey("dev_slice", "fp", "base", 8, 1700)
    assert key.rung == "8x1700"
    name = key.filename()
    assert name == "dev_slice--fp--base--b8xt1700.wse"
    assert parse_filename(name) == key
    assert parse_filename("not-an-entry.bin") is None


def test_storekey_sanitizes_unsafe_components():
    key = StoreKey("pre set/x", "", "ckpt:42", 1, 64)
    name = key.filename()
    assert "/" not in name and ":" not in name and " " not in name
    # '' is structural: it must round-trip as a parseable placeholder.
    parsed = parse_filename(name)
    assert parsed is not None and parsed.tier == "none"


def test_put_get_lookup_hit_reject_miss(tmp_path):
    root = str(tmp_path / "store")
    key = StoreKey("p", "fp", "base", 2, 64)
    a = AotStore(root, fingerprint="fp-A")
    a.put(key, b"payload-bytes", aotstore.FORMAT_EXECUTABLE, sig="s1")

    status, meta, payload = a.lookup(key)
    assert status == "hit" and payload == b"payload-bytes"
    assert meta["sig"] == "s1" and meta["fingerprint"] == "fp-A"
    assert a.keys() == [key]
    assert a.rungs("p", "fp", "base") == [(2, 64)]

    # Same root, different machine/toolchain: the entry exists only
    # under a foreign fingerprint -> reject, payload withheld.
    b = AotStore(root, fingerprint="fp-B")
    status, meta, payload = b.lookup(key)
    assert status == "reject" and payload is None
    assert meta["fingerprint"] == "fp-A"

    # Absent key: plain miss for both.
    other = StoreKey("p", "fp", "base", 4, 64)
    assert a.lookup(other)[0] == "miss"
    assert b.lookup(other)[0] == "miss"


def test_lookup_portable_fallback_is_a_hit(tmp_path):
    """Entries the offline AOT tools emit land under the PORTABLE
    target fingerprint; a runtime that registers it as a fallback
    must preload them instead of rejecting over the machine axis."""
    root = str(tmp_path / "store")
    key = StoreKey("p", "fp", "base", 2, 64)
    emitter = AotStore(root, fingerprint="portable-tpu")
    emitter.put(key, b"xc-bytes", aotstore.FORMAT_EXECUTABLE)

    runtime = AotStore(root, fingerprint="host-tpu-machine",
                       fallback_fingerprints=("portable-tpu",))
    status, _, payload = runtime.lookup(key)
    assert status == "hit" and payload == b"xc-bytes"
    # Without the fallback the same entry is a reject.
    assert AotStore(root, fingerprint="host-tpu-machine").lookup(
        key)[0] == "reject"


def test_put_rejects_unknown_format(tmp_path):
    store = AotStore(str(tmp_path), fingerprint="fp")
    with pytest.raises(ValueError):
        store.put(StoreKey("p", "fp", "base", 1, 64), b"x", "elf")


def test_corrupt_entry_is_a_miss_not_a_crash(tmp_path):
    root = str(tmp_path / "store")
    key = StoreKey("p", "fp", "base", 2, 64)
    store = AotStore(root, fingerprint="fp-A")
    store.put(key, b"ok", aotstore.FORMAT_EXECUTABLE)
    path = tmp_path / "store"
    entry = next(path.rglob("*.wse"))
    entry.write_bytes(b"\x00not json at all")
    assert store.lookup(key)[0] == "miss"
    assert store.get(key) is None


def test_tree_signature_tracks_shapes_and_dtypes():
    import jax

    t1 = {"w": np.zeros((3, 4), np.float32), "b": np.zeros((4,))}
    t2 = {"w": np.ones((3, 4), np.float32), "b": np.zeros((4,))}
    t3 = {"w": np.zeros((3, 5), np.float32), "b": np.zeros((4,))}
    t4 = {"w": np.zeros((3, 4), np.int8), "b": np.zeros((4,))}
    sig = aotstore.tree_signature
    assert sig(t1) == sig(t2)          # values don't matter
    assert sig(t1) != sig(t3)          # shapes do
    assert sig(t1) != sig(t4)          # dtypes do
    # Abstract twins (the offline emitters sign shape trees).
    t1_abs = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t1)
    assert sig(t1_abs) == sig(t1)


def test_fingerprints_cover_platform_and_machine():
    host = aotstore.host_fingerprint()
    portable = aotstore.fingerprint_for("tpu")
    assert "machine=" in host
    assert "machine=" not in portable and "plat=tpu" in portable


# -- cache: preload + rung-usage sidecar ----------------------------------

def test_shape_cache_preload_hits_without_runtime_compiles():
    c = ShapeBucketCache()
    events = []
    c.export_hook = lambda b, t: events.append((b, t))
    assert c.preload([(2, 64), (1, 64)]) == 2
    assert c.preloaded == 2
    # Call one on a preloaded rung is a HIT: no compile event, no
    # export-hook fire, and the runtime-compile truth stays 0.
    assert c.note(2, 64, 10) is True
    assert c.compiles == 0 and events == []
    # A genuinely cold rung still compiles, counts, and exports.
    assert c.note(4, 64, 10) is False
    assert c.compiles == 1 and events == [(4, 64)]
    assert c.stats()["preloaded"] == 2


def test_rung_usage_sidecar_roundtrip_and_seeding(tmp_path):
    c = ShapeBucketCache()
    c.note(2, 64, 10)
    c.note(2, 64, 10)
    c.note(1, 64, 5)
    path = str(tmp_path / "rung_usage.jsonl")
    save_rung_usage(c, path, preset="dev_slice")
    usage = load_rung_usage(path)
    assert set(usage) == {(2, 64), (1, 64)}
    assert usage[(2, 64)] > usage[(1, 64)]

    fresh = ShapeBucketCache()
    assert seed_usage(fresh, usage) == 2
    # Seeding is the ROUTING signal only: rungs rank warm for the
    # chooser but are not marked compiled (a cold jit still counts).
    assert set(fresh.rung_usage()) == {(2, 64), (1, 64)}
    assert fresh.compiles == 0
    assert fresh.note(2, 64, 10) is False
    assert fresh.compiles == 1


def test_load_rung_usage_tolerates_mixed_eras_and_torn_lines(tmp_path):
    path = tmp_path / "rung_usage.jsonl"
    path.write_text("\n".join([
        json.dumps({"event": "rung_usage", "ts": 1.0,
                    "usage": {"2x64": 1.0, "bogus": 9.0}}),
        "{torn line",
        json.dumps({"not": "a usage record"}),
        json.dumps({"event": "rung_usage", "ts": 2.0,
                    "usage": {"2x64": 5.0, "4x128": 2.0}}),
    ]) + "\n")
    usage = load_rung_usage(str(path))
    assert usage == {(2, 64): 5.0, (4, 128): 2.0}  # last era wins
    assert load_rung_usage(str(tmp_path / "absent.jsonl")) == {}


def test_seed_usage_bounded_by_max_shapes():
    c = ShapeBucketCache(max_shapes=2)
    big = {(1, 64): 1.0, (2, 64): 3.0, (4, 64): 2.0}
    assert seed_usage(c, big) == 2
    assert set(c.rung_usage()) == {(2, 64), (4, 64)}  # top scores win
    assert c.evictions == 0


# -- warmstore: end to end on a tiny inferencer ---------------------------

@pytest.fixture(scope="module")
def tiny_infer_factory(tiny_offline):
    cfg = tiny_offline.cfg
    cfg = dataclasses.replace(cfg, data=dataclasses.replace(
        cfg.data, bucket_frames=EDGES, batch_size=BS))
    return lambda: tiny_offline.inferencer(cfg)


LADDER = [(1, 64), (2, 64)]


def _decode_ladder(inf):
    from deepspeech_tpu.data.infer_bucket import InferBucketPlan

    rng = np.random.default_rng(7)
    texts = []
    for b, t in LADDER:
        feats = rng.standard_normal((b, t, NF)).astype(np.float32)
        batch = {"features": feats,
                 "feat_lens": np.full((b,), t, np.int32)}
        texts.extend(inf.decode_batch_bucketed(
            batch, plans=[InferBucketPlan(np.arange(b), b, t)]))
    return texts


@pytest.fixture(scope="module")
def populated_store(tiny_infer_factory, tmp_path_factory):
    """One cold run: compile the 2-rung ladder, export every rung at
    first compile, return (store_root, cold_texts)."""
    root = str(tmp_path_factory.mktemp("warmstore"))
    tel = ServingTelemetry()
    ws = WarmStore(root, preset="dev_slice", background=False)
    inf = tiny_infer_factory()
    Replica.from_inferencer("r0", inf, telemetry=tel, warmstore=ws)
    texts = _decode_ladder(inf)
    ws.flush()
    assert inf.shape_cache.compiles == len(LADDER)
    assert len(ws.store.keys()) == len(LADDER)
    assert counter_family(tel, "compile_cache_export") == len(LADDER)
    assert counter_family(tel, "compile_cache_miss") == len(LADDER)
    return root, texts


def test_restart_preloads_ladder_bit_identical(tiny_infer_factory,
                                               populated_store):
    root, cold_texts = populated_store
    tel = ServingTelemetry()
    ws = WarmStore(root, preset="dev_slice", background=False)
    inf = tiny_infer_factory()
    rep = Replica.from_inferencer("r0", inf, telemetry=tel,
                                  warmstore=ws)
    assert sorted(inf.preloaded_forwards) == sorted(LADDER)
    assert inf.shape_cache.preloaded == len(LADDER)
    texts = _decode_ladder(inf)
    # The whole point: bit-identical decode, zero runtime compiles.
    assert texts == cold_texts
    assert inf.shape_cache.compiles == 0
    assert counter_family(tel, "compile_cache_hit") == len(LADDER)
    # Counters always carry rung + tier (the schema-lint contract).
    hit_keys = [k for k in tel.counters
                if k.startswith("compile_cache_hit")]
    assert hit_keys and all(
        "rung=" in k and "tier=" in k for k in hit_keys)
    assert tel.gauges[
        'warm_pct{replica="r0",tier="fp"}'] == 100.0
    assert rep.can_route(0.0)


def _compiled_on(ids):
    """``v * 2`` compiled for the devices ``ids`` of this platform in
    that order (one device, or a mesh over several), and an argument
    placed for it."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    devs = np.array(jax.devices())[list(ids)]
    sharding = NamedSharding(Mesh(devs, ("d",)), PartitionSpec("d"))
    x = jax.device_put(jnp.arange(8.0), sharding)
    return jax.jit(lambda v: v * 2.0).lower(x).compile(), x


@pytest.mark.parametrize("ids", [[3], [5, 2, 7, 1]],
                         ids=["one_device", "mesh_in_its_own_order"])
def test_compiled_blob_carries_its_devices_and_reloads_on_them(ids):
    """On a platform with eight devices an executable says in its blob
    which devices it was compiled for, in its own order, and the
    reloaded callable runs there (jax's payload alone loads it onto
    every device of the backend)."""
    import pickle

    comp, x = _compiled_on(ids)
    blob = aotstore.serialize_compiled(comp)
    assert pickle.loads(blob)[3] == ids
    out = aotstore.deserialize_compiled(blob)(x)
    assert out.sharding == x.sharding
    assert [s.device.id for s in out.addressable_shards] == ids
    np.testing.assert_array_equal(np.asarray(out), np.arange(8.0) * 2.0)


@pytest.mark.parametrize("ids,error", [
    pytest.param(None, ValueError, id="written_before_ids_were_stored"),
    pytest.param([99], KeyError, id="device_not_on_this_backend")])
def test_blob_without_usable_device_ids_does_not_load(ids, error):
    import jax
    import pickle

    comp, _ = _compiled_on([0])
    parts = pickle.loads(aotstore.serialize_compiled(comp))[:3]
    blob = pickle.dumps(parts if ids is None else parts + (ids,))
    with pytest.raises(error):
        aotstore.deserialize_compiled(blob)


def test_stored_blob_without_device_ids_rejects_to_jit(
        tiny_infer_factory, populated_store, tmp_path):
    """A store written before the ids travelled with the blob: the rung
    is rejected at preload and compiles through jit, instead of being
    counted a hit and failing on the first request."""
    import pickle
    import shutil

    root, cold_texts = populated_store
    old_root = str(tmp_path / "old")
    shutil.copytree(root, old_root)
    ws = WarmStore(old_root, preset="dev_slice", background=False)
    key = StoreKey("dev_slice", "fp", "base", *LADDER[0])
    meta, payload = ws.store.get(key)
    ws.store.put(key, pickle.dumps(pickle.loads(payload)[:3]),
                 meta["format"], sig=meta["sig"])
    tel = ServingTelemetry()
    inf = tiny_infer_factory()
    Replica.from_inferencer("r0", inf, telemetry=tel, warmstore=ws)
    assert counter_family(tel, "compile_cache_reject") == 1
    assert counter_family(tel, "compile_cache_hit") == len(LADDER) - 1
    assert LADDER[0] not in inf.preloaded_forwards
    ws.flush()
    assert _decode_ladder(inf) == cold_texts
    assert inf.shape_cache.compiles == 1


def test_fingerprint_mismatch_rejects_to_jit(tiny_infer_factory,
                                             populated_store):
    """The documented SIGABRT class, downgraded to a counter: entries
    built by a different toolchain/machine must never be loaded —
    every rung rejects, jit recompiles, transcripts are unchanged."""
    root, cold_texts = populated_store
    tel = ServingTelemetry()
    ws = WarmStore(root, preset="dev_slice", background=False,
                   fingerprint="jax=9.9|jaxlib=9.9|libtpu=none|"
                               "plat=tpu|machine=other")
    inf = tiny_infer_factory()
    rep = Replica.from_inferencer("r0", inf, telemetry=tel,
                                  warmstore=None)
    summary = ws.preload_replica(rep)
    assert summary["rejects"] == len(LADDER)
    assert summary["hits"] == 0 and summary["warm_pct"] == 0.0
    assert inf.preloaded_forwards == {}
    assert counter_family(tel, "compile_cache_reject") == len(LADDER)
    texts = _decode_ladder(inf)
    assert texts == cold_texts          # jit fallback, same bytes
    assert inf.shape_cache.compiles == len(LADDER)


def test_signature_mismatch_rejects_single_rung(tiny_infer_factory,
                                                populated_store):
    """Same version label, different weights shape/dtype: the rung
    whose stored signature no longer matches rejects; the rest of the
    ladder still preloads."""
    root, _ = populated_store
    ws = WarmStore(root, preset="dev_slice", background=False)
    key = StoreKey("dev_slice", "fp", "base", *LADDER[0])
    orig_meta, orig_payload = ws.store.get(key)
    ws.store.put(key, orig_payload, orig_meta["format"],
                 sig="0000deadbeef0000")
    try:
        tel = ServingTelemetry()
        inf = tiny_infer_factory()
        Replica.from_inferencer("r0", inf, telemetry=tel, warmstore=ws)
        assert counter_family(tel, "compile_cache_reject") == 1
        assert counter_family(tel, "compile_cache_hit") == len(LADDER) - 1
        assert LADDER[0] not in inf.preloaded_forwards
        assert LADDER[1] in inf.preloaded_forwards
    finally:
        # Put the good entry back: the store fixture is module-shared.
        ws.store.put(key, orig_payload, orig_meta["format"],
                     sig=orig_meta["sig"])


def test_ineligible_replica_is_skipped_silently(tmp_path):
    ws = WarmStore(str(tmp_path / "s"), background=False)
    rep = Replica("stream0", decode_fn=lambda batch, plan: [])
    out = ws.preload_replica(rep)
    assert out == {"eligible": False, "hits": 0}
    assert ws.install_export_hook(rep) is False
    assert not any(k.startswith("compile_cache")
                   for k in rep.telemetry.counters)


def test_store_tier_keys_by_quality_then_numeric_family():
    class _Q:
        _quantized = True

    class _F:
        _quantized = False

    assert store_tier(_Q(), "premium") == "premium"
    assert store_tier(_Q(), None) == "int8"
    assert store_tier(_F(), None) == "fp"


def test_default_store_reads_env(tmp_path, monkeypatch):
    monkeypatch.delenv("DS2_WARMSTORE_DIR", raising=False)
    assert default_store() is None
    monkeypatch.setenv("DS2_WARMSTORE_DIR", str(tmp_path / "ws"))
    ws = default_store()
    assert isinstance(ws, WarmStore)
    assert ws.store.root == str(tmp_path / "ws")


def test_scenario_restart_and_fleet_consumers_start_warm(
        tiny_infer_factory, populated_store, obs_lint, postmortems):
    """A restart under tracing plus the two fleet paths that bring a
    replica up (autoscale scale-up, rolling swap to v2): the restarted
    engine's trace has no compile event, its rung-usage sidecar marks
    the ladder warm before traffic, each consumer leaves a
    ``warm_start`` postmortem that avoided compiles, and everything
    emitted passes the schema lint."""
    from deepspeech_tpu import obs
    from deepspeech_tpu.obs.metrics import MetricsRegistry
    from deepspeech_tpu.serving import (AutoscaleController, ReplicaPool,
                                        RolloutController)

    root, cold_texts = populated_store
    pm = postmortems

    def store():
        return WarmStore(root, preset="dev_slice", background=False,
                         postmortem_fn=pm.write)

    # Restart: the cold run's usage sidecar seeds the fresh cache, then
    # the ladder preloads and decodes with tracing on.
    cold = tiny_infer_factory()
    _decode_ladder(cold)
    sidecar = os.path.join(root, USAGE_SIDECAR)
    save_rung_usage(cold.shape_cache, sidecar, preset="dev_slice")
    tel = ServingTelemetry()
    inf = tiny_infer_factory()
    assert seed_usage(inf.shape_cache,
                      load_rung_usage(sidecar)) == len(LADDER)
    assert set(LADDER) <= set(inf.shape_cache.rung_usage())
    trace = io.StringIO()
    obs.configure(enabled=True, sink=trace, registry=MetricsRegistry())
    try:
        Replica.from_inferencer("r0", inf, telemetry=tel,
                                warmstore=store())
        assert _decode_ladder(inf) == cold_texts
    finally:
        obs.configure(enabled=False, registry=obs.registry())
    assert not [ln for ln in trace.getvalue().splitlines()
                if json.loads(ln).get("event") == "compile"]
    assert inf.shape_cache.compiles == 0

    # Autoscale scale-up: the newcomer preloads before it is routable.
    tel_up = ServingTelemetry()

    def factory(rid):
        return Replica.from_inferencer(rid, tiny_infer_factory(),
                                       telemetry=tel_up)

    pool = ReplicaPool([factory("r0")], telemetry=tel_up)
    ctrl = AutoscaleController(pool, factory, max_replicas=2,
                               slo_burn_budget=1.0, hold_s=0.05,
                               telemetry=tel_up, warmstore=store(),
                               postmortem_fn=pm.write)
    tel_up.gauge("slo_burn_rate", 2.0,       # pressure 1.0, held
                 labels={"window": "1h", "tier": "fp"})
    ctrl.tick(0.0)
    ctrl.tick(1.0)
    assert len(pool) == 2 and ctrl.scale_ups == 1

    # Rolling swap: the v2 ladder is in the store (same shapes, so the
    # base entries re-keyed); every swapped replica re-admits warm.
    tel_ro = ServingTelemetry()
    ws = store()
    for key in ws.store.keys():
        meta, payload = ws.store.get(key)
        ws.store.put(dataclasses.replace(key, version="v2"), payload,
                     meta["format"], sig=meta["sig"])
    pool_ro = ReplicaPool(
        [Replica.from_inferencer(f"r{k}", tiny_infer_factory(),
                                 telemetry=tel_ro, warmstore=ws)
         for k in range(2)], telemetry=tel_ro)

    def v2_backend(rep):
        inf2 = tiny_infer_factory()
        return {"decode_fn": lambda batch, plan:
                inf2.decode_batch_bucketed(batch, plans=[plan]),
                "session_factory": None, "inferencer": inf2}

    ro = RolloutController(pool_ro, v2_backend, to_version="v2",
                           telemetry=tel_ro, warmstore=ws,
                           drain_window_s=0.0, postmortem_fn=pm.write)
    ro.run(sleep_s=0.0)
    assert ro.state == "done"

    warm = [p for p in pm.recent("warm_start")]
    by_trigger = {t: [p for p in warm if p["trigger"] == t]
                  for t in ("scale_up", "rollout_readmit")}
    assert len(by_trigger["scale_up"]) == 1
    assert len(by_trigger["rollout_readmit"]) == 2
    for p in by_trigger["scale_up"] + by_trigger["rollout_readmit"]:
        assert p["compiles_avoided"] == len(LADDER)
        assert p["warm_pct"] == 100.0
    assert obs_lint(tel, tel_up, tel_ro, pm) == []
