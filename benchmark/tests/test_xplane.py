"""reduce/xplane.py: interval arithmetic on hand-made intervals, the
reduction on a hand-made trace, and the whole path on a small trace
recorded on a TPU v5e (``data/voice_sparse_tpu.xplane.pb``: 0.2 s of
``ds2_streaming.voice_sparse``, PR 22)."""

import os

import pytest

from benchmark.reduce import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "voice_sparse_tpu.xplane.pb")
KERNEL = ('%step.3 = (f32[8,4,16]{2,1,0}, f32[4,16]{1,0}) custom-call('
          'bf16[8,4,48]{2,1,0} %x), custom_call_target="tpu_custom_call"')
FUSION = "%fusion.1 = bf16[4,8]{1,0:T(8,128)} fusion(bf16[4,8]{1,0} %p)"
ALLRED = ("%all-reduce-start.2 = f32[64]{0} all-reduce-start(f32[64]{0} "
          "%g), replica_groups={{0,1}}")


def test_interval_arithmetic():
    u = xplane.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)])
    assert u == [(0, 3), (5, 8)]
    assert xplane.total(u) == 6
    assert xplane.subtract([(0, 10)], u) == [(3, 5), (8, 10)]
    assert xplane.subtract(u, [(1, 6)]) == [(0, 1), (6, 8)]
    assert xplane.subtract(u, []) == u


def test_short_name_keeps_what_identifies_an_op():
    assert xplane.short_name(FUSION) == "%fusion.1 fusion bf16[4,8]"
    assert xplane.short_name(KERNEL) == \
        "%step.3 custom-call [mosaic] (f32[8,4,16], f32[4,16])"
    assert xplane.short_name("jit_step(123)") == "jit_step(123)"


def hand_made():
    tr = xplane.Trace()
    dev = tr.devices.setdefault(0, xplane.DeviceTrace(0))
    # window [100, 200): busy 110-130 (two overlapping ops), 150-170
    # (kernel), 180-190 (all-reduce wait with nothing else running)
    dev.ops += [(110, 125, FUSION), (120, 130, FUSION),
                (150, 170, KERNEL), (90, 105, FUSION),
                (180, 190, ALLRED.replace("-start", "-done"))]
    dev.async_ops += [(160, 190, ALLRED)]
    dev.modules += [(105, 195, "jit_step(1)")]
    tr.annotations.append((100, 101, xplane.ANCHOR))
    return tr


def test_reduce_hand_made_trace():
    tr = hand_made()
    assert tr.anchor_ns() == 100
    spans = [("gen", 100, 110), ("step", 110, 178), ("join", 178, 200)]
    r = xplane.reduce_trace(tr, (100, 200), spans)
    # busy: 100-105 (clipped), 110-130, 150-170, 180-190 = 55 of 100
    assert r["busy_s"] == pytest.approx(55e-9)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["idle_pct"] == pytest.approx(45.0)
    assert r["kernel_s"] == pytest.approx(20e-9)
    # collective 160-190; compute covers 160-170: 20 ns exposed
    assert r["collective_s"] == pytest.approx(30e-9)
    assert r["collective_exposed_s"] == pytest.approx(20e-9)
    assert r["programs"] == 1
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["%fusion.1 fusion bf16[4,8]"] == pytest.approx(30e-9)
    assert r["breakdown"]["device_ops"][0][0].startswith("%fusion.1")
    # gaps: 105-110 (gen), 130-150 and 170-178 (step), 178-180 and
    # 190-200 (join)
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert gaps == {"step": pytest.approx(28e-9),
                    "join": pytest.approx(12e-9),
                    "gen": pytest.approx(5e-9)}
    assert r["longest_gaps"][0] == ["step", pytest.approx(20e-9)]
    assert xplane.kernel_events(tr, (100, 200)) == \
        [(KERNEL, pytest.approx(20e-9))]


def test_uncovered_idle_time_is_named():
    r = xplane.reduce_trace(hand_made(), (100, 200), [("step", 110, 140)])
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert gaps["step"] == pytest.approx(10e-9)
    assert gaps["(no span)"] == pytest.approx(35e-9)


def test_recorded_tpu_trace():
    tr = xplane.load(DATA)
    assert list(tr.devices) == [0]
    anchor = tr.anchor_ns()
    assert anchor is not None
    dev = tr.devices[0]
    assert len(dev.ops) > 1000 and dev.modules
    hi = max(b for _, b, _ in dev.ops)
    r = xplane.reduce_trace(tr, (anchor, hi), [("all", anchor, hi)])
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["idle_pct"] == pytest.approx(
        100 * (1 - r["busy_s"] / r["window_s"]))
    # One chunk program per tick, five scan kernels (one a layer) in it.
    chunk = [m for m in dev.modules if m[2].startswith("jit__chunk_fn")
             and anchor <= m[0] < hi]
    kernels = xplane.kernel_events(tr, (anchor, hi))
    assert len(chunk) > 3 and len(kernels) == 5 * len(chunk)
    assert all("tpu_custom_call" in k for k, _ in kernels)
    assert r["kernel_s"] == pytest.approx(sum(s for _, s in kernels))
    assert 0 < r["kernel_s"] < r["busy_s"]
    # Idle time is all accounted for, and all to the one span given.
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    assert gaps["all"] == pytest.approx(sum(gaps.values()), rel=1e-3)
    assert len(r["breakdown"]["device_ops"]) == 10
    assert r["collective_s"] == 0
