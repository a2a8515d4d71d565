"""The generators: the same seed gives the same bytes, and session
traffic never needs more slots than the manager has."""

import hashlib
import json
import os

import numpy as np
import pytest

from benchmark.gen import batches, sessions
from conftest import ROOT


def traffic(name):
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           name + ".json")) as f:
        return json.load(f)


def schedule_digest(params, seed, ticks=400):
    t = sessions.SessionTraffic(params, seed=seed)
    h = hashlib.sha256()
    peak = 0
    for _ in range(ticks):
        before = t.occupied()
        tk = t.next_tick()
        # Everything attached while the tick runs: what was there plus
        # what joined (finals leave only after the step).
        peak = max(peak, before + len(tk.joins))
        h.update(repr((tk.index, tk.joins, tk.leaves, tk.feeds,
                       tk.finals, tk.frames)).encode())
    return h.hexdigest(), peak, t


@pytest.mark.parametrize("mix", ["voice_sparse", "voice_dense"])
def test_sessions_same_seed_same_schedule(mix):
    p = traffic(mix)
    a, peak, t = schedule_digest(p, seed=3)
    b, _, _ = schedule_digest(p, seed=3)
    c, _, _ = schedule_digest(p, seed=4)
    assert a == b and a != c
    assert peak <= p["capacity"]
    # The mix is what its file says: about four fifths of the slots
    # attached, sessions of about three seconds.
    lens = [v[1] for v in t.plans.values()]
    assert p["len_min_frames"] <= min(lens)
    assert max(lens) <= p["len_max_frames"]
    if p["capacity"] >= 64:
        assert 250 < np.mean(lens) < 350


def test_sessions_joins_and_leaves_are_consistent():
    p = traffic("voice_sparse")
    t = sessions.SessionTraffic(p, seed=0)
    attached, fed = set(), {}
    for _ in range(600):
        tk = t.next_tick()
        for sid in tk.joins:
            assert sid not in attached
            attached.add(sid)
            fed[sid] = 0
        for sid, k in tk.feeds:
            assert sid in attached and k == fed[sid] // p["chunk_frames"]
            fed[sid] += p["chunk_frames"]
        for sid, n in tk.leaves:
            assert 0 <= n < p["chunk_frames"]
            fed[sid] += n
            assert fed[sid] == t.plans[sid][1]
        for sid in tk.finals:
            attached.remove(sid)
        assert len(attached) <= p["capacity"]


def test_chunk_pool_and_batches_are_seeded():
    p = traffic("voice_sparse")
    a = sessions.chunk_pool(p, seed=5, num_features=161)
    b = sessions.chunk_pool(p, seed=5, num_features=161)
    assert a.tobytes() == b.tobytes() and a.shape == (64, 64, 161)
    q = dict(traffic("train_16s_b32"), per_chip_batch=2, pool_batches=2)
    kw = dict(chips=1, vocab_size=29, max_label_len=256,
              num_features=161, time_stride=2)
    x = batches.make_batches(q, seed=7, **kw)
    y = batches.make_batches(q, seed=7, **kw)
    z = batches.make_batches(q, seed=8, **kw)
    for k in x[0]:
        assert x[1][k].tobytes() == y[1][k].tobytes()
    assert x[0]["features"].tobytes() != z[0]["features"].tobytes()
    b0 = x[0]
    assert b0["features"].shape == (2, 1700, 161)
    assert (b0["feat_lens"] >= 1201).all() and (b0["feat_lens"] <= 1650).all()
    # 14.5 characters per second of speech, not a toy lattice.
    assert (b0["label_lens"] == np.round(0.145 * b0["feat_lens"])).all()
    assert (b0["features"][0, b0["feat_lens"][0]:] == 0).all()
    assert batches.audio_seconds(b0) == pytest.approx(
        b0["feat_lens"].sum() * 0.01)
