"""Shape-bucketed infer planning (data/infer_bucket.py) + the
compiled-shape ledger (utils/cache.ShapeBucketCache) + the
double-buffered device prefetch (data/pipeline.device_prefetch).

Pure host-side tests: the planner is a deterministic function of
(feat_lens, bucket_frames, max_batch) and everything here is checked
against hand-computed expectations. The end-to-end bit-identity of the
bucketed decode path lives in tests/test_infer.py; the one model-backed
scenario here is what the ladder buys on a mixed-length request.
"""

import numpy as np
import pytest

from deepspeech_tpu.data.infer_bucket import (InferBucketPlan, batch_rung,
                                              frame_rung, ladder_shapes,
                                              padding_waste,
                                              plan_infer_buckets,
                                              slice_to_plan, unbucket)
from deepspeech_tpu.data.pipeline import device_prefetch
from deepspeech_tpu.data.sampler import assign_buckets
from deepspeech_tpu.utils.cache import ShapeBucketCache

EDGES = (16, 32, 64)


def test_batch_rung():
    assert [batch_rung(n, 8) for n in (1, 2, 3, 5, 8, 9, 100)] == \
        [1, 2, 4, 8, 8, 8, 8]
    # Uncapped (serve.py's live stream count): plain next power of two.
    assert [batch_rung(n) for n in (1, 3, 9)] == [1, 4, 16]
    with pytest.raises(ValueError):
        batch_rung(0, 8)


def test_frame_rung_matches_sampler_assignment():
    # On-ladder lengths land on the sampler's own bucket edge — one
    # assignment rule (sampler.assign_buckets), no drift.
    for t in (1, 15, 16, 17, 40, 64):
        b = int(assign_buckets([t], sorted(EDGES))[0])
        if b < len(EDGES):
            assert frame_rung(t, EDGES) == sorted(EDGES)[b]
    # Overflow: multiples of the largest edge, so long audio still
    # decodes with a bounded shape set.
    assert frame_rung(65, EDGES) == 128
    assert frame_rung(128, EDGES) == 128
    assert frame_rung(129, EDGES) == 192


def test_ladder_shapes_is_the_compile_bound():
    shapes = ladder_shapes(EDGES, 8)
    # B rungs {1,2,4,8} x T rungs {16,32,64}.
    assert len(shapes) == 12
    assert set(shapes) == {(b, t) for b in (1, 2, 4, 8)
                           for t in (16, 32, 64)}
    # Non-power-of-two cap is itself a rung (a full batch never pads).
    assert (6, 16) in ladder_shapes(EDGES, 6)


def test_plan_is_deterministic_and_partitions_the_request():
    lens = np.array([10, 20, 40, 3, 33, 64, 17, 12])
    p1 = plan_infer_buckets(lens, EDGES, 4)
    p2 = plan_infer_buckets(lens, EDGES, 4)
    assert [(list(a.indices), a.batch_pad, a.bucket_frames)
            for a in p1] == \
        [(list(a.indices), a.batch_pad, a.bucket_frames) for a in p2]
    # Every request index appears exactly once.
    all_idx = sorted(i for p in p1 for i in p.indices)
    assert all_idx == list(range(len(lens)))
    for p in p1:
        assert p.n_valid <= 4                     # chunked at max_batch
        assert p.batch_pad == batch_rung(p.n_valid, 4)
        for i in p.indices:
            assert lens[i] <= p.bucket_frames     # every row fits
    # Ascending-T emission order.
    rungs = [p.bucket_frames for p in p1]
    assert rungs == sorted(rungs)
    with pytest.raises(ValueError):
        plan_infer_buckets([], EDGES, 4)


def test_padding_waste_hand_computed():
    # 10 -> rung 16, 20 -> rung 32, 40 -> overflow rung 64 (2 * top).
    lens = [10, 20, 40]
    plans = plan_infer_buckets(lens, (16, 32), 2)
    assert [(p.batch_pad, p.bucket_frames) for p in plans] == \
        [(1, 16), (1, 32), (1, 64)]
    # computed = 16 + 32 + 64 = 112, real = 70 -> waste = 42/112.
    assert padding_waste(lens, plans) == pytest.approx(42 / 112)
    # Single-max-shape comparison point this must beat: everything at
    # (2, 64) x 2 batches = 256 computed -> waste 186/256.
    assert padding_waste(lens, plans) < 1 - 70 / 256


def test_slice_to_plan_shapes_pad_rows_and_overflow():
    lens = np.array([10, 20, 40])
    batch = {
        "features": np.arange(3 * 40 * 2, dtype=np.float32)
                      .reshape(3, 40, 2),
        "feat_lens": lens,
    }
    plans = plan_infer_buckets(lens, (16, 32), 4)
    subs = [slice_to_plan(batch, p) for p in plans]
    # Emitted shapes are EXACTLY the plan's rung — including the
    # overflow rung (64), zero-padded past the source array's 40.
    assert [s["features"].shape for s in subs] == \
        [(1, 16, 2), (1, 32, 2), (1, 64, 2)]
    np.testing.assert_array_equal(subs[0]["features"][0],
                                  batch["features"][0, :16])
    np.testing.assert_array_equal(subs[2]["features"][0, :40],
                                  batch["features"][2])
    assert not subs[2]["features"][0, 40:].any()
    # Row padding repeats the last real row (the eval_epoch precedent:
    # no zero-length streams reach a decode path).
    p = InferBucketPlan(np.array([0, 1]), batch_pad=4, bucket_frames=32)
    sub = slice_to_plan(batch, p)
    assert sub["features"].shape == (4, 32, 2)
    np.testing.assert_array_equal(sub["features"][2], sub["features"][1])
    assert list(sub["feat_lens"]) == [10, 20, 20, 20]


def test_unbucket_restores_request_order():
    lens = np.array([10, 20, 40, 3, 33, 64, 17, 12])
    plans = plan_infer_buckets(lens, EDGES, 4)
    per_plan = [[f"u{i}" for i in p.indices] for p in plans]
    assert unbucket(plans, per_plan) == [f"u{i}" for i in range(len(lens))]
    # Rows past n_valid (decode output for the repeated pad rows) are
    # ignored even when present.
    padded = [r + ["PAD"] * (p.batch_pad - p.n_valid)
              for p, r in zip(plans, per_plan)]
    assert unbucket(plans, padded) == [f"u{i}" for i in range(len(lens))]


def test_shape_bucket_cache_counters(caplog):
    c = ShapeBucketCache(max_shapes=2)
    assert c.note(4, 16, 30) is False      # miss: first (4, 16)
    assert c.note(4, 16, 20) is True       # hit
    assert c.note(2, 32, 10) is False
    assert c.compiles == 2 and c.hits == 1
    # padded = 4*16 + 4*16 + 2*32 = 192, valid = 60.
    assert c.padded_frames == 192 and c.valid_frames == 60
    assert c.padding_waste == pytest.approx(1 - 60 / 192)
    s = c.stats()
    assert s["compiles"] == 2 and s["hits"] == 1
    assert s["shapes"] == [(2, 32), (4, 16)]
    # A third distinct shape exceeds max_shapes: warn, don't fail
    # (overflow rungs for very long audio must still serve).
    import logging

    with caplog.at_level(logging.WARNING,
                         logger="deepspeech_tpu.utils.cache"):
        c.note(1, 64, 5)
    assert any("grew past the ladder" in r.message for r in caplog.records)
    assert c.compiles == 3
    # Fresh empty cache: waste is 0, not a division error.
    assert ShapeBucketCache().padding_waste == 0.0


def test_device_prefetch_order_and_overlap():
    puts = []

    def put(x):
        puts.append(x)
        return x * 10

    g = device_prefetch(iter(range(5)), put_fn=put)
    assert next(g) == 0
    # Double buffering: when item k is yielded, item k+1's put (the
    # host->device dispatch) has already been issued.
    assert puts == [0, 1]
    assert list(g) == [10, 20, 30, 40]
    assert puts == [0, 1, 2, 3, 4]
    # depth=1 degenerates to a plain map; tail still drains.
    assert list(device_prefetch(iter([7]), put_fn=put, depth=1)) == [70]
    with pytest.raises(ValueError):
        list(device_prefetch(iter([1]), put_fn=put, depth=0))


def test_scenario_mixed_request_wastes_less_than_one_max_shape(tiny_offline):
    """A mixed-length request through a real (tiny) engine: the ladder
    pads strictly less than decoding every batch at the single max
    shape, compiles at most one executable a rung, and a repeated
    request compiles nothing."""
    cfg = tiny_offline.cfg
    edges, bs = cfg.data.bucket_frames, cfg.data.batch_size
    t_max, nf = max(edges), cfg.features.num_features
    rng = np.random.default_rng(0)
    n = 2 * bs + bs // 2
    lens = rng.integers(t_max // 8, t_max, size=n, endpoint=True)
    feats = rng.standard_normal((n, t_max, nf)).astype(np.float32)
    for i, k in enumerate(lens):
        feats[i, k:] = 0.0
    batch = {"features": feats, "feat_lens": lens.astype(np.int32)}
    inf = tiny_offline.inferencer()
    texts = inf.decode_batch_bucketed(batch)
    assert len(texts) == n
    waste = padding_waste(lens, plan_infer_buckets(lens, edges, bs))
    baseline = 1.0 - lens.sum() / (-(-n // bs) * bs * t_max)
    assert 0.0 < waste < baseline
    assert inf.shape_cache.padding_waste == pytest.approx(waste)
    compiles = inf.shape_cache.compiles
    assert 0 < compiles <= len(ladder_shapes(edges, bs))
    assert inf.decode_batch_bucketed(batch) == texts
    assert inf.shape_cache.compiles == compiles
    assert inf.shape_cache.hits >= compiles
