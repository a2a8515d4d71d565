"""Grouped-query attention with a window, a gate and per-kind caches
(``models/lfm2.py`` ``Attention``, ``decode/lm_greedy.py``) against the
plain reference (``benchmark/reference/trinity_ref.py``) at a toy width
on the CPU, float32, with a window of 8 and sequences of 3-5 windows so
that a ring wraps several times: the sequence form in blocks that do
and do not divide the sequence; the two forms of a layer; prefill +
steps through a ring beside a full cache against the reference's full
forward pass, for prefixes shorter than, equal to and longer than the
window in one batch and a stream that crosses it while decoding; the
eight shares of a layer's experts add up to the uncut layer; LFM2's
attention with the new fields at their defaults is what it was."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import trinity_ref
from deepspeech_tpu.config import apply_overrides, get_config
from deepspeech_tpu.decode.lm_greedy import LMGreedy
from deepspeech_tpu.models import lfm2
from deepspeech_tpu.models.lfm2 import (Attention, SparseExperts,
                                        create_lfm2_model, ring_positions)

U = 10           # max_label_len
V = 48
W = 8            # the window
FRAMES = 48      # 24 prefix positions of 2 frames: three windows
S = 40           # cache rows of the global layer: five windows


def toy(**kw):
    """The preset at toy sizes: the dense sliding layer and one period
    (sliding x 3, global), 16 experts top-2 of which 8 are held."""
    model = dict(lfm_hidden=32, lfm_heads=4, lfm_kv_heads=2,
                 lfm_head_dim=16, lfm_window=W, lfm_ffn_dim=48,
                 lfm_expert_dim=24, lfm_experts=16, lfm_top_k=2,
                 experts_held=8, expert_offset=4, vocab_size=V,
                 dtype="float32", lfm_seq_positions=S, moe_rows_bound=0.0,
                 moe_impl="xla", frame_stack=2)
    model.update(kw)
    c = get_config("trinity_large")
    return dataclasses.replace(
        c, model=dataclasses.replace(c.model, **model),
        data=dataclasses.replace(c.data, max_label_len=U, batch_size=4,
                                 bucket_frames=(FRAMES,)),
        decode=dataclasses.replace(c.decode, lm_prefill_rows=2,
                                   lm_watch_rows=4))


# Prefix positions 5 (< W: crosses it while decoding), 8 (= W), 21 and
# 24 (wrapped before the first token).
def batch(seed=0, lens=(10, 16, 42, 48), label_lens=(10, 3, 0, 9)):
    rng = np.random.default_rng(seed)
    lens = np.asarray(lens, np.int32)
    ll = np.asarray(label_lens, np.int32)
    feats = rng.standard_normal((len(lens), FRAMES, 161)
                                ).astype(np.float32)
    feats *= np.arange(FRAMES)[None, :, None] < lens[:, None, None]
    labels = rng.integers(1, V, (len(lens), U)).astype(np.int32)
    labels *= np.arange(U)[None, :] < ll[:, None]
    return feats, lens, labels, ll


def init(cfg, b, seed=1):
    """The modules' own initialisers (seeded norm gains, the selection
    bias), matrices of size 1 after every product: the preset's std
    0.02 at a width of 32 would leave softmax and sigmoid near their
    middles, and a fault in either would read as rounding."""
    v = create_lfm2_model(cfg.model, U).init(
        jax.random.PRNGKey(seed), *b, method="loss")
    params = jax.tree.map(
        lambda x: x * (x.shape[-2] ** -0.5 / 0.02) if x.ndim >= 2 else x,
        v["params"])
    return params, v["buffers"]


def forced(labels, ll):
    out = np.full((labels.shape[0], U + 1), -1, np.int32)
    out[:, 0] = 0
    out[:, 1:] = np.where(np.arange(U)[None, :] < ll[:, None], labels, -1)
    return out


def attention_params(cfg, kind, seed=2):
    x = jnp.zeros((1, 4, cfg.model.lfm_hidden))
    p = Attention(cfg.model, kind).init(jax.random.PRNGKey(seed), x)
    return jax.tree.map(
        lambda x: x * (x.shape[-2] ** -0.5 / 0.02) if x.ndim >= 2 else x,
        p["params"])


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
@pytest.mark.parametrize("block", [5, 8, 11, 64])
def test_sequence_form_equals_the_references_masks(kind, block):
    """Query blocks against the keys they can reach (blocks of 5 and 11
    do not divide 33 positions, 8 is the window, 64 holds them all)
    against the reference's masks by index arithmetic over ALL keys."""
    cfg = toy()
    params = attention_params(cfg, kind)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 33, 32))
    got, (k, v) = Attention(cfg.model, kind, block).apply(
        {"params": params}, x)
    want, rk, rv, _ = trinity_ref.attention(
        cfg.model, kind, params, x, (), q_block=7)
    assert trinity_ref.rms_rel(got, want) < 2e-5
    assert trinity_ref.rms_rel(k, rk) < 2e-5
    assert trinity_ref.rms_rel(v, rv) < 2e-5
    # a layer without a window sees further than one with
    other = Attention(cfg.model, "full_attention" if "sliding" in kind
                      else "sliding_attention", block).apply(
                          {"params": params}, x)[0]
    assert trinity_ref.rms_rel(other[:, W:], want[:, W:]) > 1e-2


@pytest.mark.parametrize("kind, rows, kernel", [
    ("sliding_attention", W, False), ("sliding_attention", S, False),
    ("full_attention", S, False),
    # both forms through their kernels (interpreted), ``gqa_attn_fwd``
    # past one block and ``gqa_attn_decode``, as the chip runs them:
    # heads of 128, a TPU assumed; a ring, a cache longer than the
    # window (which then cuts a wrapped cache's reach), and one that
    # sees all
    ("sliding_attention", W, True), ("sliding_attention", 20, True),
    ("full_attention", S, True)])
def test_the_two_forms_agree_past_the_window(kind, rows, kernel,
                                             monkeypatch):
    """The decode form against a ring (and against a cache that never
    wraps) equals the sequence form at positions before, at and past
    the window."""
    from jax.experimental.pallas import tpu as pltpu

    cfg = toy(lfm_head_dim=128) if kernel else toy()
    params = attention_params(cfg, kind)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 33, 32))
    at = np.array([0, 3, W - 1, W, W + 1, 2 * W, 3 * W + 5, 32])

    def forms(x):
        return lfm2.both_forms(cfg.model, kind, params, x, at, rows,
                               block=8)

    if kernel:
        monkeypatch.setenv("DS2N_ASSUME_TPU", "1")
        traced = str(jax.make_jaxpr(forms)(x))
        assert "name=gqa_attn_fwd" in traced
        assert "name=gqa_attn_decode" in traced
        with pltpu.force_tpu_interpret_mode():
            dec, seq = forms(x)
    else:
        dec, seq = forms(x)
    assert trinity_ref.rms_rel(dec, seq) < 2e-5


def test_ring_positions():
    """Slot s of a ring of 8 holds the newest position of its class."""
    held = np.asarray(ring_positions(jnp.asarray([2, 7, 8, 21]), 8))
    np.testing.assert_array_equal(held[0], [0, 1, 2, -5, -4, -3, -2, -1])
    np.testing.assert_array_equal(held[1], np.arange(8))
    np.testing.assert_array_equal(held[2], [8, 1, 2, 3, 4, 5, 6, 7])
    np.testing.assert_array_equal(
        held[3], [16, 17, 18, 19, 20, 21, 14, 15])


def test_decode_form_refuses_several_positions():
    cfg = toy()
    params = attention_params(cfg, "full_attention")
    with pytest.raises(NotImplementedError, match="latent attention's"):
        Attention(cfg.model, "full_attention").apply(
            {"params": params}, jnp.zeros((1, 2, 32)),
            jnp.zeros((1, 2), jnp.int32), (jnp.zeros((1, 8, 2, 16)),) * 2,
            jnp.ones((1, 2), bool))


def served(cfg, params, buffers, b):
    """One forced call of the engine."""
    feats, lens, labels, ll = b
    engine = LMGreedy(cfg, params, buffers)
    out = engine.transcribe(feats, lens, max_tokens=ll + 1,
                            forced=forced(labels, ll),
                            watch=np.arange(len(lens)))
    return engine, out


@pytest.mark.parametrize("offset, impl", [(4, "xla"), (0, "xla"),
                                          (8, "pallas")])
def test_prefill_then_decode_through_ring_and_full_cache(offset, impl):
    """The served path with forced tokens: what decode step j emits
    after prefill and j steps through four rings and a full cache is
    the reference's logit at that position of its full forward pass;
    every ring holds the reference's keys and values of the last W
    positions by slot and the full cache all of them; the gated heads'
    outputs, the router's scores and the counters are the
    reference's."""
    cfg = toy(expert_offset=offset, moe_impl=impl)
    b = batch()
    params, buffers = init(cfg, b)
    engine, out = served(cfg, params, buffers, b)
    last = engine.last_call
    want = trinity_ref.forward(cfg.model, params, buffers, *b, S,
                               q_block=16)
    seen = last["decode_watch"]
    assert trinity_ref.rms_rel(seen["logits"], want["logits"],
                               want["steps"]) < 2e-5
    a_lens = -(-b[1] // 2)
    end = a_lens + b[3]            # each stream's last written position
    assert [[c.shape for c in pair] for pair in last["cache"]] == \
        [[(4, W, 2, 16)] * 2] * 4 + [[(4, S, 2, 16)] * 2]
    for i, pair in enumerate(last["cache"]):
        view, held = trinity_ref.cache_view(
            want["k"][i], want["v"][i], end, pair[0].shape[1])
        assert trinity_ref.rms_rel(np.concatenate(pair, 2), view,
                                   held) < 2e-5, i
    # the steps' gated outputs at the text positions, the prefill's at
    # the prefix positions (rows 0-1: the first sub-batch)
    for i, ref in enumerate(want["gated"]):
        at_text = np.take_along_axis(np.asarray(ref),
                                     want["at"][..., None], 1)
        assert trinity_ref.rms_rel(seen[f"gated{i}"], at_text,
                                   want["steps"]) < 2e-5
        pre = last["prefill_watch"][f"gated{i}"]
        prefix = np.arange(24)[None, :] < a_lens[:2, None]
        assert trinity_ref.rms_rel(pre, np.asarray(ref)[:2, :24],
                                   prefix) < 2e-5
    at_text = np.take_along_axis(np.asarray(want["scores"]),
                                 want["at"][..., None], 1)
    assert trinity_ref.rms_rel(seen["scores"], at_text,
                               want["steps"]) < 2e-5
    stats = out["stats"]
    np.testing.assert_array_equal(out["tokens"], b[3] + 1)
    assert stats["decode_steps"] == 11 and stats["dropped_pairs"] == 0
    pairs = np.asarray(stats["prefill"]["expert_pairs"]) \
        + np.asarray(stats["decode"]["expert_pairs"])
    np.testing.assert_array_equal(pairs, np.asarray(want["pairs"]))
    # rows attended per kind are what the lengths imply
    reach = [a + j + 1 for a, u in zip(a_lens, b[3])
             for j in range(u + 1)]
    assert stats["rows_attended_window"] == 4 * sum(
        min(r, W) for r in reach)
    assert stats["rows_attended_global"] == sum(reach)
    assert stats["cache_rows_read"] == stats["rows_attended_window"] \
        + stats["rows_attended_global"]
    # streams 0 (5 + 11 positions), 2 and 3 passed the window; stream 1
    # (8 + 4) did too
    assert stats["ring_wraps"] == 4
    groups = stats["empty_groups"]
    assert groups["groups"] == 8 and groups["decode_calls"] == 11 * 4
    assert groups["decode"] == 11 * 4 * 8 - stats["experts_hit"]


@pytest.mark.parametrize("kernel", [False, True], ids=["plain", "kernel"])
def test_the_call_counts_the_rows_it_fetches(kernel, monkeypatch):
    """Beside the rows attended, the rows moved to attend to them: with
    the plain decode form every row of every stream's cache at every
    step, a finished stream's among them; with ``gqa_attn_decode``
    (heads of 128, a TPU assumed, interpreted) the rows of the row
    tiles the kernel visits for the live streams, which nothing but
    tile rounding separates from the rows attended. The call is the
    same function either way."""
    from jax.experimental.pallas import tpu as pltpu

    from deepspeech_tpu.ops import attn_pallas

    cfg = toy(lfm_head_dim=128)
    b = batch()
    params, buffers = init(cfg, b)
    _, plain = served(cfg, params, buffers, b)
    out = plain
    if kernel:
        monkeypatch.setenv("DS2N_ASSUME_TPU", "1")
        monkeypatch.setattr(attn_pallas, "ROW_TILE", 4)
        with pltpu.force_tpu_interpret_mode():
            engine, out = served(cfg, params, buffers, b)
        assert "name=gqa_attn_decode" in str(jax.make_jaxpr(
            lambda *a: engine._decode(*a))(
                engine.params, engine.buffers, engine.cache_for(4, FRAMES),
                *(jnp.zeros(4, jnp.int32),) * 2,
                jnp.zeros((4, U + 1), jnp.int32), jnp.arange(4),
                jnp.asarray(False)))
        np.testing.assert_array_equal(out["ids"], plain["ids"])
    stats = out["stats"]
    for k in ("rows_attended_window", "rows_attended_global",
              "cache_rows_read", "decode_steps"):
        assert stats[k] == plain["stats"][k]
    a_lens = -(-b[1] // 2)
    live = [a + j for a, u in zip(a_lens, b[3]) for j in range(u + 1)]
    if not kernel:
        assert stats["rows_fetched_window"] == 4 * 11 * 4 * W
        assert stats["rows_fetched_global"] == 11 * 4 * S
        return
    # a ring not yet wrapped and the full cache: the tiles of 4 slots
    # up to the position's; a wrapped ring: all of it
    assert stats["rows_fetched_window"] == 4 * sum(
        W if pos >= W else (pos // 4 + 1) * 4 for pos in live)
    assert stats["rows_fetched_global"] == sum(
        (pos // 4 + 1) * 4 for pos in live)
    assert stats["rows_attended_global"] <= stats["rows_fetched_global"] \
        < stats["rows_attended_global"] + 4 * len(live)


def test_a_short_cache_makes_rings_that_never_wrap():
    """Where the cache rows are fewer than the window a sliding layer's
    cache has that many rows, and the call is the same function."""
    cfg = toy(lfm_window=64)
    b = batch()
    params, buffers = init(cfg, b)
    engine, _ = served(cfg, params, buffers, b)
    assert [c.shape[1] for pair in engine.last_call["cache"]
            for c in pair] == [S] * 10
    want = trinity_ref.forward(cfg.model, params, buffers, *b, S)
    assert trinity_ref.rms_rel(
        engine.last_call["decode_watch"]["logits"], want["logits"],
        want["steps"]) < 2e-5


def test_cache_gauges_per_kind():
    from deepspeech_tpu import obs

    cfg = toy()
    b = batch()
    params, buffers = init(cfg, b)
    served(cfg, params, buffers, b)
    gauges = obs.registry().snapshot()["gauges"]
    row = 2 * 2 * 16 * 4                       # k + v, 2 heads of 16, f32
    assert gauges["lm_cache_bytes_window"] == 4 * 4 * W * row
    assert gauges["lm_cache_bytes_global"] == 4 * S * row
    assert gauges["lm_cache_bytes"] == gauges["lm_cache_bytes_window"] \
        + gauges["lm_cache_bytes_global"]


def test_the_shares_add_up_to_the_uncut_layer():
    """Guide section 4: over the 8 shares of a layer's 16 experts (2
    each), the partial results with the shared expert counted once add
    up to what the uncut reference gives for the whole layer."""
    whole = toy(experts_held=16, expert_offset=0).model
    b = batch()
    params, buffers = init(dataclasses.replace(toy(), model=whole), b)
    p = params["layer2"]["moe"]
    bias = buffers["layer2"]["moe"]["expert_bias"]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 13, 32))
    valid = jnp.ones((2, 13), bool)
    want, *_ = trinity_ref.experts(whole, p, bias, x, valid, ())
    shared = trinity_ref.swiglu(*(p["shared"][k]["kernel"]
                                  for k in ("w1", "w3", "w2")), x)
    total = jnp.zeros_like(x)
    for c in range(8):
        share = dataclasses.replace(whole, experts_held=2,
                                    expert_offset=2 * c)
        held = dict(p, w13=p["w13"][2 * c:2 * c + 2],
                    w2=p["w2"][2 * c:2 * c + 2])
        out, counters = SparseExperts(share).apply(
            {"params": held, "buffers": {"expert_bias": bias}}, x, valid)
        assert int(jnp.sum(counters["expert_pairs"])
                   + counters["pairs_elsewhere"]) == 2 * 13 * 2
        total = total + (out - shared)
    assert trinity_ref.rms_rel(total + shared, want) < 2e-5


def test_lfm2s_attention_is_what_it_was():
    """With the new fields at their defaults the layer is the parent's
    ``Attention`` (copied here as it stood), outputs and gradients bit
    for bit."""
    cfg = get_config("lfm2_24b_a2b").model
    cfg = dataclasses.replace(cfg, lfm_hidden=64, lfm_heads=4,
                              lfm_kv_heads=2, dtype="float32")

    def parent(p, h):
        b, s, d = h.shape
        nh, nkv = cfg.lfm_heads, cfg.lfm_kv_heads
        hd, rep = d // nh, nh // nkv

        def lin(name, x):
            return jnp.dot(x, p[name]["kernel"].astype(x.dtype))

        def norm(name, x):
            x32 = x.astype(jnp.float32)
            y = x32 * jax.lax.rsqrt(jnp.mean(
                x32 * x32, axis=-1, keepdims=True) + cfg.lfm_norm_eps)
            return (y * p[name]["scale"]).astype(x.dtype)

        q = lin("q", h).reshape(b, s, nh, hd)
        k = lin("k", h).reshape(b, s, nkv, hd)
        v = lin("v", h).reshape(b, s, nkv, hd)
        q = lfm2.rotary(norm("q_norm", q), cfg.lfm_rope_theta)
        k = lfm2.rotary(norm("k_norm", k), cfg.lfm_rope_theta)
        q = q.reshape(b, s, nkv, rep, hd)
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", q, k,
                            preferred_element_type=jnp.float32)
        scores = scores * (hd ** -0.5)
        causal = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(causal, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(h.dtype)
        out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v)
        return lin("o", out.reshape(b, s, d))

    h = jax.random.normal(jax.random.PRNGKey(6), (2, 24, 64))
    layer = Attention(cfg)
    params = layer.init(jax.random.PRNGKey(7), h)["params"]
    assert sorted(params) == ["k", "k_norm", "o", "q", "q_norm", "v"]

    def ours(p, h):
        return layer.apply({"params": p}, h)[0]

    np.testing.assert_array_equal(jax.jit(ours)(params, h),
                                  jax.jit(parent)(params, h))
    loss = lambda f: (lambda p, h: jnp.sum(jnp.tanh(f(p, h))))  # noqa
    got = jax.jit(jax.grad(loss(ours), (0, 1)))(params, h)
    want = jax.jit(jax.grad(loss(parent), (0, 1)))(params, h)
    jax.tree.map(np.testing.assert_array_equal, got, want)


def test_training_path_equals_reference():
    """``LFM2ASR.hidden`` with this family's block (one block of
    queries at these sizes): the logits at the text positions."""
    cfg = toy()
    b = batch()
    params, buffers = init(cfg, b)
    model = create_lfm2_model(cfg.model, U)
    h, head, layout, _ = jax.jit(lambda p: model.apply(
        {"params": p, "buffers": buffers}, *b, method="hidden"))(params)
    want = trinity_ref.forward(cfg.model, params, buffers, *b, S)
    np.testing.assert_array_equal(layout["valid"], want["valid"])
    got = np.take_along_axis(np.asarray(h), want["at"][..., None], 1) \
        @ np.asarray(head).T
    assert trinity_ref.rms_rel(got, want["logits"], want["steps"]) < 2e-5


def test_a_preset_without_every_cache_says_what_is_missing():
    from deepspeech_tpu.data import CharTokenizer
    from deepspeech_tpu.infer import Inferencer

    lfm = apply_overrides(get_config("lfm2_24b_a2b"),
                          {"decode.mode": "lm_greedy"})
    with pytest.raises(NotImplementedError,
                       match="lacks a 2-position convolution state "):
        Inferencer(lfm, CharTokenizer.synthetic_zh(V - 1), {}, {})


def test_inferencer_transcribes_with_the_preset():
    """``Inferencer.decode_batch`` with ``decode.mode="lm_greedy"`` on
    seeded variables of the toy preset."""
    from deepspeech_tpu.data import CharTokenizer
    from deepspeech_tpu.infer import Inferencer
    from deepspeech_tpu.models.lfm2 import seeded_variables

    cfg = toy()
    assert cfg.decode.mode == "lm_greedy"
    params, buffers = seeded_variables(cfg, 3)
    gains = params["layer1"]["op_post_norm"]["scale"]
    assert 0.02 < float(jnp.std(gains)) < 0.3     # seeded, not ones
    inf = Inferencer(cfg, CharTokenizer.synthetic_zh(V - 1), params,
                     buffers)
    feats, lens, _, ll = batch()
    texts = inf.decode_batch({"features": feats, "feat_lens": lens,
                              "max_tokens": ll + 1})
    assert len(texts) == 4 and all(isinstance(t, str) for t in texts)
