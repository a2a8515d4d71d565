"""The Xing4.0 decoder-only recogniser as it is served
(``models/lfm2.py`` with four residual streams and a draft module,
``models/axk1.py``'s decode form over two positions,
``decode/lm_greedy.py``'s self-drafting loop) against the plain
reference (``benchmark/reference/xing4_ref.py``) at a toy width on the
CPU: prefill + steps of one and of two positions against the full
forward pass; the draft module through its cache; and DRAFTING NEVER
CHANGES THE TRANSCRIPT, at a vocabulary small enough that a good share
of the drafts is accepted and both branches of a step run."""

import dataclasses

import jax
import numpy as np
import pytest

from benchmark.reference import xing4_ref
from deepspeech_tpu.config import get_config
from deepspeech_tpu.decode.lm_greedy import LMGreedy
from deepspeech_tpu.models.axk1 import both_forms
from deepspeech_tpu.models.lfm2 import create_lfm2_model, seeded_variables

U = 6            # max_label_len
V = 50
S = 16           # cache rows = positions of the packed sequence


def toy(**kw):
    """The preset at toy sizes: 3 layers (one dense) of 4 streams, 16
    experts all held, top-4 with a selection bias, one draft module."""
    data = {"max_label_len": kw.pop("max_label_len", U), "batch_size": 4}
    model = dict(lfm_hidden=64, lfm_heads=4, lfm_kv_heads=4,
                 lfm_ffn_dim=96, lfm_expert_dim=32, lfm_experts=16,
                 lfm_top_k=4, experts_held=16, vocab_size=V,
                 dtype="float32", lfm_seq_positions=S, moe_impl="xla",
                 lfm_layer_types=("latent_attention",) * 3,
                 mla_q_rank=24, mla_kv_rank=16, mla_nope_dim=8,
                 mla_rope_dim=4, mla_v_dim=8)
    model.update(kw)
    c = get_config("xing4_29b_a4b")
    return dataclasses.replace(
        c, model=dataclasses.replace(c.model, **model),
        data=dataclasses.replace(c.data, **data),
        decode=dataclasses.replace(c.decode, lm_prefill_rows=2))


def batch(seed=0, lens=(40, 33, 17, 25), label_lens=(6, 3, 0, 5), u=U,
          v=V):
    rng = np.random.default_rng(seed)
    lens = np.asarray(lens, np.int32)
    ll = np.asarray(label_lens, np.int32)
    rows, frames = len(lens), 40
    feats = rng.standard_normal((rows, frames, 161)).astype(np.float32)
    feats *= np.arange(frames)[None, :, None] < lens[:, None, None]
    labels = rng.integers(1, v, (rows, u)).astype(np.int32)
    labels *= np.arange(u)[None, :] < ll[:, None]
    return feats, lens, labels, ll


def weights(cfg, seed=1):
    """Seeded as the cell seeds them, then every matrix but the
    hyper-connections' to size 1 after its product (std 0.02 at a
    width of 64 would leave softmax and sigmoid near their middles)."""
    params, buffers = seeded_variables(cfg, seed, dtype="float32")
    n = cfg.model.hc_streams
    return jax.tree.map(
        lambda x: x * (x.shape[-2] ** -0.5 / 0.02)
        if x.ndim >= 2 and x.shape[-1] != n * (n + 2) else x,
        params), buffers


def forced(labels, ll):
    u = labels.shape[1]
    out = np.full((labels.shape[0], u + 1), -1, np.int32)
    out[:, 0] = 0
    out[:, 1:] = np.where(np.arange(u)[None, :] < ll[:, None], labels, -1)
    return out


def served(cfg, params, buffers, b, **kw):
    """One forced call of the engine: its result, every watched
    output and the cache."""
    feats, lens, labels, ll = b
    engine = LMGreedy(cfg, params, buffers)
    out = engine.transcribe(feats, lens, max_tokens=ll + 1,
                            forced=forced(labels, ll),
                            watch=np.arange(len(lens)), **kw)
    last = engine.last_call
    return (out, jax.device_get(last["decode_watch"]),
            [np.asarray(c) for c in last["cache"]], last)


def packed(want, before, after, a_lens, steps):
    """``[B, S, ...]`` as the reference lays a sequence out: the prefix
    positions from the prefill program's watch, each stream's tokens
    from the decode loop's."""
    before, after = np.asarray(before), np.asarray(after)
    out = np.zeros(want.shape, before.dtype)
    out[:, :before.shape[1]] = before
    for r, (a, n) in enumerate(zip(a_lens, steps)):
        out[r, a:a + n] = after[r, :n]
    return out


@pytest.fixture(scope="module")
def case():
    cfg = toy()
    params, buffers = weights(cfg)
    return cfg, params, buffers, batch()


@pytest.mark.parametrize("draft_layers", [0, 1])
def test_prefill_then_steps_equal_full_forward(case, draft_layers):
    """Forced tokens through the cache, ONE position a step (no module:
    today's loop, over four streams) and TWO (a forced input is an
    accepted draft): logits, every layer's cache rows, the last expert
    layer's router scores, the last layer's mixing coefficients and
    the pairs on every expert are the reference's."""
    cfg, params, buffers, b = case
    cfg = dataclasses.replace(cfg, model=dataclasses.replace(
        cfg.model, lm_draft_layers=draft_layers))
    out, dec, cache, last = served(cfg, params, buffers, b)
    want = xing4_ref.forward(cfg.model, params, buffers, *b, S)
    assert xing4_ref.rms_rel(dec["logits"], want["logits"],
                             want["steps"]) < 2e-5
    assert len(cache) == 3 + draft_layers == len(want["rows"])
    for got, rows in zip(cache[:3], want["rows"]):
        assert xing4_ref.rms_rel(got, rows, want["valid"]) < 2e-5
    stats = out["stats"]
    np.testing.assert_array_equal(out["tokens"], b[3] + 1)
    # 7 tokens at most: 7 steps of one position, 4 of two
    assert stats["decode_steps"] == (4 if draft_layers else 7)
    pairs = np.asarray(stats["prefill"]["expert_pairs"]) \
        + np.asarray(stats["decode"]["expert_pairs"])
    np.testing.assert_array_equal(pairs, np.asarray(want["pairs"]))
    a_lens = -(-b[1] // cfg.model.frame_stack)
    pre = jax.device_get(last["prefill_watch"])
    # prefill watches its first sub-batch (2 rows); the plain loop
    # gives out no mixing coefficients
    mixes = ("h_pre", "h_post", "h_res") if draft_layers else ()
    for key in ("scores",) + mixes:
        w = np.asarray(want[key])[:2]
        got = packed(w, pre[key], dec[key][:2], a_lens[:2], b[3][:2] + 1)
        assert xing4_ref.rms_rel(got, w, want["valid"][:2]) < 2e-5, key
    if draft_layers:
        assert xing4_ref.rms_rel(dec["draft_logits"], want["draft_logits"],
                                 want["draft_steps"]) < 2e-5
        assert xing4_ref.rms_rel(cache[3], want["rows"][3],
                                 want["follows"]) < 2e-5
        # every forced input behind a token was an accepted draft: a
        # stream's tokens go two a step
        assert stats["draft_accepted"] == stats["draft_positions"] \
            == int(np.sum((b[3] + 1) // 2))
        assert stats["verify_positions"] == int(np.sum(b[3] + 1))
        assert stats["drafts"] == stats["decode_steps"] * 4 \
            - stats["idle_slot_steps"]


def test_training_path_reads_the_streams(case):
    """``LFM2ASR.hidden`` over four streams: the logits at the text
    positions are the reference's."""
    cfg, params, buffers, b = case
    model = create_lfm2_model(cfg.model, U)
    h, head, layout, _ = jax.jit(lambda p: model.apply(
        {"params": p, "buffers": buffers}, *b, method="hidden"))(params)
    want = xing4_ref.forward(cfg.model, params, buffers, *b, S)
    np.testing.assert_array_equal(layout["valid"], want["valid"])
    got = np.take_along_axis(np.asarray(h), want["at"][..., None], 1) \
        @ np.asarray(head).T
    assert xing4_ref.rms_rel(got, want["logits"], want["steps"]) < 2e-5


def test_two_positions_against_the_cache_equal_the_sequence_form(case):
    cfg, params, _, _ = case
    x = jax.random.normal(jax.random.PRNGKey(3), (2, S, 64))
    at = np.arange(2, S - 1, 3)
    dec, seq = both_forms(cfg.model, params["layer1"]["attn"], x, at, q=2)
    assert dec.shape == (2, 2 * len(at), 64)
    assert xing4_ref.rms_rel(dec, seq) < 2e-5
    one, seq1 = both_forms(cfg.model, params["layer1"]["attn"], x, at)
    assert xing4_ref.rms_rel(one, seq1) < 2e-5
    np.testing.assert_allclose(one, dec[:, 0::2], rtol=1e-4, atol=1e-6)


# -- drafting never changes the transcript --------------------------------

SMALL = 6        # ids: a fifth of the seeded drafts is accepted
LONG = 14


@pytest.fixture(scope="module")
def pair():
    """The same weights with and without the module, at 6 ids and up
    to 15 tokens a stream."""
    cfg = toy(vocab_size=SMALL, max_label_len=LONG, lfm_seq_positions=24)
    params, buffers = weights(cfg, seed=4)
    plain = dataclasses.replace(cfg, model=dataclasses.replace(
        cfg.model, lm_draft_layers=0))
    b = batch(seed=2, lens=(40, 33, 17, 25, 40, 9, 28, 36),
              label_lens=(0,) * 8, u=LONG, v=SMALL)
    engines = {}

    def run(drafting: bool, ignore_end=True, **kw):
        key = (drafting, ignore_end)
        if key not in engines:
            c = cfg if drafting else plain
            c = dataclasses.replace(c, decode=dataclasses.replace(
                c.decode, lm_ignore_end=ignore_end))
            engines[key] = LMGreedy(c, params, buffers)
        return engines[key].transcribe(b[0], b[1], **kw), engines[key]

    return run, b


def same(pair, **kw):
    run, _ = pair
    (with_, engine), (without, _) = run(True, **kw), run(False, **kw)
    np.testing.assert_array_equal(with_["ids"], without["ids"])
    np.testing.assert_array_equal(with_["tokens"], without["tokens"])
    return with_, without, engine


def test_drafting_never_changes_the_transcript(pair):
    """Free-running to the longest transcript: both branches of a step
    run (drafts accepted and rejected), fewer steps, the same ids."""
    with_, without, _ = same(pair)
    s = with_["stats"]
    assert 0 < s["draft_accepted"] < s["draft_positions"]
    print({k: v for k, v in s.items() if not isinstance(v, dict)})
    # streams that had a draft accepted finished in fewer steps
    assert s["decode_steps"] <= without["stats"]["decode_steps"] == LONG + 1
    assert s["idle_slot_steps"] > 0 == without["stats"]["idle_slot_steps"]
    assert int(np.sum(with_["tokens"])) == 8 * (LONG + 1)
    # a rejected draft's rows were written again (the end id is ignored,
    # so a stream whose draft was put to the test goes on)
    assert s["draft_accepted"] + s["rejected_rows_overwritten"] \
        == s["draft_positions"]
    assert s["verify_positions"] == s["draft_positions"] \
        + s["decode_steps"] * 8 - s["idle_slot_steps"]
    assert s["drafts"] == s["decode_steps"] * 8 - s["idle_slot_steps"]
    assert s["decode"]["valid_positions"] == 8 * (LONG + 1)
    assert s["decode"]["padded_positions"] \
        == 2 * 8 * s["decode_steps"] - 8 * (LONG + 1)


@pytest.mark.parametrize("limits", [
    (1, 2, 3, 4, 5, 6, 7, 8), (15, 1, 0, 9, 2, 15, 4, 11), (3,) * 8])
def test_max_tokens_reached_mid_pair(pair, limits):
    """Odd and even limits: a stream whose limit falls between the two
    tokens of an accepted pair emits the first alone."""
    limits = np.asarray(limits, np.int32)
    with_, _, _ = same(pair, max_tokens=limits)
    np.testing.assert_array_equal(with_["tokens"], limits)
    assert not np.any(with_["ids"][np.arange(LONG + 1)[None, :]
                                   >= limits[:, None]])


def test_the_end_id_is_honoured(pair):
    """With the end id in force (1 id in 6 is it) streams stop at
    different tokens, some on the second token of a pair."""
    with_, _, _ = same(pair, ignore_end=False)
    assert len(set(with_["tokens"].tolist())) > 2
    assert int(np.min(with_["tokens"])) < LONG + 1
    for row, n in zip(with_["ids"], with_["tokens"]):
        assert n == LONG + 1 or row[n - 1] == 0
        assert not np.any(row[:n - 1] == 0) and not np.any(row[n:])


def test_forced_tokens_are_accepted_drafts(pair):
    """Forced inputs for some tokens of some streams, the model's own
    for the rest; a forced end id does not end a stream."""
    run, b = pair
    rng = np.random.default_rng(5)
    f = rng.integers(0, SMALL, (8, LONG + 1)).astype(np.int32)
    f[rng.random(f.shape) < 0.5] = -1
    f[:, 0] = 0
    f[3] = -1
    for ignore_end in (True, False):
        with_, _, _ = same(pair, ignore_end=ignore_end, forced=f)
        s = with_["stats"]
        assert s["draft_accepted"] >= int(np.sum(
            f[:, 1:][np.arange(1, LONG + 1)[None, :]
                     < with_["tokens"][:, None]] >= 0)) // 2


def test_the_module_through_its_cache_equals_its_full_forward(pair):
    """Free-running with drafts: on the FINAL sequence (the ids the loop
    emitted as labels) the reference's module gives, at every token
    that has a next one, the logits the loop's module gave there, and
    its cache rows are the reference's."""
    run, b = pair
    out, engine = run(True, watch=np.arange(8))
    ids, n = out["ids"], out["tokens"]
    labels, ll = ids[:, :LONG], n - 1
    labels = labels * (np.arange(LONG)[None, :] < ll[:, None])
    m = engine.cfg.model
    want = xing4_ref.forward(m, engine.params, engine.buffers, b[0], b[1],
                             labels, ll, 24)
    dec = jax.device_get(engine.last_call["decode_watch"])
    assert xing4_ref.rms_rel(dec["logits"], want["logits"],
                             want["steps"]) < 2e-5
    assert xing4_ref.rms_rel(dec["draft_logits"], want["draft_logits"],
                             want["draft_steps"]) < 2e-5
    cache = [np.asarray(c) for c in engine.last_call["cache"]]
    for got, rows in zip(cache[:3], want["rows"]):
        assert xing4_ref.rms_rel(got, rows, want["valid"]) < 2e-5
    assert xing4_ref.rms_rel(cache[3], want["rows"][3],
                             want["follows"]) < 2e-5
    # the loop's drafts were the module's argmax one token back
    guess = np.argmax(np.asarray(want["draft_logits"]), -1)
    agree = sum(int(guess[r, j] == ids[r, j + 1])
                for r in range(8) for j in range(n[r] - 1))
    assert agree >= out["stats"]["draft_accepted"] > 0


def test_more_than_one_module_is_refused():
    cfg = toy(lm_draft_layers=2)
    with pytest.raises(NotImplementedError, match="one module"):
        LMGreedy(cfg, {}, {})
