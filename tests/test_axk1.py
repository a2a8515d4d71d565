"""The A.X-K1 decoder-only recogniser (``models/axk1.py``, the shell and
expert block of ``models/lfm2.py``, ``ops/moe.py``,
``decode/lm_greedy.py``) against the plain reference
(``benchmark/reference/axk1_ref.py``) at a toy width on the CPU: the
training path and the served path (prefill, then decode through the
cache) for two shares of 16 experts and both grouped-product builds;
latent attention's two forms agree; the shares and the shared expert
add up to the uncut layer; group-limited selection; right padding and
batch order change nothing valid; ``moe_gmm`` at the small row tile;
``Inferencer`` and ``Trainer.evaluate`` transcribe."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import axk1_ref
from deepspeech_tpu.config import apply_overrides, get_config
from deepspeech_tpu.decode.lm_greedy import LMGreedy
from deepspeech_tpu.models.axk1 import LatentAttention
from deepspeech_tpu.models.lfm2 import create_lfm2_model, seeded_variables
from deepspeech_tpu.ops import moe, moe_pallas

U = 6            # max_label_len
V = 50
S = 16           # cache rows = positions of the packed sequence


def toy(**kw):
    """The preset at toy sizes: 3 layers (one dense), 16 experts in 4
    groups of which 2 are kept, top-4, 8 experts held."""
    model = dict(lfm_hidden=64, lfm_heads=4, lfm_kv_heads=4,
                 lfm_ffn_dim=96, lfm_expert_dim=32, lfm_experts=16,
                 lfm_top_k=4, moe_groups=4, moe_groups_kept=2,
                 experts_held=8, expert_offset=0, vocab_size=V,
                 dtype="float32", lfm_seq_positions=S, moe_rows_bound=0.0,
                 moe_impl="xla", lfm_layer_types=("latent_attention",) * 3,
                 mla_q_rank=24, mla_kv_rank=16, mla_nope_dim=8,
                 mla_rope_dim=4, mla_v_dim=8)
    model.update(kw)
    c = get_config("ax_k1")
    return dataclasses.replace(
        c, model=dataclasses.replace(c.model, **model),
        data=dataclasses.replace(c.data, max_label_len=U, batch_size=4),
        decode=dataclasses.replace(c.decode, lm_prefill_rows=2))


def batch(seed=0, lens=(40, 33, 17, 25), label_lens=(6, 3, 0, 5)):
    rng = np.random.default_rng(seed)
    lens = np.asarray(lens, np.int32)
    ll = np.asarray(label_lens, np.int32)
    rows, frames = len(lens), 40
    feats = rng.standard_normal((rows, frames, 161)).astype(np.float32)
    feats *= np.arange(frames)[None, :, None] < lens[:, None, None]
    labels = rng.integers(1, V, (rows, U)).astype(np.int32)
    labels *= np.arange(U)[None, :] < ll[:, None]
    return feats, lens, labels, ll


def init(cfg, b, seed=1):
    """Weights of size 1 after every product (the preset's std 0.02 at
    a width of 64 would leave softmax and sigmoid near their middles,
    and a fault in either would read as rounding)."""
    v = create_lfm2_model(cfg.model, U).init(
        jax.random.PRNGKey(seed), *b, method="loss")
    return jax.tree.map(
        lambda x: x * (x.shape[-2] ** -0.5 / 0.02) if x.ndim >= 2 else x,
        v["params"])


def forced(labels, ll):
    out = np.full((labels.shape[0], U + 1), -1, np.int32)
    out[:, 0] = 0
    out[:, 1:] = np.where(np.arange(U)[None, :] < ll[:, None], labels, -1)
    return out


def served(cfg, params, b, **kw):
    """One forced call of the engine: every step's logits and the cache."""
    feats, lens, labels, ll = b
    engine = LMGreedy(cfg, params, {})
    out = engine.transcribe(feats, lens, max_tokens=ll + 1,
                            forced=forced(labels, ll),
                            watch=np.arange(len(lens)), **kw)
    last = engine.last_call
    return (out, np.asarray(last["decode_watch"]["logits"]),
            [np.asarray(c) for c in last["cache"]])


SHARES = [(0, "xla"), (8, "xla"), (8, "pallas")]


@pytest.mark.parametrize("offset, impl", SHARES)
def test_training_path_equals_reference(offset, impl):
    """``LFM2ASR.hidden`` with this family's block: the logits at the
    text positions for each share of 16 experts, and through the
    interpreted kernel."""
    cfg = toy(expert_offset=offset, moe_impl=impl)
    b = batch()
    params = init(cfg, b)
    model = create_lfm2_model(cfg.model, U)
    h, head, layout, _ = jax.jit(lambda p: model.apply(
        {"params": p}, *b, method="hidden"))(params)
    want = axk1_ref.forward(cfg.model, params, *b, S)
    np.testing.assert_array_equal(layout["valid"], want["valid"])
    at = want["at"]
    got = np.take_along_axis(np.asarray(h), at[..., None], 1) \
        @ np.asarray(head).T
    assert axk1_ref.rms_rel(got, want["logits"], want["steps"]) < 2e-5


@pytest.mark.parametrize("offset, impl", SHARES)
def test_prefill_then_decode_equals_full_forward(offset, impl):
    """The served path with forced tokens: what decode step j emits
    after prefill and j steps through the cache is the reference's
    logit at that position of its full forward pass; every layer's
    cache rows are the reference's; the call's counters are its
    counts."""
    cfg = toy(expert_offset=offset, moe_impl=impl)
    b = batch()
    params = init(cfg, b)
    out, logits, cache = served(cfg, params, b)
    want = axk1_ref.forward(cfg.model, params, *b, S)
    assert axk1_ref.rms_rel(logits, want["logits"], want["steps"]) < 2e-5
    for got, rows in zip(cache, want["rows"]):
        assert axk1_ref.rms_rel(got, rows, want["valid"]) < 2e-5
    stats = out["stats"]
    np.testing.assert_array_equal(out["tokens"], b[3] + 1)
    assert stats["decode_steps"] == 7 and stats["dropped_pairs"] == 0
    assert stats["idle_slot_steps"] == 7 * 4 - int(np.sum(b[3] + 1))
    pairs = [sum(p) + sum(d) for p, d in zip(
        stats["prefill"]["expert_pairs"], stats["decode"]["expert_pairs"])]
    np.testing.assert_array_equal(pairs, np.asarray(want["pairs_held"]))
    valid = int(want["valid"].sum())
    assert stats["prefill"]["valid_positions"] \
        + stats["decode"]["valid_positions"] == valid
    # never more groups than the rule keeps, a position
    assert stats["prefill"]["groups_used"] + stats["decode"]["groups_used"] \
        <= 2 * valid * cfg.model.moe_groups_kept


def test_free_running_decode_feeds_its_argmax_back():
    """Without forced tokens each step's input is the last step's
    argmax: forcing the ids a free call returned gives the same ids;
    a stream stops at the end id unless told to decode on."""
    cfg = toy()
    b = batch()
    params = init(cfg, b)
    engine = LMGreedy(cfg, params, {})
    free = engine.transcribe(b[0], b[1])
    ids, n = free["ids"], free["tokens"]
    again = LMGreedy(cfg, params, {}).transcribe(
        b[0], b[1], max_tokens=n,
        forced=forced(np.where(ids > 0, ids, 1)[:, :U], n - 1))
    for row, got, k in zip(ids, again["ids"], n):
        np.testing.assert_array_equal(row[:k], got[:k])
        assert not row[k:].any() and (k == U + 1 or row[k - 1] == 0)
    on = dataclasses.replace(cfg, decode=dataclasses.replace(
        cfg.decode, lm_ignore_end=True))
    assert (LMGreedy(on, params, {}).transcribe(b[0], b[1])["tokens"]
            == U + 1).all()


def test_decode_form_equals_prefill_form():
    """Latent attention's two forms on one input: each position's
    output by the absorbed form against the cache equals the expanded
    form's at that position."""
    cfg = toy()
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((2, S, 64)), jnp.float32)
    layer = LatentAttention(cfg.model)
    pos = jnp.broadcast_to(jnp.arange(S)[None, :], (2, S))
    p = layer.init(jax.random.PRNGKey(0), x, pos)["params"]
    p = jax.tree.map(
        lambda w: w * (w.shape[0] ** -0.5 / 0.02) if w.ndim == 2 else w, p)
    seq, rows = layer.apply({"params": p}, x, pos)
    for t in (0, 5, S - 1):
        # the cache as the steps before t left it: later rows unwritten
        cache = jnp.where(jnp.arange(S)[None, :, None] < t, rows, 7.0)
        one, new = layer.apply({"params": p}, x[:, t:t + 1],
                               pos[:, t:t + 1], cache)
        assert axk1_ref.rms_rel(one[:, 0], seq[:, t]) < 2e-5
        np.testing.assert_allclose(new[:, t], rows[:, t], rtol=1e-5,
                                   atol=1e-6)


def _layer_inputs(seed=3, n=96, d=64, e=16, f=32):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((1, n, d)).astype(np.float32)
    valid = np.ones((1, n), bool)
    valid[0, -7:] = False
    lin = lambda a, b: {"kernel": rng.standard_normal(  # noqa: E731
        (a, b)).astype(np.float32) * 0.1}
    p = {"router": rng.standard_normal((d, e)).astype(np.float32) * 0.3,
         "w13": rng.standard_normal((e, d, 2 * f)).astype(np.float32) * 0.1,
         "w2": rng.standard_normal((e, f, d)).astype(np.float32) * 0.1,
         "shared": {"w1": lin(d, f), "w3": lin(d, f), "w2": lin(f, d)}}
    return x, valid, p


@pytest.mark.parametrize("shares", [4, 16])
def test_the_shares_and_the_shared_expert_add_up_to_the_uncut_layer(shares):
    """The routed parts of all shares of one expert layer, plus the
    shared expert counted ONCE (every chip computes it alike), equal
    what the uncut reference layer gives."""
    x, valid, p = _layer_inputs()
    m = toy(experts_held=16).model
    want, _, _, _, _ = axk1_ref.experts(m, p, jnp.asarray(x),
                                        jnp.asarray(valid), ())
    shared = axk1_ref.swiglu(p["shared"]["w1"]["kernel"],
                             p["shared"]["w3"]["kernel"],
                             p["shared"]["w2"]["kernel"], jnp.asarray(x))
    routing = moe.route(x[0], p["router"], None, m.lfm_top_k, m.moe_groups,
                        m.moe_groups_kept, m.moe_routed_scale)
    held = 16 // shares
    total, pairs = shared[0], 0
    for i in range(shares):
        lo = i * held
        part, counters = moe.expert_layer(
            jnp.asarray(x[0]), jnp.asarray(valid[0]), routing,
            p["w13"][lo:lo + held], p["w2"][lo:lo + held], offset=lo,
            impl="xla")
        total = total + part
        pairs += int(jnp.sum(counters["expert_pairs"]))
    assert axk1_ref.rms_rel(total, want[0]) < 2e-5
    assert pairs == int(valid.sum()) * m.lfm_top_k


def test_group_selection_keeps_a_token_in_its_groups():
    """8 groups of 24, 4 kept, top-8 (the published rule): every token
    gets 8 distinct experts, all inside the 4 groups whose best scores
    are highest; the weights sum to the scaling factor; the program's
    choice is the reference's."""
    m = get_config("ax_k1").model
    rng = np.random.default_rng(0)
    x = rng.standard_normal((200, 32)).astype(np.float32)
    w = rng.standard_normal((32, 192)).astype(np.float32)
    r = moe.route(x, w, None, m.lfm_top_k, m.moe_groups, m.moe_groups_kept,
                  m.moe_routed_scale)
    experts, scores = np.asarray(r.experts), np.asarray(r.scores)
    assert all(len(set(row)) == 8 for row in experts)
    best = scores.reshape(200, 8, 24).max(-1)
    kept = np.argsort(-best, axis=1)[:, :4]
    assert all(set(row // 24) <= set(k) for row, k in zip(experts, kept))
    np.testing.assert_allclose(np.asarray(r.weights).sum(1), 2.5, rtol=1e-5)
    want = np.asarray(axk1_ref.select(m, jnp.asarray(scores), ()))
    np.testing.assert_array_equal(np.sort(experts, 1), np.sort(want, 1))
    # ... and it is not the plain top-8 of all 192
    plain = np.asarray(axk1_ref.select(m, jnp.asarray(scores),
                                       ("plain_top8",)))
    assert (np.sort(plain, 1) != np.sort(want, 1)).any()


def test_right_padding_and_batch_order_change_nothing_valid():
    cfg = toy()
    b = batch()
    params = init(cfg, b)
    _, logits, _ = served(cfg, params, b)
    feats, lens, labels, ll = b
    wider = np.pad(feats, [(0, 0), (0, 8), (0, 0)])
    wider[:, 40:] = 9.0   # garbage past every utterance's length
    wider *= (np.arange(48)[None, :, None] < lens[:, None, None]) \
        | (np.arange(48)[None, :, None] >= 40)
    _, padded, _ = served(cfg, params, (wider, lens, labels, ll))
    order = np.asarray([2, 0, 3, 1])
    _, moved, _ = served(cfg, params, tuple(x[order] for x in b))
    steps = np.arange(U + 1)[None, :] <= ll[:, None]
    assert axk1_ref.rms_rel(padded, logits, steps) < 2e-5
    assert axk1_ref.rms_rel(moved, logits[order], steps[order]) < 2e-5


@pytest.mark.parametrize("rows, groups, tile", [(256, 12, 128),
                                                (2048, 12, 256),
                                                (13568, 12, 512),
                                                (32256, 8, 512)])
def test_the_row_tile_follows_the_calls_rows(rows, groups, tile):
    """A decode step's few hundred rows over a dozen experts take tiles
    of 128; prefill's and training's thousands a group keep 512."""
    assert moe_pallas.row_tile(rows, groups) == tile
    m = moe_pallas.row_capacity(rows, groups)
    assert m >= rows and m % tile == 0 and m - rows < tile


def test_moe_gmm_at_the_small_row_tile_equals_ragged_dot():
    """The interpreted kernel on a decode step's shape: 256 static rows
    in tiles of 128, a dozen groups of a few rows each (one empty), rows
    past the groups zero."""
    rng = np.random.default_rng(0)
    sizes = jnp.asarray([11, 9, 0, 14, 10, 12, 8, 13, 11, 9, 10, 12],
                        jnp.int32)
    lhs = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((12, 256, 128)), jnp.float32)
    got = moe_pallas.gmm(lhs, rhs, sizes, jnp.float32, True)
    want = jax.lax.ragged_dot(lhs, rhs, sizes)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    assert not np.asarray(got)[int(sizes.sum()):].any()


def test_inferencer_transcribes_with_the_preset():
    """``Inferencer.decode_batch`` for ``decode.mode="lm_greedy"``:
    weights from the seed in the compute dtype, transcripts back; an
    lfm2 preset raises, naming what its layers lack."""
    from deepspeech_tpu import obs
    from deepspeech_tpu.data import CharTokenizer
    from deepspeech_tpu.infer import Inferencer

    cfg = toy(dtype="bfloat16")
    params, buffers = seeded_variables(cfg, 2 ** 31 + 7)
    assert {x.dtype for x in jax.tree.leaves(params)} == {
        jnp.dtype("bfloat16")}
    obs.registry().reset()
    inf = Inferencer(cfg, CharTokenizer.synthetic_zh(V - 1), params,
                     buffers)
    feats, lens, _, ll = batch()
    texts = inf.decode_batch({"features": feats, "feat_lens": lens,
                              "max_tokens": ll + 1})
    assert len(texts) == 4 and all(isinstance(t, str) for t in texts)
    assert all(len(t) <= n + 1 for t, n in zip(texts, ll))
    snap = obs.registry().snapshot()
    assert snap["counters"]["lm_decode_steps"] == 7
    assert snap["counters"]["moe_dropped_pairs"] == 0
    assert snap["gauges"]["lm_cache_bytes"] == 3 * 4 * S * 20 * 2

    lfm2 = apply_overrides(get_config("lfm2_24b_a2b"),
                           {"decode.mode": "lm_greedy"})
    with pytest.raises(NotImplementedError,
                       match="lacks a 2-position convolution state "):
        Inferencer(lfm2, CharTokenizer.synthetic_zh(V - 1), {}, {})


def test_trainer_evaluates_the_lm_objective_by_greedy_decoding():
    """``Trainer.evaluate`` for this family: greedy transcripts through
    the cache, scored against the references."""
    from deepspeech_tpu.data import CharTokenizer
    from deepspeech_tpu.parallel import make_mesh
    from deepspeech_tpu.train import Trainer, _SyntheticPipeline

    cfg = toy(moe_rows_bound=0.5)
    cfg = apply_overrides(cfg, {
        "data.batch_size": 4, "data.bucket_frames": (64,),
        "model.lfm_seq_positions": 0, "train.checkpoint_dir": "",
        "train.epochs": 1, "train.warmup_steps": 1})
    pipe = _SyntheticPipeline(cfg, 8, frames=64, label_len=4)
    trainer = Trainer(cfg, pipe, CharTokenizer.synthetic_zh(V - 1),
                      mesh=make_mesh((1, 1)), eval_pipeline=pipe)
    trainer.fit()
    summary = trainer.evaluate()
    assert summary["n_utts"] == 8 and 0.0 <= summary["wer"]
