"""The MiniCPM-SALA block (a grouped-query layer whose queries read a
SELECTION of their cache's blocks, beside linear-attention layers with
one constant decay a head; ``models/lfm2.py`` ``Attention`` kind
"sparse_attention", ``LinearAttention``; ``ops/attn_pallas.py``
``gqa_attn_select_decode`` / ``gqa_attn_select_fwd``;
``ops/ssd_pallas.py``; ``decode/lm_greedy.py``) against the plain
reference (``benchmark/reference/minicpm_sala_ref.py``) at the
configuration file's rehearsal widths on the CPU, float32: each new
layer's sequence form against its step form and both against the
reference; the chosen blocks against the reference's; prefill + forced
steps through ``LMGreedy`` against the reference's full forward pass
(logits, rows, pooled keys, state, gated outputs, counters); the
``dense_len`` switch on both sides of it; the kernels interpreted
against their oracles; controls that put one fault each into the
reference and must fail the limits."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import minicpm_sala_ref as ref
from deepspeech_tpu.config import get_config
from deepspeech_tpu.decode.lm_greedy import LMGreedy
from deepspeech_tpu.models import lfm2
from deepspeech_tpu.models.lfm2 import (LINEAR, SPARSE, Attention,
                                        LinearAttention, create_lfm2_model)
from deepspeech_tpu.ops import attn_pallas as ap
from deepspeech_tpu.ops import ssd_pallas as ssd

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
U = 30            # max_label_len
FRAMES = 768      # 96 prefix positions of 8 frames
S = 128           # cache rows: 16 blocks of 8
TOL = 3e-5        # float32 on the CPU: only the order of sums


def toy(**kw):
    """The preset at the configuration file's rehearsal widths: blocks
    of 8 rows, pooling windows of 4 every 2, the first block + a local
    window of 2 blocks + the 2 best of the rest, dense up to 64 rows."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "minicpm_sala.json")) as f:
        model = json.load(f)["rehearsal"]
    model = {k: tuple(v) if isinstance(v, list) else v
             for k, v in model.items()}
    model.update(lfm_seq_positions=S, **kw)
    c = get_config("minicpm_sala")
    return dataclasses.replace(
        c, model=dataclasses.replace(c.model, **model),
        data=dataclasses.replace(c.data, max_label_len=U, batch_size=4,
                                 bucket_frames=(FRAMES,)),
        decode=dataclasses.replace(c.decode, lm_prefill_rows=2,
                                   lm_watch_rows=4))


# Prefix positions 96 and 80 (past dense_len 64: prefill selects), 58
# (dense in prefill, its steps cross 64 rows) and 20 (dense throughout).
def batch(seed=0, lens=(768, 640, 464, 160), label_lens=(30, 12, 30, 9),
          v=64):
    rng = np.random.default_rng(seed)
    lens = np.asarray(lens, np.int32)
    ll = np.asarray(label_lens, np.int32)
    feats = rng.standard_normal((len(lens), FRAMES, 161)
                                ).astype(np.float32)
    feats *= np.arange(FRAMES)[None, :, None] < lens[:, None, None]
    labels = rng.integers(1, v, (len(lens), U)).astype(np.int32)
    labels *= np.arange(U)[None, :] < ll[:, None]
    return feats, lens, labels, ll


def init(cfg, b, seed=1):
    """The modules' own initialisers, matrices of size 1 after every
    product (the preset's std 0.02 at a width of 64 would leave every
    softmax flat and every selection a coin's toss)."""
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


def served(cfg, params, b):
    engine = LMGreedy(cfg, params, {})
    feats, lens, labels, ll = b
    out = engine.transcribe(feats, lens, max_tokens=ll + 1,
                            forced=forced(labels, ll),
                            watch=np.arange(len(lens)))
    return engine, out


# -- the selection ------------------------------------------------------------

def test_the_sizes_of_the_selection():
    m = get_config("minicpm_sala").model
    assert lfm2.selected_blocks(m) == 97
    assert lfm2.select_list_len(m) == 96         # dense_len / 64 - 32
    assert lfm2.pooled_rows(m, 19328) == 1208    # 1,207 whole windows
    # rows read: everything up to dense_len, then 97 blocks less what
    # the query's own block lacks
    pos = np.asarray([0, 8191, 8192, 15000, 19320])
    np.testing.assert_array_equal(
        lfm2.rows_selected(m, pos, np),
        [1, 8192, 96 * 64 + 1, 96 * 64 + 15000 % 64 + 1,
         96 * 64 + 19320 % 64 + 1])
    t = toy().model
    assert lfm2.selected_blocks(t) == 5 and lfm2.select_list_len(t) == 16


def test_a_query_reads_the_first_the_local_and_the_best_blocks():
    """Block scores by hand: block 0 and the two local blocks whatever
    they score, then the two best of the rest; all blocks in reach
    where they are no more than five, and where the sequence is
    dense."""
    m = toy().model
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 3, 2, 4, 16))
    pooled = jax.random.normal(jax.random.PRNGKey(1), (1, 46, 2, 16))
    t = jnp.asarray([[95, 39, 70]])
    dense = jnp.asarray([[False, False, True]])
    scores = lfm2.block_scores(m, q, pooled, t, dense, 12)
    sel = np.asarray(lfm2.select_mask(m, scores))
    assert sel.shape == (1, 2, 3, 12)
    for g in range(2):
        # t = 95: block 11 is its own; 10, 11 local; 0 first; 2 more
        assert sel[0, g, 0].sum() == 5
        assert sel[0, g, 0, [0, 10, 11]].all()
        rest = np.asarray(scores)[0, g, 0, 1:10]
        best = set(1 + np.argsort(rest)[-2:])
        assert set(np.nonzero(sel[0, g, 0, 1:10])[0] + 1) == best
        # t = 39: five blocks in reach, all kept
        assert sel[0, g, 1].tolist() == [True] * 5 + [False] * 7
        # dense: every block in reach
        assert sel[0, g, 2].tolist() == [True] * 9 + [False] * 3


def test_block_scores_are_the_references():
    m = toy().model
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 2, 4, 16))
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 96, 2, 16))
    t = jnp.asarray([[95, 64, 63, 40, 3], [90, 77, 71, 66, 65]])
    dense = jnp.zeros((2, 5), bool)
    got = lfm2.select_mask(m, lfm2.block_scores(
        m, q, lfm2.pool_keys(m, k), t, dense, 12))
    pooled, stride = ref.pooled_keys(m, k)
    want = ref.selection(m, q, pooled, stride, t, dense, 12)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_allclose(lfm2.pool_keys(m, k), pooled, atol=1e-6)


# -- each layer in its two forms ----------------------------------------------

def sparse_layer(m, s=96, seed=0, block=16):
    layer = Attention(m, SPARSE, block)
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, s, m.lfm_hidden))
    valid = jnp.ones((2, s), bool)
    params = layer.init(jax.random.PRNGKey(seed + 1), x, None, None,
                        valid)["params"]
    params = jax.tree.map(
        lambda p: p * (p.shape[-2] ** -0.5 / 0.02) if p.ndim >= 2 else p,
        params)
    return layer, params, x, valid


def step_cache(m, k, v, t, rows=S):
    """The cache a step at row ``t`` finds: rows (head-major, as the
    sequence form gives them) and whole windows before it."""
    kc, vc = (jnp.zeros((2, 2, rows, 16)).at[:, :, :t].set(c[:, :, :t])
              for c in (k, v))
    pooled = lfm2.pool_keys(m, k.swapaxes(1, 2)[:, :t]) \
        if t >= m.sparse_kernel else jnp.zeros((2, 0, 2, 16))
    pc = jnp.zeros((2, lfm2.pooled_rows(m, rows), 2, 16)
                   ).at[:, :pooled.shape[1]].set(pooled)
    return kc, vc, pc


@pytest.mark.parametrize("t", [2, 3, 39, 63, 64, 65, 70, 71, 95])
def test_sparse_layers_step_equals_its_sequence_form(t):
    """The decode form at row t (a sequence of t + 1 rows: dense up to
    64 of them, selected past them) against the sequence form over
    exactly those rows: the same output, the same chosen blocks, the
    new row and, where row t ends a window, its pooled key written."""
    m = toy().model
    layer, params, x, valid = sparse_layer(m)
    (seq, (k, v, pooled)), mid = layer.apply(
        {"params": params}, x[:, :t + 1], None, None, valid[:, :t + 1],
        mutable=["intermediates"])
    (dec, (kc, vc, pc)), step = layer.apply(
        {"params": params}, x[:, t:t + 1], jnp.full((2, 1), t),
        step_cache(m, k, v, t), jnp.ones((2, 1), bool),
        mutable=["intermediates"])
    np.testing.assert_allclose(dec[:, 0], seq[:, t], atol=TOL)
    chosen = np.asarray(mid["intermediates"]["selected"][0])[:, :, t]
    got = np.asarray(step["intermediates"]["selected"][0])
    np.testing.assert_array_equal(got[..., :chosen.shape[-1]], chosen)
    assert not got[..., chosen.shape[-1]:].any()
    np.testing.assert_allclose(kc[:, :, t], k[:, :, t], atol=1e-6)
    n = pooled.shape[1] if t + 1 >= m.sparse_kernel else 0
    np.testing.assert_allclose(pc[:, :n], pooled[:, :n], atol=1e-6)
    assert not np.any(np.asarray(pc[:, n:]))


def test_dense_len_switches_the_selection_on():
    """A sequence of 64 rows is dense (every query reads every row
    before it); one of 65 is not: its last query reads 5 of 9 blocks,
    and its output differs from the dense one's."""
    m = toy().model
    layer, params, x, valid = sparse_layer(m)

    def run(n, model=m):
        lay = Attention(model, SPARSE, 16)
        (out, _), mid = lay.apply({"params": params}, x[:, :n], None, None,
                                  valid[:, :n], mutable=["intermediates"])
        return out, np.asarray(mid["intermediates"]["selected"][0])

    out, sel = run(64)
    assert all(sel[0, 0, t].sum() == t // 8 + 1 for t in range(64))
    plain = dataclasses.replace(m, sparse_dense_len=10 ** 6)
    np.testing.assert_allclose(out, run(64, plain)[0], atol=1e-6)
    out, sel = run(65)
    assert sel[0, 0, 64].sum() == 5 and sel[0, 0, 63].sum() == 5
    assert sel[0, 0, 39].sum() == 5 and sel[0, 0, 30].sum() == 4
    assert ref.rms_rel(out[:, 64], run(65, plain)[0][:, 64]) > 1e-3
    # a stream's own length decides, not the batch's
    short = valid.at[1, 64:].set(False)
    (_, _), mid = layer.apply({"params": params}, x[:, :80], None, None,
                              short[:, :80], mutable=["intermediates"])
    sel = np.asarray(mid["intermediates"]["selected"][0])
    assert sel[0, 0, 63].sum() == 5 and sel[1, 0, 63].sum() == 8


def linear_layer(m, index, s=29, seed=0):
    layer = LinearAttention(m, index)
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, s, m.lfm_hidden))
    valid = jnp.arange(s)[None, :] < jnp.asarray([s, s - 6])[:, None]
    params = layer.init(jax.random.PRNGKey(seed + 1), x, valid)["params"]
    params = jax.tree.map(
        lambda p: p * (p.shape[-2] ** -0.5 / 0.02) if p.ndim >= 2 else p,
        params)
    return layer, params, x, valid


@pytest.mark.parametrize("index", [1, 31])
def test_linear_layers_steps_continue_its_sequence_form(index):
    """The sequence form over 29 positions (chunks of 8; the second
    stream 23 valid) gives the state the decode form reaches from the
    sequence form over the first 11 and steps over the rest, the same
    outputs, and the reference's positionwise recurrence; a stream that
    is not live keeps its state."""
    m = toy().model
    layer, params, x, valid = linear_layer(m, index)
    (want, (whole,)), mid = layer.apply({"params": params}, x, valid,
                                        mutable=["intermediates"])
    (_, (state,)), _ = layer.apply({"params": params}, x[:, :11],
                                   valid[:, :11], mutable=["intermediates"])
    outs = []
    for t in range(11, 29):
        (o, (state,)), _ = layer.apply(
            {"params": params}, x[:, t:t + 1], valid[:, t:t + 1],
            jnp.full((2, 1), t), (state,), mutable=["intermediates"])
        outs.append(o)
    got = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(got[0], want[0, 11:], atol=TOL)
    np.testing.assert_allclose(got[1, :12], want[1, 11:23], atol=TOL)
    np.testing.assert_allclose(state, whole, atol=TOL)
    at = jnp.asarray([[28], [22]])
    gated, kept = ref.linear_attention(m, params, x, index, at, ())
    np.testing.assert_allclose(whole, kept[:, 0], atol=TOL)
    sown = np.asarray(mid["intermediates"]["gated"][0])
    v = np.asarray(valid)
    np.testing.assert_allclose(sown[v], np.asarray(gated)[v], atol=TOL)


def test_the_decay_follows_the_published_layer_index():
    m = toy().model
    lam = np.exp(-lfm2.decay_slopes(m, 3))
    np.testing.assert_allclose(lam, ref.decays(m, 3), rtol=1e-6)
    # head 1 forgets fastest; the first layer faster than the last
    assert lam[0] < lam[-1] < 1
    assert np.all(np.exp(-lfm2.decay_slopes(m, 0)) < np.exp(
        -lfm2.decay_slopes(m, 31)))
    np.testing.assert_allclose(
        lfm2.decay_slopes(m, 31), 2.0 ** (-8 * np.arange(1, 5) / 4) * 1e-5,
        rtol=1e-4)


# -- the kernels, interpreted -------------------------------------------------

def select_inputs(b=4, rows=128, nkv=2, rep=4, hd=16, block=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (b, nkv, rep, hd)),
            jax.random.normal(ks[1], (b, nkv, rows, hd)),
            jax.random.normal(ks[2], (b, nkv, rows, hd)),
            jax.random.bernoulli(ks[3], 0.5, (b, nkv, rows // block)))


@pytest.mark.parametrize("per_step, length, window", [
    (2, 8, 16), (4, 16, 16), (8, 16, 32), (8, 16, 256)])
def test_select_decode_kernel_equals_its_oracle(per_step, length, window):
    """``gqa_attn_select_decode``, interpreted, against the plain form:
    ragged positions, the local window as one run of rows (at the
    cache's start, in its middle, hanging over its end, wider than the
    cache), a stream that is not live (first and among the live ones),
    lists shorter and longer than a grid step."""
    q, keys, values, sel = select_inputs()
    pos = jnp.asarray([127, 63, 37, 5])
    live = jnp.asarray([False, True, False, True])
    local = window // 8
    first = jnp.maximum(pos // 8 - local + 1, 0)
    block = jnp.arange(16)[None, None, :]
    sel = sel.at[:, :, 0].set(True) | (block >= first[:, None, None])
    sel = sel & (block <= (pos // 8)[:, None, None]) & live[:, None, None]
    idx, count = ap.select_list(sel, length, first, per_step)
    assert idx.shape == (4, 2, length)
    got = ap.gqa_select_decode(q, keys, values, idx, count, pos, first * 8,
                               live, 8, window, per_step, interpret=True)
    want = lfm2.cached_attend_selected(q, keys, values, sel, pos, 8)
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert not np.any(np.asarray(got)[~np.asarray(live)])


def test_a_list_entry_past_the_count_fetches_nothing():
    """Past the count an entry repeats the one ``per_step`` before it:
    the same operand's block index as the grid step before; blocks of
    the local window are not in the list."""
    sel = jnp.zeros((1, 1, 48), bool).at[0, 0, jnp.asarray(
        [0, 3, 4, 9, 17, 18, 30, 31, 33, 39, 40, 41, 47])].set(True)
    idx, count = ap.select_list(sel, 24, jnp.asarray([40]), 8)
    assert int(count[0, 0]) == 10
    idx = np.asarray(idx[0, 0])
    assert idx[:10].tolist() == [0, 3, 4, 9, 17, 18, 30, 31, 33, 39]
    np.testing.assert_array_equal(idx[10:16], idx[2:8])
    np.testing.assert_array_equal(idx[16:24], idx[8:16])


@pytest.mark.parametrize("s", [40, 64, 77])
def test_select_sequence_kernel_equals_its_oracle(s):
    """``gqa_attn_select_fwd``, interpreted at tiles of 16 queries and
    32 keys (4 blocks of 8), against the plain form: ragged last tiles,
    a different selection for every (query, key/value head)."""
    ks = jax.random.split(jax.random.PRNGKey(s), 4)
    q = jax.random.normal(ks[0], (2, s, 2, 3, 16))
    k = jax.random.normal(ks[1], (2, s, 2, 16))
    v = jax.random.normal(ks[2], (2, s, 2, 16))
    sel = jax.random.bernoulli(ks[3], 0.4, (2, 2, s, -(-s // 8)))
    sel = sel.at[..., 0].set(True)
    got = ap.gqa_select_attention(q, k, v, sel, 8, q_tile=16, k_tile=32,
                                  interpret=True)
    want = lfm2.selected_attend(q, k, v, sel, 0, 8)
    np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("live", [(1, 1, 1), (0, 1, 0), (0, 0, 0)])
def test_state_step_takes_several_single_head_groups_a_grid_step(live):
    """``ssd_state_step`` with as many groups as heads, all of them in
    one grid step (B and C with the groups along the lanes), no skip:
    the plain update's; idle streams keep their state."""
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    b, h, n, p = 3, 8, 16, 8
    args = (jax.random.normal(ks[0], (b, h, n, p)),
            jax.random.normal(ks[1], (b, h, p)), jnp.ones((b, h)),
            -jnp.exp(jax.random.normal(ks[2], (h,))),
            jax.random.normal(ks[3], (b, h, n)),
            jax.random.normal(ks[4], (b, h, n)), None)
    live = jnp.asarray(live, bool)
    want_y, want_state = ssd.step_oracle(*args, live)
    got_y, got_state = ssd.state_step(*args, live, interpret=True,
                                      group_block=8)
    np.testing.assert_allclose(got_y, want_y, atol=5e-6)
    np.testing.assert_allclose(got_state, want_state, atol=2e-6)
    idle = ~np.asarray(live)
    assert np.array_equal(np.asarray(got_state)[idle],
                          np.asarray(args[0])[idle])


def test_chunk_scan_without_a_skip_and_a_group_a_head():
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    b, s, h, n, p = 2, 21, 4, 16, 8
    x = jax.random.normal(ks[0], (b, s, h, p))
    bm, cm = (jax.random.normal(k, (b, s, h, n)) for k in ks[1:3])
    a = -jnp.asarray(lfm2.decay_slopes(toy().model, 2))
    valid = jnp.arange(s)[None, :] < jnp.asarray([21, 9])[:, None]
    args = (x, jnp.ones((b, s, h)), a, bm, cm, None, valid)
    want_y, want_state = ssd.scan_oracle(*args)
    got_y, got_state = ssd.chunk_scan(*args, chunk=8, interpret=True)
    v = np.asarray(valid)
    np.testing.assert_allclose(np.asarray(got_y)[v], np.asarray(want_y)[v],
                               rtol=1e-5, atol=5e-6)
    np.testing.assert_allclose(got_state, want_state, rtol=1e-5, atol=2e-6)


def test_the_layers_take_the_kernels_where_they_say(monkeypatch):
    """Heads of 128 on an assumed TPU: the two forms of both layers
    lower to the four named kernels and give what the oracles give."""
    from jax.experimental.pallas import tpu as pltpu

    m = toy(lfm_heads=4, lfm_head_dim=128, lin_heads=8,
            lin_head_dim=128).model
    layer, params, x, valid = sparse_layer(m, s=80, block=16)
    want, (k, v, pooled) = layer.apply({"params": params}, x, None, None,
                                       valid)
    cache = tuple(jnp.zeros((2, 2, S, 128)).at[:, :, :79].set(c[:, :, :79])
                  for c in (k, v)) + (jnp.zeros(
                      (2, lfm2.pooled_rows(m, S), 2, 128)).at[
                          :, :pooled.shape[1] - 1].set(pooled[:, :-1]),)
    new = (x[:, 79:], jnp.full((2, 1), 79), cache,
           jnp.asarray([[True], [False]]))
    want_step, _ = layer.apply({"params": params}, *new)
    lin, lparams, lx, lvalid = linear_layer(m, 2)
    lwant, (state,) = lin.apply({"params": lparams}, lx, lvalid)
    lnew = (lx[:, :1], jnp.asarray([[True], [False]]), jnp.full((2, 1), 29),
            (state,))
    lwant_step, (after,) = lin.apply({"params": lparams}, *lnew)
    monkeypatch.setenv("DS2N_ASSUME_TPU", "1")
    with pltpu.force_tpu_interpret_mode():
        assert "name=gqa_attn_select_fwd" in str(jax.make_jaxpr(
            lambda: layer.apply({"params": params}, x, None, None, valid))())
        assert "name=gqa_attn_select_decode" in str(jax.make_jaxpr(
            lambda: layer.apply({"params": params}, *new))())
        assert "name=ssd_chunk_scan" in str(jax.make_jaxpr(
            lambda: lin.apply({"params": lparams}, lx, lvalid))())
        assert "name=ssd_state_step" in str(jax.make_jaxpr(
            lambda: lin.apply({"params": lparams}, *lnew))())
        got, _ = layer.apply({"params": params}, x, None, None, valid)
        got_step, _ = layer.apply({"params": params}, *new)
        lgot, (gstate,) = lin.apply({"params": lparams}, lx, lvalid)
        lgot_step, (gafter,) = lin.apply({"params": lparams}, *lnew)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(got_step, want_step, atol=2e-5)
    assert not np.any(np.asarray(got_step[1]))
    lv = np.asarray(lvalid)
    np.testing.assert_allclose(np.asarray(lgot)[lv], np.asarray(lwant)[lv],
                               atol=2e-5)
    np.testing.assert_allclose(gstate, state, atol=2e-5)
    np.testing.assert_allclose(lgot_step, lwant_step, atol=2e-5)
    np.testing.assert_allclose(gafter, after, atol=2e-5)
    assert np.array_equal(gafter[1], state[1])


# -- the served path against the reference ------------------------------------

@pytest.fixture(scope="module")
def call():
    cfg = toy()
    b = batch()
    params = init(cfg, b)
    engine, out = served(cfg, params, b)
    want = jax.device_get(ref.forward(cfg.model, params, *b, S, (), 32))
    return cfg, b, params, engine, out, want


def system(call) -> dict:
    """What the comparison reads of the served call, in the
    reference's layout."""
    cfg, b, params, engine, out, want = call
    last = engine.last_call
    seen, pre = last["decode_watch"], last["prefill_watch"]
    a_lens = -(-b[1] // 8)
    keys, values, pooled = last["cache"][0]
    (state,) = last["cache"][3]
    return {"logits": seen["logits"], "k": np.swapaxes(keys, 1, 2),
            "v": np.swapaxes(values, 1, 2),
            "pooled": pooled, "state_last": state,
            "state_prefill": pre["state"], "seen": seen, "pre": pre,
            "a_lens": a_lens}


def errors(got: dict, want: dict, b) -> dict:
    a_lens, ll = got["a_lens"], b[3]
    last = a_lens + ll
    held = np.arange(S)[None, :] <= last[:, None]
    n = np.asarray(want["pooled"]).shape[1]
    whole = (np.arange(n) * 2 + 3)[None, :] <= last[:, None]
    at = np.clip(a_lens[:, None] + np.arange(U + 1)[None, :], 0, S - 1)
    prefix = np.arange(96)[None, :] < a_lens[:2, None]
    out = {"logits": ref.rms_rel(got["logits"], want["logits"],
                                 want["steps"]),
           "keys": ref.rms_rel(got["k"], want["k"], held),
           "values": ref.rms_rel(got["v"], want["v"], held),
           "pooled": ref.rms_rel(np.asarray(got["pooled"])[:, :n],
                                 want["pooled"], whole),
           "state_last": ref.rms_rel(got["state_last"], want["state_last"]),
           "state_prefill": ref.rms_rel(got["state_prefill"],
                                        np.asarray(want["state_prefill"])[:2])}
    for i, name in enumerate(("gated_sparse", "gated_linear")):
        text = np.take_along_axis(np.asarray(want[name]), at[..., None], 1)
        out[name] = max(
            ref.rms_rel(got["seen"][f"gated{i}"], text, want["steps"]),
            ref.rms_rel(got["pre"][f"gated{i}"],
                        np.asarray(want[name])[:2, :96], prefix))
    return out


def chosen(got: dict, want: dict, b):
    """The share of (query, key/value head) whose chosen blocks differ
    from the reference's: prefix queries of the first sub-batch, text
    queries of every stream."""
    a_lens = got["a_lens"]
    ref_sel = np.asarray(want["chosen"])                  # [B, kv, S, 16]
    pre = np.asarray(got["pre"]["selected"]).reshape(2, 96, 2, -1)
    pre = np.moveaxis(pre, 2, 1)                          # [2, kv, 96, 12]
    prefix = np.arange(96)[None, :] < a_lens[:2, None]
    differ = [ref.chosen_differ_share(pre, ref_sel[:2, :, :96], prefix)]
    dec = np.asarray(got["seen"]["selected"]).reshape(4, U + 1, 2, -1)
    dec = np.moveaxis(dec, 2, 1)                          # [4, kv, U+1, 16]
    at = np.clip(a_lens[:, None] + np.arange(U + 1)[None, :], 0, S - 1)
    text = np.take_along_axis(ref_sel, at[:, None, :, None], axis=2)
    differ.append(ref.chosen_differ_share(dec, text, want["steps"]))
    return max(differ)


def test_prefill_then_decode_through_the_cache_equals_the_reference(call):
    """The served path with forced tokens: what decode step j emits
    after prefill and j steps through rows, pooled keys and states is
    the reference's logit at that position of its full forward pass;
    the sparse layer's rows and pooled keys, the last linear layer's
    state after prefill and after the last step, both mixers' gated
    outputs and every query's chosen blocks are the reference's."""
    cfg, b, params, engine, out, want = call
    got = system(call)
    errs = errors(got, want, b)
    assert all(v < TOL for v in errs.values()), errs
    assert chosen(got, want, b) == 0.0
    keys, values, pooled = engine.last_call["cache"][0]
    assert keys.shape == (4, 2, S, 16) and pooled.shape == (4, 64, 2, 16)
    (state,) = engine.last_call["cache"][2]
    assert state.dtype == jnp.float32 and state.shape == (4, 4, 16, 16)


def test_the_calls_counters_are_what_the_lengths_imply(call):
    cfg, b, params, engine, out, want = call
    m = cfg.model
    stats = out["stats"]
    a_lens = -(-b[1] // 8)
    np.testing.assert_array_equal(out["tokens"], b[3] + 1)
    assert stats["decode_steps"] == 31
    steps = int(np.sum(b[3] + 1))
    assert stats["state_updates"] == 3 * steps
    assert stats["idle_slot_steps"] == 31 * 4 - steps
    pos = np.concatenate([a + np.arange(u + 1)
                          for a, u in zip(a_lens, b[3])])
    read = int(np.sum(lfm2.rows_selected(m, pos, np)))
    by_hand = sum(p + 1 if p + 1 <= 64 else
                  (min(p // 8 + 1, 5) - 1) * 8 + p % 8 + 1 for p in pos)
    assert stats["select_rows_read"] == read == by_hand
    assert stats["select_rows_held"] == int(np.sum(pos + 1))
    assert stats["cache_rows_read"] == read
    assert stats["select_rows_read"] < stats["select_rows_held"]
    assert stats["pooled_key_writes"] == int(np.sum(
        (pos >= 3) & ((pos - 3) % 2 == 0)))
    windows = int(np.sum(np.where(pos + 1 > 64, (pos - 3) // 2 + 1, 0)))
    assert stats["select_windows_read"] == windows
    parts = stats["decode_bytes"]
    assert parts["state"] == 3 * steps * 2 * 4 * 4 * 16 * 16
    assert parts["rows"] == read * 2 * 2 * 16 * 4
    assert parts["select"] == windows * 2 * 16 * 4
    assert parts["head"] == 31 * 64 * 64 * 4
    layers = sum(x.size for i in range(4)
                 for x in jax.tree.leaves(params[f"layer{i}"]))
    assert parts["weights"] == 31 * layers * 4
    # the selection's arrays of every step came out
    assert sorted(engine.last_call["decode_watch"]) == [
        "gated0", "gated1", "logits", "selected"]


def test_training_path_equals_reference(call):
    """``LFM2ASR.hidden`` over the packed sequences (the sequence form
    alone, no cache) is ONE call over the whole sequence: its
    ``dense_len`` switch looks at prefix + text, so it is the
    reference's where the two agree on which queries select (streams
    whose prefix is past ``dense_len`` or whose whole sequence is under
    it)."""
    cfg, b, params, engine, out, want = call
    model = create_lfm2_model(cfg.model, U)
    h, head, layout, _ = model.apply({"params": params}, *b,
                                     method="hidden")
    at = np.clip(np.asarray(layout["a_lens"])[:, None]
                 + np.arange(U + 1)[None, :], 0, h.shape[1] - 1)
    got = np.take_along_axis(np.asarray(h), at[..., None], 1) \
        @ np.asarray(head).T * cfg.model.mup_lm_head
    same = np.asarray([True, True, False, True])[:, None] & want["steps"]
    assert ref.rms_rel(got, want["logits"], same) < TOL


CONTROLS = [f for f in ref.FAULTS if f != "float8_weights"]


@pytest.mark.parametrize("fault", CONTROLS)
def test_a_fault_in_the_reference_fails_the_limits(call, fault):
    """One departure each, put into the REFERENCE: the served call no
    longer agrees with it inside the limits the clean one meets (some
    quantity reads over 1e-3, or the chosen blocks differ)."""
    cfg, b, params, engine, out, _ = call
    m = cfg.model
    if fault == "tied_head":
        pytest.skip("the toy's head is seeded apart from its embedding; "
                    "benchmark/tests holds this one")
    want = jax.device_get(ref.forward(m, params, *b, S, (fault,), 32))
    got = system(call)
    if fault == "pool_stride_wrong":     # other windows: nothing to lay
        got["pooled"] = np.zeros_like(want["pooled"])  # beside them
    errs = errors(got, want, b)
    differ = chosen(got, want, b)
    assert max(errs.values()) > 1e-3 or differ > 0.01, (errs, differ)


def test_a_preset_of_these_layers_goes_through_the_entry_point():
    """``Inferencer.decode_batch`` on seeded variables made a layer at a
    time: no routing counters, the gauges of the three kinds of array."""
    from deepspeech_tpu import obs
    from deepspeech_tpu.data import CharTokenizer
    from deepspeech_tpu.infer import Inferencer
    from deepspeech_tpu.models.lfm2 import seeded_variables

    cfg = toy()
    params, buffers = seeded_variables(cfg, 3)
    assert buffers == {} and sorted(params["layer0"]) == [
        "ffn", "ffn_norm", "op_norm", "sparse"]
    assert sorted(params["layer1"]["lin"]) == [
        "gate", "k", "k_norm", "o", "o_norm", "q", "q_norm", "v"]
    inf = Inferencer(cfg, CharTokenizer.synthetic_zh(63), params, buffers)
    engine = inf.lm_greedy
    assert engine.selecting == [0] and engine.linear == [1, 2, 3]
    assert engine.stateful == [1, 2, 3] and engine.hybrid == ""
    feats, lens, _, ll = batch()
    texts = inf.decode_batch({"features": feats, "feat_lens": lens,
                              "max_tokens": ll + 1})
    assert len(texts) == 4 and all(isinstance(t, str) for t in texts)
    stats = engine.last_call["stats"]
    assert "expert_pairs" not in stats["decode"]
    assert stats["dropped_pairs"] == 0
    gauges = obs.registry().snapshot()["gauges"]
    assert gauges["lm_cache_bytes_state"] == 3 * 4 * 4 * 16 * 16 * 4
    assert gauges["lm_cache_bytes_pooled"] == 4 * 64 * 2 * 16 * 4
    assert gauges["lm_cache_bytes_select"] == 2 * 4 * S * 2 * 16 * 4
    assert gauges["lm_cache_bytes"] == gauges["lm_cache_bytes_state"] \
        + gauges["lm_cache_bytes_pooled"] + gauges["lm_cache_bytes_select"]


def test_the_trainer_says_why_it_does_not_train_the_kernel(monkeypatch):
    from deepspeech_tpu.config import apply_overrides
    from deepspeech_tpu.data import CharTokenizer
    from deepspeech_tpu.parallel.mesh import make_mesh
    from deepspeech_tpu.train import Trainer

    b = batch()

    class Pipeline:
        provides_global_batches = True

        def peek(self):
            return dict(zip(("features", "feat_lens", "labels",
                             "label_lens"), b))

    wide = apply_overrides(toy(lin_head_dim=128),
                           {"train.checkpoint_dir": ""})
    monkeypatch.setenv("DS2N_ASSUME_TPU", "1")
    with pytest.raises(NotImplementedError, match="no backward pass"):
        Trainer(wide, Pipeline(), CharTokenizer.synthetic_zh(63),
                mesh=make_mesh((1, 1)))
