"""``ops/attn_pallas.py`` on the CPU, interpreted, at toy sizes in
float32. ``gqa_attn_decode`` against the plain decode form
(``models/lfm2.cached_attend``): rings that have not wrapped, are about
to and have, a global cache's position on and about a tile's edge, a
finished stream among live ones, the last tile hanging over a cache of
6,784 rows, the tiles a stream visits against ``ring_positions``' mask
itself, and ``Attention``'s choice. ``gqa_attn_fwd``: the kernel
against ``reach_mask``'s dense reference and against the blockwise loop
it replaces, for sliding and global
layers, prefixes that are not whole tiles, shorter than the window and
past window + tile, and tiles of several shapes; a stream's padded tail
changes no valid row; the backward kernels (``gqa_attn_bwd_dq``,
``gqa_attn_bwd_dkv``) against ``jax.vjp`` of the dense form over layer
kind x sequence x tiles, dk/dv summed over 7 query heads, the gradient
past the window against the loop's, the query tiles a key tile visits;
the static tile counts the kernel's facts carry; and ``Attention`` takes
the kernel where it says it does. (The interpreter pads a block that
hangs over the sequence's end with NaN: a build that multiplied a
probability of 0 with what lies there fails every ragged case.)"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeech_tpu.config import get_config
from deepspeech_tpu.models import lfm2
from deepspeech_tpu.ops import attn_pallas

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))

# ``Attention``'s ``attend`` and its loop over query blocks, on q, k, v
from attn_bench import attend, blockwise  # noqa: E402

W = 16                     # the window
B, KV, REP, HD = 2, 2, 3, 16


def qkv(s, seed=0, hd=HD):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (B, s, KV, REP, hd)),
            jax.random.normal(keys[1], (B, s, KV, hd)),
            jax.random.normal(keys[2], (B, s, KV, hd)))


def dense(q, k, v, window):
    return attend(q, k, v, 0, 0, window)


def kernel(q, k, v, window, tq, tk):
    return attn_pallas.gqa_attention(q, k, v, window, tq, tk, True)


@pytest.mark.parametrize("window", [W, 0], ids=["sliding", "global"])
@pytest.mark.parametrize("s", [37, 11, 70])
@pytest.mark.parametrize("tq, tk", [(8, 8), (16, 8), (8, 32), (32, 16)])
def test_kernel_equals_dense_reference_and_blockwise_loop(window, s, tq,
                                                          tk):
    """37 positions are whole tiles of no shape here, 11 are fewer than
    the window, 70 are past window + tile for every tile."""
    q, k, v = qkv(s)
    got = kernel(q, k, v, window, tq, tk)
    assert got.shape == q.shape
    np.testing.assert_allclose(got, dense(q, k, v, window), atol=2e-6)
    np.testing.assert_allclose(got, blockwise(window, 8)(q, k, v), atol=2e-6)


@pytest.mark.parametrize("window", [40, 0], ids=["sliding", "global"])
def test_whole_lane_tiles_keep_their_statistics_lane_wide(window):
    """Heads of 128 against key tiles of 128: the shapes of the chip,
    where a row's running maximum and sum fill a lane tile."""
    q, k, v = qkv(300, seed=3, hd=128)
    got = kernel(q, k, v, window, 16, 128)
    np.testing.assert_allclose(got, dense(q, k, v, window), atol=2e-6)


def test_a_window_is_not_no_window():
    q, k, v = qkv(70)
    near = kernel(q, k, v, W, 16, 8)
    far = kernel(q, k, v, 0, 16, 8)
    np.testing.assert_array_equal(near[:, :W], far[:, :W])
    assert float(jnp.max(jnp.abs(near[:, W:] - far[:, W:]))) > 1e-2


@pytest.mark.parametrize("window", [W, 0], ids=["sliding", "global"])
def test_a_padded_tail_changes_no_valid_row(window):
    """Positions past a stream's valid length attend causally and count
    for nothing: whatever they hold, the rows before them are the same
    to the bit."""
    s, valid = 61, 43
    q, k, v = qkv(s)
    tail = jnp.arange(s)[None, :, None, None] >= valid
    other = [jnp.where(tail[..., None] if x.ndim == 5 else tail,
                       1e4 * y, x)
             for x, y in zip((q, k, v), qkv(s, seed=9))]
    got = kernel(*other, window, 16, 8)
    np.testing.assert_array_equal(got[:, :valid],
                                  kernel(q, k, v, window, 16, 8)[:, :valid])
    assert np.all(np.isfinite(got))


def grads(f, q, k, v, seed=5):
    """The gradients of a scalar of ``f(q, k, v)`` (the cotangent comes
    through tanh of the forward's result)."""
    ct = jax.random.normal(jax.random.PRNGKey(seed), q.shape)
    return jax.grad(lambda *x: jnp.sum(jnp.tanh(f(*x)) * ct),
                    (0, 1, 2))(q, k, v)


@pytest.mark.parametrize("window", [W, 0], ids=["sliding", "global"])
@pytest.mark.parametrize("s", [11, 37, 64, 70],
                         ids=["one_block", "ragged", "whole_tiles",
                              "past_window_and_tile"])
@pytest.mark.parametrize("tq, tk", [(8, 8), (16, 8), (8, 32), (32, 16)])
def test_backward_kernels_equal_the_dense_forms_vjp(window, s, tq, tk):
    """``gqa_attn_bwd_dq`` / ``gqa_attn_bwd_dkv`` interpreted against
    ``jax.vjp`` of ``reach_mask``'s dense form: 11 positions are one
    tile and fewer than the window, 37 whole tiles of no shape here (a
    ragged tail in queries and keys), 64 whole tiles of every shape, 70
    past window + tile for every tile."""
    q, k, v = qkv(s)
    got = grads(lambda *x: kernel(*x, window, tq, tk), q, k, v)
    want = grads(lambda *x: dense(*x, window), q, k, v)
    for g, w in zip(got, want):
        assert np.all(np.isfinite(g))
        np.testing.assert_allclose(g, w, atol=1e-5)


@pytest.mark.parametrize("window", [W, 0], ids=["sliding", "global"])
def test_backward_sums_dk_dv_over_seven_query_heads(window):
    """28 / 4 heads: a key/value head's gradient is the sum over the
    ``rep`` = 7 query heads that share it."""
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(keys[0], (1, 45, 2, 7, HD))
    k = jax.random.normal(keys[1], (1, 45, 2, HD))
    v = jax.random.normal(keys[2], (1, 45, 2, HD))
    got = grads(lambda *x: kernel(*x, window, 16, 8), q, k, v)
    want = grads(lambda *x: dense(*x, window), q, k, v)
    # ... and it is NOT one head's share
    one = grads(lambda q, k, v: dense(q, k, v, window)[:, :, :, :1],
                q[:, :, :, :1], k, v, seed=5)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-5)
    assert float(jnp.max(jnp.abs(got[1] - one[1]))) > 1e-2


@pytest.mark.parametrize("window", [40, 0], ids=["sliding", "global"])
def test_backward_at_whole_lane_tiles(window):
    """Heads of 128 against tiles of 128: the shapes of the chip, where
    the forward's log-sum-exp leaves its lane-wide statistic as a row."""
    q, k, v = qkv(300, seed=3, hd=128)
    got = grads(lambda *x: kernel(*x, window, 128, 128), q, k, v)
    want = grads(lambda *x: dense(*x, window), q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5)


@pytest.mark.parametrize("window", [W, 0], ids=["sliding", "global"])
def test_gradient_past_the_window_is_the_loops(window):
    """``jax.grad`` through a sequence past the window agrees between
    the kernels and the blockwise loop they replace."""
    q, k, v = qkv(70)
    got = grads(lambda *x: kernel(*x, window, 16, 8), q, k, v)
    want = grads(blockwise(window, 8), q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-5)


@pytest.mark.parametrize("window", [W, 0], ids=["sliding", "global"])
def test_a_padded_tail_changes_no_valid_rows_gradient(window):
    """Whatever the positions past a stream's valid length hold, with a
    zero cotangent there the gradients before them are the same."""
    s, valid = 61, 43
    q, k, v = qkv(s)
    tail = jnp.arange(s)[None, :, None, None] >= valid
    other = [jnp.where(tail[..., None] if x.ndim == 5 else tail, 7.0 * y, x)
             for x, y in zip((q, k, v), qkv(s, seed=9))]
    ct = jnp.where(tail[..., None], 0.0,
                   jax.random.normal(jax.random.PRNGKey(5), q.shape))

    def grad_of(q, k, v):
        return jax.grad(lambda *x: jnp.sum(
            kernel(*x, window, 16, 8) * ct), (0, 1, 2))(q, k, v)

    for g, w in zip(grad_of(*other), grad_of(q, k, v)):
        np.testing.assert_allclose(g[:, :valid], w[:, :valid], atol=1e-5)
        assert np.all(np.isfinite(g))


@pytest.mark.parametrize("s, window, tq, tk", [
    (6784, 4096, 256, 512), (6784, 0, 256, 512), (70, W, 16, 8),
    (70, 0, 8, 32), (37, W, 32, 16)])
def test_reach_of_keys_is_reach_transposed(s, window, tq, tk):
    """The query tiles a key tile's grid visits are those that hold a
    query which reaches one of its keys, counted from the mask itself;
    over all key tiles they are the forward's tile pairs."""
    first, last = attn_pallas.reach_of_keys(s, window, tq, tk)
    seen = np.asarray(lfm2.reach_mask(0, s, 0, s, window))
    pairs = 0
    for n, j0 in enumerate(range(0, s, tk)):
        hit = [i0 // tq for i0 in range(0, s, tq)
               if seen[i0:i0 + tq, j0:j0 + tk].any()]
        assert (first[n], last[n]) == (hit[0], hit[-1])
        assert hit == list(range(hit[0], hit[-1] + 1))
        pairs += len(hit)
    assert pairs == attn_pallas.tile_counts(s, window, tq, tk)[
        "key_tiles_in_reach"]


@pytest.mark.parametrize("s, window, tq, tk, counts", [
    # the cell's sub-batch at the module's tiles: the masked work
    (5250, 4096, 256, 512, None),
    (5250, 0, 256, 512, None),
    (70, W, 16, 8, {"key_tiles": 17, "key_tiles_in_reach": 17,
                    "key_tiles_masked": 17}),
    (70, 0, 16, 8, {"key_tiles": 29, "key_tiles_in_reach": 29,
                    "key_tiles_masked": 9}),
])
def test_tile_counts(s, window, tq, tk, counts):
    """What the grid computes is what holds a key in reach (the guard
    is exact), counted here from the mask itself; the tiles that need
    no mask are the ones the mask fills."""
    got = attn_pallas.tile_counts(s, window, tq, tk)
    # a query tile that hangs over the end is computed whole: its rows
    # past the end reach what they would, and are not written
    rows = -(-s // tq) * tq
    seen = np.asarray(lfm2.reach_mask(0, rows, 0, rows, window))[:, :s]
    some = whole = 0
    for i0 in range(0, s, tq):
        for j0 in range(0, s, tk):
            tile = seen[i0:i0 + tq, j0:j0 + tk]
            some += bool(tile.any())
            # a key tile that hangs over the end is masked there
            whole += bool(tile.all()) and tile.shape[1] == tk
    assert got == {"key_tiles": some, "key_tiles_in_reach": some,
                   "key_tiles_masked": some - whole}
    if counts:
        assert got == counts
    first, last, _, _ = attn_pallas.reach(s, window, tq, tk)
    assert np.all(first <= last) and last[-1] == (s - 1) // tk


def decode_inputs(rows, seed=0, streams=4, hd=HD):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (streams, KV, REP, hd)),
            jax.random.normal(keys[1], (streams, rows, KV, hd)),
            jax.random.normal(keys[2], (streams, rows, KV, hd)))


def decode_kernel(q, k, v, pos, live, window, rt):
    return attn_pallas.gqa_decode(q, k, v, jnp.asarray(pos),
                                  jnp.asarray(live, bool), window, rt, True)


@pytest.mark.parametrize("rt", [4, 8, 16])
@pytest.mark.parametrize("rows, window, pos", [
    # a ring of the window's rows: not yet wrapped, its last slot
    # written (R - 1), the first row written again, long wrapped
    (W, W, [0, 5, W - 2, W - 1]), (W, W, [W, W + 1, 3 * W - 1, 5 * W + 3]),
    # a cache longer than the window: the window cuts its reach before
    # it wraps and (two runs of slots) after
    (40, W, [3, W - 1, W, 39]), (40, W, [40, 41, 47, 95]),
    # a cache that sees all, never wrapped: a position on a tile's
    # edge, just before it and just after it
    (40, 0, [15, 16, 17, 39]), (40, 0, [0, 7, 8, 31]),
    # not whole tiles of any size here
    (37, 0, [0, 20, 35, 36]), (37, W, [2, 19, 36, 80])])
def test_decode_kernel_equals_the_plain_decode_form(rows, window, pos, rt):
    q, k, v = decode_inputs(rows)
    got = decode_kernel(q, k, v, pos, [True] * 4, window, rt)
    want = lfm2.cached_attend(q, k, v, jnp.asarray(pos), window)
    assert got.shape == q.shape
    np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("window", [40, 0], ids=["sliding", "global"])
def test_decode_kernel_at_whole_lane_tiles(window):
    """Heads of 128 and row tiles of 128 columns: the shapes of the
    chip, where a head's running maximum and sum fill a lane tile."""
    q, k, v = decode_inputs(40, seed=3, hd=128)
    pos = [11, 31, 39, 90 if window else 32]
    got = decode_kernel(q, k, v, pos, [True] * 4, window, 64)
    np.testing.assert_allclose(
        got, lfm2.cached_attend(q, k, v, jnp.asarray(pos), window),
        atol=2e-6)


@pytest.mark.parametrize("rows, window", [(W, W), (40, 0)],
                         ids=["sliding", "global"])
@pytest.mark.parametrize("live", [[True, False, True, True],
                                  [False, False, True, False],
                                  [True, True, False, False],
                                  [False] * 4])
def test_a_finished_stream_is_not_read_and_gives_zeros(rows, window, live):
    """A stream that is not live fetches no row (its cache is NaN here)
    and writes zeros; no live stream's result moves."""
    q, k, v = decode_inputs(rows, seed=1)
    pos = [9, 30, 12, 33]
    dead = ~np.asarray(live)[:, None, None, None]
    got = decode_kernel(q, jnp.where(dead, jnp.nan, k),
                        jnp.where(dead, jnp.nan, v), pos, live, window, 8)
    want = decode_kernel(q, k, v, pos, [True] * 4, window, 8)
    np.testing.assert_array_equal(got, jnp.where(dead, 0, want))


def test_the_last_tile_hangs_over_a_cache_of_6784_rows():
    """The cell's global cache is 13.25 row tiles of 512: the
    interpreter fills the overhang with NaN, which must reach neither
    the scores nor, through a probability of 0, the mix."""
    rows = 6784
    q, k, v = decode_inputs(rows, seed=2, streams=3)
    pos = [6655, 6656, 6783]
    got = decode_kernel(q, k, v, pos, [True] * 3, 0, attn_pallas.ROW_TILE)
    np.testing.assert_allclose(
        got, lfm2.cached_attend(q, k, v, jnp.asarray(pos), 0), atol=2e-6)
    assert int(attn_pallas.rows_fetched(
        jnp.asarray(pos), jnp.ones(3, bool), rows, 0)) == 6656 + 2 * 6784


@pytest.mark.parametrize("rows, window", [(W, W), (40, W), (40, 0),
                                          (37, 0), (8, W)])
@pytest.mark.parametrize("rt", [4, 8, 16])
def test_a_stream_visits_the_tiles_its_mask_reaches(rows, window, rt):
    """``decode_run`` against ``ring_positions``' rule itself: the tiles
    visited are those with a slot in reach (all tiles, and the mask
    decides, where a window shorter than the cache cuts a wrapped
    cache), none for a stream that is not live."""
    pos = np.arange(3 * rows + 2)
    held = np.asarray(lfm2.ring_positions(jnp.asarray(pos), rows))
    seen = held >= 0
    if window:
        seen &= pos[:, None] - held < window
    tiles = -(-rows // rt)
    some = np.stack([seen[:, j * rt:(j + 1) * rt].any(1)
                     for j in range(tiles)], 1)
    first, last = attn_pallas.decode_run(pos, True, rows, window, rt, np)
    visited = (np.arange(tiles) >= first[:, None]) \
        & (np.arange(tiles) <= last[:, None])
    assert np.all(visited | ~some)
    exact = ~((pos >= rows) & bool(window) & (window < rows))
    np.testing.assert_array_equal(visited[exact], some[exact])
    fetched = [int(attn_pallas.rows_fetched(
        pos[i:i + 1], np.array([True]), rows, window, rt, np))
        for i in range(len(pos))]
    np.testing.assert_array_equal(
        fetched, np.minimum((last + 1) * rt, rows) - first * rt)
    assert np.all(np.asarray(fetched) >= seen.sum(1))
    first, last = attn_pallas.decode_run(pos, False, rows, window, rt, np)
    assert np.all(last < first)
    assert int(attn_pallas.rows_fetched(
        pos, np.zeros(len(pos), bool), rows, window, rt, np)) == 0


def toy_model():
    """Heads of 128 (whole lane tiles: what ``Attention`` asks of a
    preset before it takes the kernel), 4 / 2 of them."""
    m = get_config("trinity_large").model
    return dataclasses.replace(m, lfm_hidden=64, lfm_heads=4,
                               lfm_kv_heads=2, lfm_head_dim=128,
                               lfm_window=W, dtype="float32")


def kernels_traced(fn, *args, name="gqa_attn_fwd"):
    """``pallas_call``s of the kernel in ``fn`` traced now (a wrapper of
    its own: a trace is cached by function, and ``on_tpu`` is asked
    while tracing)."""
    jaxpr = str(jax.make_jaxpr(lambda *a: fn(*a))(*args))
    return jaxpr.count("name=" + name)


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_attention_takes_the_kernel_past_one_block_on_a_tpu(
        kind, monkeypatch):
    """Selection is by what the code observes: past one block and on a
    TPU the kernel, one a layer; one block, the CPU, or a head that is
    not whole lane tiles keep ``attend``. Under the interpreter the
    layer with the kernel is the layer with the loop, gradient
    included."""
    from jax.experimental.pallas import tpu as pltpu

    m = toy_model()
    layer = lfm2.Attention(m, kind, 16)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 70, 64))
    params = layer.init(jax.random.PRNGKey(4), x[:, :4])["params"]

    def apply(p, x, layer=layer):
        return layer.apply({"params": p}, x)[0]

    want = apply(params, x)
    loss = lambda p, x: jnp.sum(jnp.tanh(apply(p, x)))  # noqa: E731
    want_grad = jax.grad(loss)(params, x)
    assert kernels_traced(apply, params, x) == 0
    monkeypatch.setenv("DS2N_ASSUME_TPU", "1")
    assert kernels_traced(apply, params, x) == 1
    assert kernels_traced(apply, params, x[:, :16]) == 0
    small = lfm2.Attention(dataclasses.replace(m, lfm_head_dim=16),
                           kind, 16)
    assert kernels_traced(
        lambda x: small.init_with_output(jax.random.PRNGKey(4), x)[0][0],
        x) == 0
    with pltpu.force_tpu_interpret_mode():
        got = apply(params, x)
        got_grad = jax.grad(loss)(params, x)
    np.testing.assert_allclose(got, want, atol=2e-6)
    jax.tree.map(lambda g, w: np.testing.assert_allclose(g, w, atol=2e-5),
                 got_grad, want_grad)


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_attention_decodes_through_the_kernel_on_a_tpu(kind, monkeypatch):
    """The decode branch's choice is the prefill branch's: on a TPU and
    with heads of whole lane tiles ``gqa_attn_decode``, one a layer;
    on the CPU, or with smaller heads, the plain form. Under the
    interpreter the layer with the kernel is the layer without: output,
    the cache it leaves, and a finished stream's cache row unwritten."""
    from jax.experimental.pallas import tpu as pltpu

    m = toy_model()
    layer = lfm2.Attention(m, kind, 16)
    rows = W if "sliding" in kind else 40
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 1, 64))
    params = layer.init(jax.random.PRNGKey(4), x)["params"]
    keys = jax.random.split(jax.random.PRNGKey(5), 2)
    cache = tuple(jax.random.normal(k, (4, rows, 2, 128)) for k in keys)
    pos = jnp.asarray([[3], [W - 1], [W + 5], [38]])
    live = jnp.asarray([[True], [True], [False], [True]])

    def apply(p, x, layer=layer):
        return layer.apply({"params": p}, x, pos, cache, live)

    name = "gqa_attn_decode"
    want, kept = apply(params, x)
    assert kernels_traced(apply, params, x, name=name) == 0
    monkeypatch.setenv("DS2N_ASSUME_TPU", "1")
    assert kernels_traced(apply, params, x, name=name) == 1
    small = lfm2.Attention(dataclasses.replace(m, lfm_head_dim=16),
                           kind, 16)
    assert kernels_traced(
        lambda x: small.init_with_output(
            jax.random.PRNGKey(4), x, pos,
            tuple(c[..., :16] for c in cache), live)[0][0],
        x, name=name) == 0
    with pltpu.force_tpu_interpret_mode():
        got, got_kept = apply(params, x)
    # the finished stream's output is the gate times zeros
    np.testing.assert_allclose(got[jnp.asarray([0, 1, 3])],
                               want[jnp.asarray([0, 1, 3])], atol=2e-6)
    for a, b, c in zip(got_kept, kept, cache):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a[2], c[2])
