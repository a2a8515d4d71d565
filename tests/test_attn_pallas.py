"""``ops/attn_pallas.py`` ``gqa_attn_fwd`` on the CPU, interpreted, at toy
sizes in float32: the kernel against ``reach_mask``'s dense reference
and against the blockwise loop it replaces, for sliding and global
layers, prefixes that are not whole tiles, shorter than the window and
past window + tile, and tiles of several shapes; a stream's padded tail
changes no valid row; the gradient past one block is the loop's; the
static tile counts the kernel's facts carry; and ``Attention`` takes
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


def kernel(q, k, v, window, tq, tk, oracle=None):
    return attn_pallas.gqa_attention(q, k, v, window, oracle, tq, tk, True)


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


@pytest.mark.parametrize("window", [W, 0], ids=["sliding", "global"])
def test_gradient_past_one_block_is_the_oracles(window):
    q, k, v = qkv(37)
    ct = jax.random.normal(jax.random.PRNGKey(5), q.shape)
    oracle = blockwise(window, 8)

    def loss(f):
        return lambda q, k, v: jnp.sum(jnp.tanh(f(q, k, v)) * ct)

    got = jax.grad(loss(lambda *x: kernel(*x, window, 16, 8, oracle)),
                   (0, 1, 2))(q, k, v)
    want = jax.grad(loss(oracle), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        # the cotangent comes through tanh of the kernel's forward
        np.testing.assert_allclose(g, w, atol=1e-5)


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


def toy_model():
    """Heads of 128 (whole lane tiles: what ``Attention`` asks of a
    preset before it takes the kernel), 4 / 2 of them."""
    m = get_config("trinity_large").model
    return dataclasses.replace(m, lfm_hidden=64, lfm_heads=4,
                               lfm_kv_heads=2, lfm_head_dim=128,
                               lfm_window=W, dtype="float32")


def kernels_traced(fn, *args):
    """``pallas_call``s of the kernel in ``fn`` traced now (a wrapper of
    its own: a trace is cached by function, and ``on_tpu`` is asked
    while tracing)."""
    jaxpr = str(jax.make_jaxpr(lambda *a: fn(*a))(*args))
    return jaxpr.count("name=gqa_attn_fwd")


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
