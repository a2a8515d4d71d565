"""The conv frontend's frequency fold (models/conv.py): the same sums
as ``lax.conv_general_dilated``, presented to the compiler with 128
channels. CPU, float32 at ``HIGHEST``; the chip's side of it is
tests/test_tpu_compile.py ``test_frontend_convolutions_fill_the_lanes``.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeech_tpu import obs
from deepspeech_tpu.config import get_config
from deepspeech_tpu.models import create_model
from deepspeech_tpu.models.conv import (ConvFrontend, fold_factor,
                                        freq_folded_conv, freq_padding)
from deepspeech_tpu.models.layers import (MaskedBatchNorm, clipped_relu,
                                          length_mask)

NHWC = ("NHWC", "HWIO", "NHWC")


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def plain(x, kernel, strides, padding):
    return jax.lax.conv_general_dilated(x, kernel, strides, padding,
                                        dimension_numbers=NHWC)


def same_padding(kt, kf, st, sf, fdim):
    """ConvFrontend's padding rule, as the parent wrote it."""
    pt = (kt - st) // 2
    pf_total = (-(-fdim // sf) - 1) * sf + kf - fdim
    pad = ((pt, kt - 1 - pt), (pf_total // 2, pf_total - pf_total // 2))
    assert pad[1] == freq_padding(fdim, kf, sf)
    return pad


class ParentFrontend(nn.Module):
    """``ConvFrontend`` as it was before the fold (commit d9a78ae), kept
    as the oracle: plain ``nn.Conv``, the same names."""

    cfg: object

    @nn.compact
    def __call__(self, x, feat_lens, train):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        x = x.astype(dtype)[..., None]
        lens = feat_lens
        for i, ((kt, kf, st, sf), ch) in enumerate(
                zip(cfg.conv_layers, cfg.conv_channels)):
            x = nn.Conv(ch, kernel_size=(kt, kf), strides=(st, sf),
                        padding=same_padding(kt, kf, st, sf, x.shape[2]),
                        use_bias=False, dtype=dtype, name=f"conv{i}")(x)
            lens = -(-lens // st)
            mask = length_mask(lens, x.shape[1])
            x = MaskedBatchNorm(name=f"bn{i}")(x, mask, train)
            x = clipped_relu(x, cfg.relu_clip)
            x = x * mask[:, :, None, None].astype(x.dtype)
        b, t, f, c = x.shape
        return x.reshape(b, t, f * c), lens


def rel(got, want):
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


# (F, C_in, C_out, kt, kf, st, sf, T): g and the surplus follow.
SHAPES = {
    "conv0": (161, 1, 32, 11, 41, 2, 2, 24),       # g=4, 84 for 81
    "conv1": (81, 32, 32, 11, 21, 1, 2, 12),       # g=4, 44 for 41
    "odd_t_stride2": (161, 1, 32, 11, 41, 2, 2, 23),
    "freq_stride1": (40, 3, 16, 5, 7, 1, 1, 9),    # g=8, no surplus
    "freq_stride3": (33, 4, 64, 3, 5, 2, 3, 11),   # g=2, 12 for 11
    "wide_kernel_few_groups": (9, 2, 32, 3, 9, 1, 1, 6),  # 12 for 9
    "one_group": (5, 2, 16, 3, 3, 1, 2, 6),        # g=8, 8 for 3
}


@pytest.mark.parametrize("case", SHAPES)
def test_folded_equals_plain_in_value_and_both_gradients(case):
    fdim, c_in, c_out, kt, kf, st, sf, t = SHAPES[case]
    rng = np.random.default_rng(len(case))
    x = jnp.asarray(rng.standard_normal((2, t, fdim, c_in)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((kt, kf, c_in, c_out)),
                    jnp.float32)
    pad = same_padding(kt, kf, st, sf, fdim)
    assert fold_factor(c_out) > 1
    want = plain(x, k, (st, sf), pad)
    got = freq_folded_conv(x, k, (st, sf), pad)
    assert got.shape == want.shape
    assert rel(got, want) < 1e-5
    ct = jnp.asarray(rng.standard_normal(want.shape), jnp.float32)
    grads = [jax.grad(lambda x, k: jnp.sum(f(x, k, (st, sf), pad) * ct),
                      argnums=(0, 1))(x, k)
             for f in (freq_folded_conv, plain)]
    assert rel(grads[0][0], grads[1][0]) < 1e-5
    assert rel(grads[0][1], grads[1][1]) < 1e-5


def test_uneven_given_padding_and_unread_columns():
    """Any explicit padding, not only the frontend's: more on the left
    than on the right, and a stride that leaves given columns unread."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((2, 8, 30, 3)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((3, 6, 3, 32)), jnp.float32)
    for pad in [((1, 1), (5, 0)), ((0, 2), (0, 7)), ((1, 1), (2, 3))]:
        want = plain(x, k, (1, 4), pad)
        assert rel(freq_folded_conv(x, k, (1, 4), pad), want) < 1e-5


@pytest.mark.parametrize("c_out", [48, 128, 256])
def test_wide_or_odd_widths_lower_to_the_plain_call(c_out):
    """g = 1: the helper IS the plain call, so presets with 128 or more
    channels keep the program text they had."""
    assert fold_factor(c_out) == 1
    x = jnp.zeros((2, 8, 20, 4), jnp.float32)
    k = jnp.zeros((3, 5, 4, c_out), jnp.float32)
    args = ((1, 2), ((1, 1), (2, 1)))
    assert str(jax.make_jaxpr(lambda x, k: freq_folded_conv(
        x, k, *args, layer="wide"))(x, k)) \
        == str(jax.make_jaxpr(lambda x, k: plain(x, k, *args))(x, k))


@pytest.mark.parametrize("c_out, g", [(1, 128), (16, 8), (32, 4), (64, 2),
                                      (96, 1), (128, 1), (512, 1)])
def test_fold_factor_follows_the_channels(c_out, g):
    assert fold_factor(c_out) == g


def test_kernel_gradient_is_a_contraction_not_a_scatter():
    """The folded kernel is placed by a 0/1 contraction, so its
    transpose is one too; a TPU runs a scatter-add one update after
    another (PERF.md, PR 25)."""
    x = jnp.zeros((2, 12, 81, 32), jnp.float32)
    k = jnp.zeros((11, 21, 32, 32), jnp.float32)
    pad = same_padding(11, 21, 1, 2, 81)
    text = str(jax.make_jaxpr(jax.grad(
        lambda x, k: jnp.sum(freq_folded_conv(x, k, (1, 2), pad)),
        argnums=(0, 1)))(x, k))
    assert "conv_general_dilated" in text and "dot_general" in text
    for primitive in ("scatter", "gather", "dynamic_update_slice"):
        assert primitive not in text


@pytest.fixture(scope="module")
def small_cfg():
    import dataclasses

    return dataclasses.replace(get_config("ds2_full").model,
                               dtype="float32")


def test_surplus_positions_reach_neither_statistics_nor_output(small_cfg):
    """84 positions computed for 81 frequencies (44 for 41): the three
    on top read the real last frequencies, made LARGE here, and must
    not show in the batch-norm statistics, in the next layer or in the
    ``[B, T', F'*C]`` result; the oracle is the frontend before the
    fold."""
    rng = np.random.default_rng(3)
    feats = rng.standard_normal((3, 31, 161)).astype(np.float32)
    feats[:, :, -12:] *= 50.0
    lens = jnp.asarray([31, 24, 17])
    new, old = ConvFrontend(small_cfg), ParentFrontend(small_cfg)
    variables = old.init(jax.random.PRNGKey(1), feats, lens, False)
    (want, want_lens), want_stats = old.apply(
        variables, feats, lens, True, mutable=["batch_stats"])
    (got, got_lens), got_stats = new.apply(
        variables, feats, lens, True, mutable=["batch_stats"])
    assert got.shape == want.shape == (3, 16, 41 * 32)
    assert np.array_equal(got_lens, want_lens)
    assert rel(got, want) < 1e-5
    flat = lambda s: jax.tree_util.tree_leaves_with_path(s)  # noqa: E731
    for (path, a), (_, b) in zip(flat(got_stats), flat(want_stats)):
        assert rel(a, b) < 1e-5, path
    # and in evaluation, from the running statistics
    assert rel(new.apply(variables, feats, lens, False)[0],
               old.apply(variables, feats, lens, False)[0]) < 1e-5


@pytest.mark.parametrize("preset", ["ds2_full", "ds2_streaming"])
def test_parameter_tree_is_the_parents(preset):
    """Names, shapes, dtypes AND seeded values: a checkpoint written
    before the fold restores into the model after it."""
    cfg = get_config(preset).model
    feats, lens = jnp.zeros((2, 40, 161)), jnp.asarray([40, 33])
    new = ConvFrontend(cfg).init(jax.random.PRNGKey(5), feats, lens, False)
    old = ParentFrontend(cfg).init(jax.random.PRNGKey(5), feats, lens,
                                   False)
    assert jax.tree.structure(new) == jax.tree.structure(old)
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(new),
            jax.tree_util.tree_leaves_with_path(old)):
        assert a.shape == b.shape and a.dtype == b.dtype, path
        assert np.array_equal(a, b), path
    assert new["params"]["conv1"]["kernel"].shape == (11, 21, 32, 32)
    assert new["params"]["conv1"]["kernel"].dtype == jnp.float32


def test_model_tree_unchanged_and_fold_facts_recorded():
    """``DeepSpeech2``'s frontend keeps its place in the tree, and
    tracing it leaves ``conv_fold{layer, g, taps, surplus}`` in the
    registry: which layers ran folded, by how much."""
    import dataclasses

    cfg = dataclasses.replace(get_config("ds2_full").model, rnn_hidden=8,
                              rnn_layers=1, rnn_impl="xla")
    obs.registry().reset()
    shapes = jax.eval_shape(
        lambda: create_model(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((2, 40, 161)),
            jnp.asarray([40, 33]), train=False))
    conv = shapes["params"]["conv"]
    assert sorted(conv) == ["bn0", "bn1", "conv0", "conv1"]
    assert conv["conv0"]["kernel"].shape == (11, 41, 1, 32)
    assert sorted(shapes["batch_stats"]["conv"]) == ["bn0", "bn1"]
    gauges = obs.registry().snapshot()["gauges"]
    assert gauges['conv_fold{g="4",layer="conv0",surplus="3",taps="6"}'] \
        == 1
    assert gauges['conv_fold{g="4",layer="conv1",surplus="3",taps="4"}'] \
        == 1
