"""Manifold-constrained hyper-connections (``models/mhc.py``) and the
decoder layer under them (``models/lfm2.DecoderLayer``) at a toy width
on the CPU: the coefficients against the plain reference's
(``benchmark/reference/xing4_ref.hyper``), the residual mix doubly
stochastic, the three mixes against their einsums, the clamp, and ONE
stream is the plain residual bit for bit."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import xing4_ref
from deepspeech_tpu.config import get_config
from deepspeech_tpu.models import mhc
from deepspeech_tpu.models.axk1 import LatentAttention
from deepspeech_tpu.models.lfm2 import (DecoderLayer, RMSNorm,
                                        SparseExperts, SwiGLU)

D, N = 48, 4


def model(**kw):
    sizes = dict(lfm_hidden=D, lfm_heads=4, lfm_ffn_dim=64,
                 lfm_expert_dim=16, lfm_experts=8, lfm_top_k=2,
                 experts_held=8, mla_q_rank=12, mla_kv_rank=8,
                 mla_nope_dim=8, mla_rope_dim=4, mla_v_dim=8,
                 dtype="float32", moe_impl="xla")
    sizes.update(kw)
    return dataclasses.replace(get_config("xing4_29b_a4b").model, **sizes)


def streams(seed=0, lead=(3, 5), n=N):
    return jax.random.normal(jax.random.PRNGKey(seed), lead + (n, D))


@pytest.fixture(scope="module")
def connection():
    m = model()
    x = streams()
    layer = mhc.HyperConnection(m)
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    # logits of size one and more, so that no sigmoid sits in its middle
    params = dict(params, phi=params["phi"] * 5.0,
                  alpha=jnp.asarray([0.7, 1.3, 1.9]),
                  norm=1.0 + 0.1 * jax.random.normal(
                      jax.random.PRNGKey(2), params["norm"].shape))
    return m, layer, params, x


def test_coefficients_equal_the_references(connection):
    m, layer, params, x = connection
    got = layer.apply({"params": params}, x)
    want = xing4_ref.hyper(m, params, x, ())
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == jnp.float32
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-6)
    assert float(jnp.max(got[0])) < 1 < float(jnp.max(got[1])) < 2


@pytest.mark.parametrize("axis, within", [(-2, 2e-5), (-1, 0.1)])
def test_residual_mix_is_doubly_stochastic(connection, axis, within):
    """The last of the 20 rounds' two divisions leaves the columns at
    one up to ``hc_eps``; the rows are as near as 20 rounds bring them
    from logits of this size (std 2 and more: hundredths)."""
    m, layer, params, x = connection
    h_res = layer.apply({"params": params}, x)[2]
    assert float(jnp.min(h_res)) > 0
    np.testing.assert_allclose(jnp.sum(h_res, axis), 1.0, atol=within)


def test_mild_logits_converge_in_twenty_rounds():
    logits = jax.random.normal(jax.random.PRNGKey(9), (N, N, 500))
    mat = mhc.sinkhorn(jnp.exp(logits), 20, 1e-6)
    for axis in (0, 1):
        np.testing.assert_allclose(jnp.sum(mat, axis), 1.0, atol=5e-3)
    once = mhc.sinkhorn(jnp.exp(logits), 1, 1e-6)
    assert float(jnp.max(jnp.abs(jnp.sum(once, 1) - 1.0))) > 0.1


def test_one_sinkhorn_round_is_not_twenty(connection):
    m, layer, params, x = connection
    once = mhc.HyperConnection(dataclasses.replace(
        m, hc_sinkhorn_iters=1)).apply({"params": params}, x)[2]
    assert float(jnp.max(jnp.abs(jnp.sum(once, -1) - 1.0))) > 1e-3
    np.testing.assert_allclose(
        once, xing4_ref.hyper(m, params, x, ("sinkhorn_1",))[2],
        rtol=2e-5)


def test_the_clamp_keeps_large_logits_finite(connection):
    m, layer, params, x = connection
    big = dict(params, bias=params["bias"].at[2 * N:].multiply(40.0)
               .at[2 * N].set(100.0).at[2 * N + 5].set(-120.0))
    h_res = layer.apply({"params": big}, x)[2]
    assert bool(jnp.all(jnp.isfinite(h_res)))
    np.testing.assert_allclose(h_res, xing4_ref.hyper(m, big, x, ())[2],
                               rtol=1e-4, atol=1e-6)
    open_ = mhc.HyperConnection(dataclasses.replace(
        m, hc_res_clamp=(-1e9, 1e9))).apply({"params": big}, x)[2]
    assert not bool(jnp.all(jnp.isfinite(open_)))


def test_the_three_mixes_are_their_einsums(connection):
    m, layer, params, x = connection
    h_pre, h_post, h_res = layer.apply({"params": params}, x)
    y = jax.random.normal(jax.random.PRNGKey(3), x.shape[:-2] + (D,))
    np.testing.assert_allclose(
        mhc.read(h_pre, x), jnp.einsum("bsn,bsnd->bsd", h_pre, x),
        rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        mhc.write(h_res, h_post, x, y),
        jnp.einsum("bsij,bsjd->bsid", h_res, x)
        + h_post[..., None] * y[..., None, :], rtol=1e-5, atol=1e-6)


def test_the_ends_copy_and_sum():
    h = streams(4, n=1)[..., 0, :]
    x = mhc.fan_out(h, N)
    assert x.shape == h.shape[:-1] + (N, D)
    np.testing.assert_array_equal(x[..., 2, :], h)
    np.testing.assert_allclose(mhc.contract(x, N), N * h, rtol=1e-6)
    assert mhc.fan_out(h, 1) is h and mhc.contract(h, 1) is h


def test_bfloat16_streams_keep_float32_coefficients(connection):
    m, layer, params, x = connection
    xb = x.astype(jnp.bfloat16)
    h_pre, h_post, h_res = layer.apply({"params": params}, xb)
    assert h_res.dtype == jnp.float32
    out = mhc.write(h_res, h_post, xb, mhc.read(h_pre, xb))
    assert out.dtype == jnp.bfloat16 and out.shape == x.shape
    np.testing.assert_allclose(jnp.sum(h_res, -2), 1.0, atol=2e-5)


@pytest.mark.parametrize("sparse", [False, True])
def test_one_stream_is_the_plain_residual_bit_for_bit(sparse):
    """``hc_streams`` = 1: the layer is ``h + attention(norm(h))``, then
    ``h + ffn(norm(h))``, made of the same modules by hand, and has no
    hyper-connection parameter."""
    m = model(hc_streams=1)
    h = streams(5, n=1)[..., 0, :]
    b, s, _ = h.shape
    valid = jnp.ones((b, s), bool)
    pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    layer = DecoderLayer(m, "latent_attention", sparse)
    v = layer.init(jax.random.PRNGKey(6), h, valid, pos)
    assert not any("hc" in k for k in v["params"])
    got, _, rows = layer.apply(v, h, valid, pos)

    def part(module, name, *args):
        held = {"params": v["params"][name]}
        if name in v.get("buffers", {}):
            held["buffers"] = v["buffers"][name]
        return module.apply(held, *args)

    x = part(RMSNorm(m.lfm_norm_eps), "op_norm", h)
    y, want_rows = part(LatentAttention(m), "attn", x, pos, None)
    mid = h + y
    x = part(RMSNorm(m.lfm_norm_eps), "ffn_norm", mid)
    if sparse:
        want = mid + part(SparseExperts(m), "moe", x, valid)[0]
    else:
        want = mid + part(SwiGLU(m.lfm_ffn_dim), "ffn", x)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(rows, want_rows)


def test_four_streams_are_the_references_layer():
    """The whole layer under hyper-connections against the reference's
    (attention sub-layer, then the expert block's)."""
    m = model()
    x = streams(7)
    b, s = x.shape[:2]
    valid = jnp.ones((b, s), bool)
    pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    layer = DecoderLayer(m, "latent_attention", True)
    v = layer.init(jax.random.PRNGKey(8), x, valid, pos)
    params = jax.tree.map(
        lambda p: p * (p.shape[-2] ** -0.5 / 0.02)
        if p.ndim >= 2 and p.shape[-1] != N * (N + 2) else p, v["params"])
    got, _, rows = layer.apply({**v, "params": params}, x, valid, pos)
    want, want_rows, _, _ = xing4_ref.decoder_layer(
        m, params, v["buffers"]["moe"]["expert_bias"], x, valid,
        jnp.zeros((b, s, s), bool), True, ())
    assert xing4_ref.rms_rel(got, want) < 2e-5
    assert xing4_ref.rms_rel(rows, want_rows) < 2e-5
    assert xing4_ref.rms_rel(got, x) > 0.1      # it did something
