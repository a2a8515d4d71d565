"""The hyper-connection kernels (``ops/mhc_pallas.py``) interpreted on
the CPU against ``models/mhc.py``'s ``jax.numpy`` form: ``mhc_read``'s
coefficients and read mix, ``mhc_write``'s streams, for float32 and
bfloat16 streams, 2 and 4 of them, over positions that fill their
tiles, leave a ragged one and fall short of one; the route
``DecoderLayer.residual`` takes from the shapes, and what it sows."""

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from deepspeech_tpu.config import get_config
from deepspeech_tpu.models import mhc
from deepspeech_tpu.models.lfm2 import DecoderLayer
from deepspeech_tpu.ops import mhc_pallas

D = 256
# one bfloat16 ulp of a result, and what float32's rounding of the
# coefficient path leaves on a result that its terms cancel in
BF16 = dict(rtol=2.0 ** -7, atol=1e-5)


def model(n=4, d=D, **kw):
    sizes = dict(hc_streams=n, lfm_hidden=d, lfm_heads=4, lfm_ffn_dim=64,
                 lfm_expert_dim=16, lfm_experts=8, lfm_top_k=2,
                 experts_held=8, mla_q_rank=12, mla_kv_rank=8,
                 mla_nope_dim=8, mla_rope_dim=4, mla_v_dim=8,
                 dtype="float32", moe_impl="xla")
    sizes.update(kw)
    return dataclasses.replace(get_config("xing4_29b_a4b").model, **sizes)


def connection(m, x, seed=1):
    """The module and parameters with logits of size one and more, a
    gain off one and three different scalings: a mix-up of any shows."""
    layer = mhc.HyperConnection(m)
    params = layer.init(jax.random.PRNGKey(seed), x)["params"]
    return layer, dict(
        params, phi=params["phi"] * 5.0,
        alpha=jnp.asarray([0.7, 1.3, 1.9]),
        norm=1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(seed + 1),
                                           params["norm"].shape))


def kernel_read(m, params, x):
    return mhc_pallas.read(
        x, params["norm"], params["phi"], params["alpha"], params["bias"],
        norm_eps=m.lfm_norm_eps, clamp=m.hc_res_clamp,
        iters=m.hc_sinkhorn_iters, eps=m.hc_eps, interpret=True)


def positions(kind, n, dtype):
    """Positions that are whole tiles of the size the call computes
    from its shapes; that leave a ragged last tile; fewer than one tile
    (padded up to whole lane tiles outside the kernel); and the same
    under a leading shape of two."""
    tile = mhc_pallas.tile_rows(1 << 20, n, D, jnp.dtype(dtype).itemsize)
    return {"whole": (2 * tile,), "ragged": (tile + 72,), "short": (40,),
            "short_lead": (3, 50)}[kind]


@pytest.mark.parametrize("kind", ["whole", "ragged", "short", "short_lead"])
@pytest.mark.parametrize("n", [2, 4])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kernels_equal_the_plain_form(dtype, n, kind):
    m = model(n)
    lead = positions(kind, n, dtype)
    x = (1.5 * jax.random.normal(jax.random.PRNGKey(len(lead) + n),
                                 lead + (n, D))).astype(dtype)
    y = jax.random.normal(jax.random.PRNGKey(9), lead + (D,)).astype(dtype)
    layer, params = connection(m, x)
    h_pre, h_post, h_res = layer.apply({"params": params}, x)
    coef, mix = kernel_read(m, params, x)
    assert coef.shape == lead + (n * (n + 2),) and coef.dtype == jnp.float32
    assert mix.shape == lead + (D,) and mix.dtype == x.dtype
    want = jnp.concatenate(
        [h_pre, h_post, h_res.reshape(lead + (n * n,))], axis=-1)
    # float32's rounding of logits of size ten, on both sides (the
    # plain form against the reference: rtol 2e-5, tests/test_mhc.py)
    np.testing.assert_allclose(coef, want, rtol=1e-5, atol=2e-6)
    assert float(jnp.mean(jnp.abs(coef - want))) < 5e-7
    # the last round's second division leaves the columns at one
    columns = coef[..., 2 * n:].reshape(lead + (n, n)).sum(-2)
    np.testing.assert_allclose(columns, 1.0, atol=10 * m.hc_eps)
    assert float(jnp.min(coef)) > 0
    near = BF16 if dtype == "bfloat16" else dict(rtol=1e-5, atol=1e-5)
    f32 = np.float32
    np.testing.assert_allclose(np.asarray(mix, f32),
                               np.asarray(mhc.read(h_pre, x), f32), **near)
    # the write-back by the plain form's own coefficients: the mixes
    # alone, in its order of summation
    got = mhc_pallas.write(x, y, want, interpret=True)
    assert got.shape == x.shape and got.dtype == x.dtype
    ref = np.asarray(mhc.write(h_res, h_post, x, y), f32)
    np.testing.assert_allclose(np.asarray(got, f32), ref, **near)
    if dtype == "bfloat16":
        assert np.mean(np.asarray(got, f32) != ref) < 1e-3
    # ... and by the kernel's
    got = mhc_pallas.write(x, y, coef, interpret=True)
    np.testing.assert_allclose(np.asarray(got, f32), ref, **near)


def test_split_product_keeps_float32s_precision():
    """bfloat16 streams against ``gain * phi`` in three bfloat16 parts:
    the logits' product lies as near the float64 product of the same
    operands as the plain form's ``Precision.HIGHEST`` one does."""
    n, rows = 4, 128
    k = n * D
    x = jax.random.normal(jax.random.PRNGKey(3), (rows, n, D)
                          ).astype(jnp.bfloat16)
    folded = np.asarray(jax.random.normal(jax.random.PRNGKey(4),
                                          (k, n * (n + 2))), np.float32)
    weights, parts = mhc_pallas._phi_rows(
        jnp.ones((k,)), jnp.asarray(folded), x.dtype)
    assert parts == 3 and weights.dtype == jnp.bfloat16
    pieces = np.asarray(weights, np.float64).reshape(3, -1, k)[:, :24]
    np.testing.assert_array_equal(pieces.sum(0).T, folded)  # exact
    flat = x.reshape(rows, k)
    exact = np.asarray(flat, np.float64) @ folded.astype(np.float64)
    got = jnp.dot(flat, weights.T, preferred_element_type=jnp.float32)
    got = np.asarray(got, np.float64).reshape(rows, 3, -1)[..., :24]
    got = (got[:, 2] + got[:, 1]) + got[:, 0]
    plain = np.asarray(jnp.dot(flat.astype(jnp.float32), folded,
                               precision=jax.lax.Precision.HIGHEST))
    scale = np.abs(exact).max()
    assert np.abs(got - exact).max() / scale < 1e-6
    assert np.abs(got - exact).max() <= 2 * np.abs(plain - exact).max()


def test_fits_wants_streams_of_whole_lane_tiles():
    assert mhc_pallas.fits(4, 3584) and mhc_pallas.fits(2, 128)
    assert not mhc_pallas.fits(4, 48) and not mhc_pallas.fits(4, 200)
    assert not mhc_pallas.fits(1, 128)        # one stream has no mixes
    # off a TPU the kernels are never the route
    assert not mhc_pallas.in_kernels(4, 3584)
    # xing4_29b_a4b's two calls: 128 positions, 3.67 MB of streams
    assert mhc_pallas.tile_rows(6784, 4, 3584, 2) == 128
    assert mhc_pallas.tile_rows(512, 4, 3584, 2) == 128
    assert mhc_pallas.tile_rows(40, 4, 128, 4) == 128


def layer_outputs(m, x, route: bool, monkeypatch):
    """A decoder layer's result and what its hyper-connections sow,
    with the kernels' route open (a TPU assumed, kernels interpreted) or
    shut."""
    b, s = x.shape[:2]
    valid = jnp.ones((b, s), bool)
    pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    layer = DecoderLayer(m, "latent_attention", False)
    v = layer.init(jax.random.PRNGKey(8), x, valid, pos)
    with contextlib.ExitStack() as stack:
        if route:
            monkeypatch.setenv("DS2N_ASSUME_TPU", "1")
            stack.enter_context(pltpu.force_tpu_interpret_mode())
        out, state = layer.apply(v, x, valid, pos,
                                 mutable=["intermediates"])
    return out[0], state["intermediates"], v


def test_residual_takes_the_kernels_where_they_fit(monkeypatch):
    """On a TPU with streams of whole lane tiles the layer runs its two
    hyper-connections through the kernels: the same streams, the same
    parameters, and ``h_pre`` / ``h_post`` / ``h_res`` sown under the
    same names with the same shapes."""
    m = model(4, 128)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 5, 4, 128))
    want, sown, v = layer_outputs(m, x, False, monkeypatch)
    calls = []
    read = mhc_pallas.read
    monkeypatch.setattr(mhc_pallas, "read", lambda *a, **kw: (
        calls.append(a[0].shape), read(*a, **kw))[1])
    got, sown_k, v_k = layer_outputs(m, x, True, monkeypatch)
    assert calls == [x.shape] * 2          # both sub-layers
    assert jax.tree.structure(v_k) == jax.tree.structure(v)
    assert jax.tree.structure(sown_k) == jax.tree.structure(sown)
    assert sorted(sown["op_hc"]) == ["h_post", "h_pre", "h_res"]
    for a, w in zip(jax.tree.leaves(sown_k), jax.tree.leaves(sown)):
        assert a.shape == w.shape and a.dtype == w.dtype == jnp.float32
        np.testing.assert_allclose(a, w, rtol=1e-5, atol=2e-6)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_a_gradient_through_the_kernels_is_the_plain_forms(monkeypatch):
    """The kernels have no derivative of their own: a layer on their
    route is differentiated by ``models/mhc.py``'s form from the same
    operands, so a hyper-connection model trains on the chip as it
    does off it."""
    m = model(4, 128)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 5, 4, 128))
    valid = jnp.ones(x.shape[:2], bool)
    pos = jnp.broadcast_to(jnp.arange(5)[None, :], (2, 5))
    layer = DecoderLayer(m, "latent_attention", False)
    v = layer.init(jax.random.PRNGKey(8), x, valid, pos)
    weight = jax.random.normal(jax.random.PRNGKey(10), x.shape)

    def loss(params, x):
        out = layer.apply(dict(v, params=params), x, valid, pos)[0]
        return jnp.sum(out * weight)

    want = jax.grad(loss, argnums=(0, 1))(v["params"], x)
    monkeypatch.setenv("DS2N_ASSUME_TPU", "1")
    calls = []
    write = mhc_pallas.write
    monkeypatch.setattr(mhc_pallas, "write", lambda *a, **kw: (
        calls.append(a[0].shape), write(*a, **kw))[1])
    with pltpu.force_tpu_interpret_mode():
        got = jax.grad(loss, argnums=(0, 1))(v["params"], x)
    assert calls == [x.shape] * 2          # the forward ran the kernels
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5 * scale)


def test_residual_keeps_the_plain_form_where_they_do_not(monkeypatch):
    """Streams of 48 are no whole lane tiles: on a TPU too the layer
    runs ``models/mhc.py``'s functions (a kernel called here, outside
    interpret mode, could not run on this CPU)."""
    m = model(4, 48)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 5, 4, 48))
    want, _, v = layer_outputs(m, x, False, monkeypatch)
    monkeypatch.setenv("DS2N_ASSUME_TPU", "1")
    monkeypatch.setattr(mhc_pallas, "read", None)
    monkeypatch.setattr(mhc_pallas, "write", None)
    valid = jnp.ones(x.shape[:2], bool)
    pos = jnp.broadcast_to(jnp.arange(5)[None, :], (2, 5))
    got = DecoderLayer(m, "latent_attention", False).apply(
        v, x, valid, pos)[0]
    np.testing.assert_array_equal(got, want)
