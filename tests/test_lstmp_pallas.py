"""The fused LSTM-with-projection kernels (ops/lstm_pallas.py
``lstmp_scan_pallas``, interpret mode) against the XLA scan oracle
(models/rnn.py ``lstmp_scan``): forward, every gradient, masks, with
and without layer normalisation, float32 and bfloat16 operands, and
the routing that chooses between them."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeech_tpu.models.rnn import lstmp_scan
from deepspeech_tpu.ops import lstm_pallas
from deepspeech_tpu.ops.lstm_pallas import lstmp_scan_pallas


def _case(seed=0, b=8, t=9, h=16, p=8):
    rng = np.random.default_rng(seed)
    f32 = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa
    lens = np.array([t, t, 7, 5, 3, 1, t, 2][:b])
    mask = jnp.asarray(np.arange(t)[None, :] < lens[:, None], jnp.float32)
    return {"xp": f32(b, t, 4 * h), "mask": mask, "w_r": 0.3 * f32(p, 4 * h),
            "w_p": 0.3 * f32(h, p), "scale": 1 + 0.1 * f32(4 * h),
            "bias": 0.1 * f32(4 * h), "dy": f32(b, t, p), "lens": lens}


@pytest.mark.parametrize("layer_norm", [True, False])
def test_forward_and_gradients_match_the_scan(layer_norm):
    c = _case()
    ln = (c["scale"], c["bias"]) if layer_norm else (None, None)
    diff = (c["xp"], c["w_r"], c["w_p"]) + (ln if layer_norm else ())

    def total(fn):
        def f(xp, w_r, w_p, *ln_):
            ys = fn(xp, c["mask"], w_r, w_p, *(ln_ or (None, None)))
            return jnp.sum(ys * c["dy"]), ys
        return jax.value_and_grad(f, argnums=tuple(range(len(diff))),
                                  has_aux=True)

    (_, want), g_want = total(lstmp_scan)(*diff)
    (_, got), g_got = total(
        lambda *a: lstmp_scan_pallas(*a, True, None))(*diff)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    # Masked frames repeat the last valid output and pass no gradient
    # into their own input projection.
    for i, n in enumerate(c["lens"]):
        np.testing.assert_array_equal(
            got[i, n:], jnp.broadcast_to(got[i, n - 1], got[i, n:].shape))
        assert not np.asarray(g_got[0][i, n:]).any()


def test_bfloat16_operands_match_the_scans_mixed_precision():
    """bf16 xproj and matmul operands, float32 state: both paths round
    the same operands, so they agree far inside bf16's own 2^-9."""
    c = _case(seed=1)
    xp = c["xp"].astype(jnp.bfloat16)
    want = lstmp_scan(xp, c["mask"], c["w_r"], c["w_p"], c["scale"],
                      c["bias"], dot_dtype=jnp.bfloat16)
    got = lstmp_scan_pallas(xp, c["mask"], c["w_r"], c["w_p"], c["scale"],
                            c["bias"], True, "bfloat16")
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)

    def loss(fn):
        return jax.grad(lambda w_r, w_p: jnp.sum(
            fn(xp, c["mask"], w_r, w_p) * c["dy"]), argnums=(0, 1))

    g_want = loss(lambda x, m, a, b: lstmp_scan(
        x, m, a, b, c["scale"], c["bias"], dot_dtype=jnp.bfloat16))(
            c["w_r"], c["w_p"])
    g_got = loss(lambda x, m, a, b: lstmp_scan_pallas(
        x, m, a, b, c["scale"], c["bias"], True, "bfloat16"))(
            c["w_r"], c["w_p"])
    for a, b in zip(g_got, g_want):
        assert float(jnp.sqrt(jnp.mean((a - b) ** 2))
                     / jnp.sqrt(jnp.mean(b ** 2))) < 0.02


def test_vmem_budget_at_the_published_widths():
    """rnnt_he2019 at b=64: bf16 weights are 13.1 MB (over the other
    scan kernels' 10 MB budget); both kernels fit the raised limit,
    float32 weights at a large batch do not and route to the scan."""
    from deepspeech_tpu.ops.scan_pallas import scan_route

    def route(rows, dot_bytes, backward=False):
        return scan_route("lstmp", "pallas", rows=rows, hidden=2048,
                          proj=640, dot_bytes=dot_bytes, backward=backward)

    assert (640 * 8192 + 2048 * 640) * 2 == 13_107_200
    # a call asks for its need and a quarter: under 32 / 48 MiB of need
    assert route(64, 2).vmem_limit < 32 * 2 ** 20 * 5 // 4
    assert route(64, 2, True).vmem_limit < 48 * 2 ** 20 * 5 // 4
    assert route(64, 2).kernel == "lstmp_scan_fwd"
    assert route(256, 4).kernel is None


def test_layer_routes_by_impl_rows_and_carry(monkeypatch):
    """'pallas' takes the kernel for a whole sequence with
    sublane-aligned rows; odd row counts, the carried one-step path and
    'xla' take the scan. Same numbers either way."""
    from deepspeech_tpu.config import get_config
    from deepspeech_tpu.models.rnn import LSTMPLayer

    calls = []
    real = lstm_pallas.lstmp_scan_pallas
    monkeypatch.setattr(
        lstm_pallas, "lstmp_scan_pallas",
        lambda *a: calls.append(a[0].shape) or real(*a))
    mcfg = dataclasses.replace(
        get_config("rnnt_he2019").model, rnn_hidden=16, rnn_proj=8,
        dtype="float32")
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(8, 6, 5)), jnp.float32)
    mask = jnp.ones((8, 6), jnp.float32)

    def layer(impl):
        return LSTMPLayer(dataclasses.replace(mcfg, rnn_impl=impl), 16, 8)

    variables = layer("xla").init(jax.random.PRNGKey(0), x, mask)
    want = layer("xla").apply(variables, x, mask)
    assert not calls
    got = layer("pallas").apply(variables, x, mask)
    assert calls == [(8, 6, 64)]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    layer("pallas").apply(variables, x[:5], mask[:5])       # 5 rows
    cr0 = (jnp.zeros((8, 16)), jnp.zeros((8, 8)))
    ys, _ = layer("pallas").apply(variables, x, mask, cr0=cr0,
                                  return_final=True)        # carried
    assert len(calls) == 1
    np.testing.assert_allclose(ys, want, rtol=1e-5, atol=1e-6)
