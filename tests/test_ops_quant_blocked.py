"""Blocked-q (s8 weight-streaming) Pallas RNN kernels, interpret mode
on the CPU harness.

The contract under test: the int8 column-streaming kernels compute
the resident-q kernels' gates wherever both apply (matmul columns are
independent, so each block's ``(h @ Q_blk) * sc_blk + bh_blk`` is a
column slice of the resident full product) and agree with them to a
few ulp of the output's range — not to the bit: the two are different
programs, and the compiler contracts the elementwise gate update into
fused multiply-adds differently in each (on this harness XLA:CPU gives
one-ulp differences from IDENTICAL gates) —
match the dequant-outside oracle within the established int8
tolerances, and the regime plumbing — the route's boundaries per stored
width, the serving ladder's streamed-bytes reservation, the analytic
4x stream ratio — prices them correctly.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeech_tpu.models.rnn import gru_scan, lstm_scan
from deepspeech_tpu.ops import rnn_pallas, scan_pallas
from deepspeech_tpu.ops.lstm_pallas import lstm_scan_pallas_q
from deepspeech_tpu.ops.rnn_pallas import gru_scan_pallas_q
from deepspeech_tpu.ops.scan_pallas import block_layout, scan_route


def _variant(cell, hidden, dot_bytes, int8=False, rows=8):
    """The build the route names for a batch call at the Pallas impl."""
    return scan_route(cell, "pallas", rows=rows, hidden=hidden,
                      dot_bytes=dot_bytes, int8=int8).variant


def _rand_gru(rng, b, t, h):
    xproj = jnp.asarray(rng.normal(size=(b, t, 3 * h)), jnp.float32)
    w_h = jnp.asarray(rng.normal(size=(h, 3 * h)) / np.sqrt(h),
                      jnp.float32)
    b_h = jnp.asarray(rng.normal(size=(3 * h,)) * 0.1, jnp.float32)
    lens = rng.integers(1, t + 1, size=b)
    mask = jnp.asarray(np.arange(t)[None] < lens[:, None], jnp.float32)
    return xproj, mask, w_h, b_h


def _rand_lstm(rng, b, t, h):
    xproj = jnp.asarray(rng.normal(size=(b, t, 4 * h)), jnp.float32)
    w_h = jnp.asarray(rng.normal(size=(h, 4 * h)) / np.sqrt(h),
                      jnp.float32)
    b_h = jnp.asarray(rng.normal(size=(4 * h,)) * 0.1, jnp.float32)
    lens = rng.integers(1, t + 1, size=b)
    mask = jnp.asarray(np.arange(t)[None] < lens[:, None], jnp.float32)
    return xproj, mask, w_h, b_h


def _quantize_wh(w_h):
    """Per-output-channel symmetric int8, the utils/quantize.py layout."""
    w = np.asarray(w_h)
    scale = np.abs(w).max(axis=0) / 127.0
    scale = np.where(scale == 0, 1.0, scale)
    q = np.clip(np.rint(w / scale), -127, 127).astype(np.int8)
    return jnp.asarray(q), jnp.asarray(scale.astype(np.float32))


# ---------------------------------------------------------------------------
# blocked-q == resident-q to _ULPS (outputs lie in (-1, 1), where one
# f32 ulp is 1.19e-7). h=16 exercises a
# single zero-padded block (3H=48 -> one 128-col block), h=176 a
# multi-block layout with a padded tail (3H=528 -> 512 + 16).
# ---------------------------------------------------------------------------

_ULPS = dict(rtol=0, atol=5e-7)


@pytest.mark.parametrize("reverse", [False, True])
@pytest.mark.parametrize("h", [16, 176])
def test_gru_blocked_q_matches_resident(reverse, h):
    rng = np.random.default_rng(60)
    xproj, mask, w_h, b_h = _rand_gru(rng, 2, 9, h)
    q, scale = _quantize_wh(w_h)
    ys_res = gru_scan_pallas_q(xproj, mask, q, scale, b_h, reverse,
                               True, None, blocked=False)
    ys_blk = gru_scan_pallas_q(xproj, mask, q, scale, b_h, reverse,
                               True, None, blocked=True)
    np.testing.assert_allclose(np.asarray(ys_res), np.asarray(ys_blk),
                               **_ULPS)


@pytest.mark.parametrize("reverse", [False, True])
@pytest.mark.parametrize("h", [16, 144])  # 4H=64 / 4H=576 -> 2 blocks
def test_lstm_blocked_q_matches_resident(reverse, h):
    rng = np.random.default_rng(61)
    xproj, mask, w_h, b_h = _rand_lstm(rng, 2, 8, h)
    q, scale = _quantize_wh(w_h)
    ys_res = lstm_scan_pallas_q(xproj, mask, q, scale, b_h, reverse,
                                True, None, blocked=False)
    ys_blk = lstm_scan_pallas_q(xproj, mask, q, scale, b_h, reverse,
                                True, None, blocked=True)
    np.testing.assert_allclose(np.asarray(ys_res), np.asarray(ys_blk),
                               **_ULPS)


# ---------------------------------------------------------------------------
# Oracle match + mask semantics (the ragged-tail contract survives the
# (T, G) grid: the elementwise update only fires on the last block).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("reverse", [False, True])
@pytest.mark.parametrize("dot_dtype", [None, "bfloat16"])
def test_gru_blocked_q_matches_dequantized_oracle(reverse, dot_dtype):
    rng = np.random.default_rng(62)
    xproj, mask, w_h, b_h = _rand_gru(rng, 3, 11, 176)
    q, scale = _quantize_wh(w_h)
    w_deq = q.astype(jnp.float32) * scale
    ys_q = gru_scan_pallas_q(xproj, mask, q, scale, b_h, reverse, True,
                             dot_dtype, blocked=True)
    ys_o = gru_scan(xproj, mask, w_deq, b_h, reverse=reverse,
                    dot_dtype=None if dot_dtype is None else jnp.bfloat16)
    tol = 1e-5 if dot_dtype is None else 2e-2
    np.testing.assert_allclose(np.asarray(ys_q), np.asarray(ys_o),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("reverse", [False, True])
def test_lstm_blocked_q_matches_dequantized_oracle(reverse):
    rng = np.random.default_rng(63)
    xproj, mask, w_h, b_h = _rand_lstm(rng, 3, 10, 144)
    q, scale = _quantize_wh(w_h)
    w_deq = q.astype(jnp.float32) * scale
    ys_q = lstm_scan_pallas_q(xproj, mask, q, scale, b_h, reverse, True,
                              None, blocked=True)
    ys_o = lstm_scan(xproj, mask, w_deq, b_h, reverse=reverse)
    np.testing.assert_allclose(np.asarray(ys_q), np.asarray(ys_o),
                               rtol=1e-5, atol=1e-5)


def test_gru_blocked_q_respects_mask():
    rng = np.random.default_rng(64)
    xproj, mask, w_h, b_h = _rand_gru(rng, 2, 10, 16)
    q, scale = _quantize_wh(w_h)
    ys = np.asarray(gru_scan_pallas_q(xproj, mask, q, scale, b_h,
                                      False, True, None, blocked=True))
    lens = np.asarray(mask).sum(axis=1).astype(int)
    for b in range(2):
        for t in range(lens[b], 10):
            np.testing.assert_allclose(ys[b, t], ys[b, lens[b] - 1],
                                       rtol=1e-6)


def test_blocked_q_auto_dispatch(monkeypatch):
    """With the residency budget forced to 0 the q entry points pick
    the blocked kernel on their own (no ``blocked=`` hint) and still
    produce the resident answer (to ``_ULPS``)."""
    rng = np.random.default_rng(65)
    xproj, mask, w_h, b_h = _rand_gru(rng, 2, 7, 16)
    q, scale = _quantize_wh(w_h)
    ys_res = gru_scan_pallas_q(xproj, mask, q, scale, b_h, False, True)
    monkeypatch.setattr(scan_pallas, "VMEM_WEIGHT_BUDGET", 0)
    assert _variant("gru", 16, 4, int8=True) == "blocked_q"
    ys_auto = gru_scan_pallas_q(xproj, mask, q, scale, b_h, False, True)
    np.testing.assert_allclose(np.asarray(ys_res), np.asarray(ys_auto),
                               **_ULPS)


def test_models_rnn_routes_qdict_every_h(monkeypatch):
    """models/rnn threads a qdict into the q kernel even when the
    budget says blocked (pre-PR it dequantized to an fp working copy
    there); the kernel sees the int8 leaf, not a dequantized array."""
    from deepspeech_tpu.config import get_config
    from deepspeech_tpu.models.rnn import _run_direction

    calls = []
    real = rnn_pallas.gru_scan_pallas_q

    def spy(xp, m, wq, sc, bh, *a, **kw):
        calls.append(wq.dtype)
        return real(xp, m, wq, sc, bh, *a, **kw)

    monkeypatch.setattr(rnn_pallas, "gru_scan_pallas_q", spy)
    monkeypatch.setattr(scan_pallas, "VMEM_WEIGHT_BUDGET", 0)
    cfg = dataclasses.replace(get_config("ds2_small").model,
                              rnn_impl="pallas", rnn_hidden=16,
                              dtype="float32")
    rng = np.random.default_rng(66)
    xproj, mask, w_h, b_h = _rand_gru(rng, 2, 6, 16)
    q, scale = _quantize_wh(w_h)
    ys = _run_direction(cfg, xproj, mask, {"q": q, "scale": scale},
                        b_h, False)
    assert calls == [jnp.int8]
    w_deq = q.astype(jnp.float32) * scale
    ys_o = gru_scan(xproj, mask, w_deq, b_h)
    np.testing.assert_allclose(np.asarray(ys), np.asarray(ys_o),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Regime boundaries: residency is a function of the STORED width. These
# pins are the dtype-aware contract of the route the Inferencer and the
# ladder both price against.
# ---------------------------------------------------------------------------

def test_fits_vmem_dtype_boundaries():
    # Flagship H=1760: f32 GRU streams (37.2 MB), int8 GRU is newly
    # resident (9.3 MB), int8 LSTM streams (12.4 MB > 10 MB).
    assert _variant("gru", 1760, 4) == "blocked"
    assert _variant("gru", 1760, 4, int8=True) == "resident_q"
    assert _variant("lstm", 1760, 4, int8=True) == "blocked_q"
    # First blocked H per cell at 1-byte storage.
    assert [_variant("gru", h, 2, int8=True) for h in (1869, 1870)] == [
        "resident_q", "blocked_q"]
    assert [_variant("lstm", h, 2, int8=True) for h in (1619, 1620)] == [
        "resident_q", "blocked_q"]


def test_use_blocked_stored_width():
    # fp kernels: regime follows the MXU operand width.
    assert _variant("gru", 1760, 4) == "blocked"
    assert _variant("gru", 1760, 2) == "pinned"
    # q kernels: the s8 array is what streams — its one byte
    # overrides the dot width, so int8 H=1760 GRU stays resident.
    assert _variant("gru", 1760, 2, int8=True) == "resident_q"
    assert _variant("gru", 1870, 2, int8=True) == "blocked_q"
    assert _variant("lstm", 1760, 2, int8=True) == "blocked_q"


def test_kernel_regime_per_replica():
    from deepspeech_tpu.config import get_config
    from deepspeech_tpu.utils.quantize import kernel_regime

    base = get_config("ds2_small").model
    gru = dataclasses.replace(base, rnn_impl="pallas", rnn_hidden=1760)
    lstm = dataclasses.replace(gru, rnn_type="lstm")
    assert kernel_regime(gru, quantized=False) == "fp"
    assert kernel_regime(gru, quantized=True) == "resident-q"
    assert kernel_regime(lstm, quantized=True) == "blocked-q"
    assert kernel_regime(
        dataclasses.replace(gru, rnn_hidden=1870), True) == "blocked-q"


# ---------------------------------------------------------------------------
# The streamed-bytes economics: 4x less per-step HBM traffic, and the
# taller bulk ladder it buys. Analytic (padded block layout), so it
# holds on the CPU harness without the AOT toolchain.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_gates", [3, 4])
def test_blocked_stream_ratio_at_flagship(n_gates):
    h = 1760
    n_blocks, c = block_layout(n_gates * h)
    step_s8 = n_blocks * c * h * 1
    step_f32 = n_blocks * c * h * 4
    assert step_f32 / step_s8 >= 3.5  # the PR's acceptance floor
    # Padding overhead stays small: streamed columns within 12% of 3H.
    assert n_blocks * c < 1.12 * n_gates * h


@pytest.mark.parametrize("rnn_type,n_gates", [("gru", 3), ("lstm", 4)])
def test_stream_ladder_bulk_rises(rnn_type, n_gates):
    """The bench's streamed-bytes leg, pinned: charging the s8 stream
    term (or zero once int8 is resident) instead of the old fp working
    copy strictly raises the bulk rung under the identical budget."""
    from deepspeech_tpu.serving import (recurrent_stream_bytes,
                                        tier_max_batches)

    h = 1760
    wq = n_gates * h * h
    stream_premium = recurrent_stream_bytes(h, n_gates, 4)
    stream_s8 = recurrent_stream_bytes(h, n_gates, 1)
    assert stream_premium == 4 * wq  # f32 misses residency at H=1760
    # GRU int8 is newly resident (no stream term); LSTM int8 streams
    # its stored bytes — either way 4x less than the fp working copy.
    assert stream_s8 == (0 if rnn_type == "gru" else wq)
    report = {"bytes_before": 4 * wq, "bytes_after": wq}
    per_row = wq // 32
    budget = 4 * wq + stream_premium + 8 * per_row
    ladder_s8 = tier_max_batches(
        report, per_row, budget,
        stream_bytes={"premium": stream_premium, "bulk": stream_s8})
    ladder_fp = tier_max_batches(
        report, per_row, budget,
        stream_bytes={"premium": stream_premium,
                      "bulk": stream_premium})
    assert ladder_s8["bulk"] > ladder_fp["bulk"] > 0
    assert ladder_s8["bulk"] > ladder_s8["premium"] > 0
    assert ladder_s8["premium"] == ladder_fp["premium"]


def test_recurrent_stream_bytes_validates():
    from deepspeech_tpu.serving import recurrent_stream_bytes

    assert recurrent_stream_bytes(800, 3, 4) == 0  # resident
    assert recurrent_stream_bytes(1760, 3, 4, layers=2,
                                  directions=2) == 4 * 3 * 1760 * 1760 * 4
    with pytest.raises(ValueError):
        recurrent_stream_bytes(0, 3, 4)
    with pytest.raises(ValueError):
        recurrent_stream_bytes(1760, 3, 0)
