"""reference/ds2_ref.py agrees with the model's XLA path at toy width
(float32, so the two differ only by summation order)."""

import dataclasses

import numpy as np
import pytest


@pytest.mark.parametrize("preset", ["ds2_full", "ds2_streaming"])
def test_reference_matches_model(preset):
    import jax

    from benchmark.reference import ds2_ref
    from deepspeech_tpu.config import get_config
    from deepspeech_tpu.models import create_model

    m = dataclasses.replace(get_config(preset).model, rnn_hidden=32,
                            rnn_layers=2, dtype="float32",
                            rnn_impl="xla")
    model = create_model(m)
    rng = np.random.default_rng(0)
    lens = np.array([96, 70, 51], np.int32)
    feats = rng.normal(size=(3, 96, 161)).astype(np.float32)
    feats *= np.arange(96)[None, :, None] < lens[:, None, None]
    v = model.init(jax.random.PRNGKey(0), feats, lens, train=False)
    # Running statistics away from (0, 1), so batch norm matters.
    stats = jax.tree.map(
        lambda a: a + 0.1 * np.abs(rng.normal(size=a.shape)
                                   ).astype(np.float32),
        v["batch_stats"])
    got, got_lens = model.apply(
        {"params": v["params"], "batch_stats": stats}, feats, lens,
        train=False)
    want, want_lens = ds2_ref.forward(m, v["params"], stats, feats, lens)
    assert np.array_equal(np.asarray(got_lens), np.asarray(want_lens))
    err = ds2_ref.relative_error(got, want, np.asarray(want_lens))
    assert err["max_rel"] < 1e-5
    # The measure itself: a 1% perturbation reads as about 1%.
    off = ds2_ref.relative_error(np.asarray(want) * 1.01, want,
                                 np.asarray(want_lens))
    assert off["rms_rel"] == pytest.approx(0.01, rel=1e-3)
