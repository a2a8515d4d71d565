"""costs/falcon_h1.py: its parameters are ISSUE 49's count at the
published widths (and reproduce the published size of the whole model
and the training floor that does not fit), its cache the state beside
keys, values and convolution inputs, its operations twice the
parameters a valid position touches plus attention's mixing and the
mixer's chunked recurrence on valid positions, and its bytes those of a
decode step that moves every live stream's state twice."""

import dataclasses

import pytest

import conftest  # noqa: F401  (puts the checkout on sys.path)
from benchmark.costs import falcon_h1

D, V = 5120, 261120


@pytest.fixture(scope="module")
def model():
    from deepspeech_tpu.config import get_config

    return get_config("falcon_h1_34b").model


def test_parameters_are_the_issues_count(model):
    # ISSUE 49: attention 13.107 (q) + 2.621 (k) + 2.621 (v) + 13.107
    # (o) = 31.457 M; mixer 47.350 (in) + 20.972 (out) + 0.030 = 68.352
    # M; MLP 3 x 110.100 = 330.301 M; norms 0.010 M; a layer 430.12 M.
    assert falcon_h1.attention_params(model) == 2 * D * 2560 + 2 * D * 512
    assert falcon_h1.attention_params(model) == pytest.approx(31.457e6,
                                                              rel=1e-4)
    assert falcon_h1.conv_channels(model) == 5120
    assert falcon_h1.mixer_matrices(model) == D * 9248 + 4096 * D
    assert falcon_h1.mixer_params(model) - falcon_h1.mixer_matrices(model) \
        == 5 * 5120 + 3 * 32 + 4096
    assert falcon_h1.mixer_params(model) == pytest.approx(68.352e6, rel=1e-4)
    assert falcon_h1.mlp_params(model) == 3 * D * 21504
    assert falcon_h1.layer_params(model) == pytest.approx(430.12e6, rel=1e-5)
    total = falcon_h1.parameters(model)
    assert total == 6 * falcon_h1.layer_params(model) + 2 * V * D \
        + 1288 * D + D
    # 5,261 M parameters, 10.52 GB in bfloat16: 66% of the chip
    assert total == pytest.approx(5261e6, rel=1e-4)
    assert falcon_h1.DOT_BYTES * total == pytest.approx(10.52e9, rel=5e-4)


def test_the_whole_model_is_the_published_size(model):
    """The same formulas at the published depth: 33.64 B (published:
    34B)."""
    whole = dataclasses.replace(model,
                                lfm_layer_types=("ssm_attention",) * 72)
    assert falcon_h1.parameters(whole, num_features=0) \
        == pytest.approx(33.64e9, rel=1e-4)


def test_training_does_not_fit(model):
    """The guide's floors (four layers, an eighth of the vocabulary) at
    16 B a parameter: 32.9 GB, twice the chip."""
    assert falcon_h1.training_floor_bytes(model) \
        == 16 * (4 * falcon_h1.layer_params(model) + 2 * 32640 * D)
    assert falcon_h1.training_floor_bytes(model) \
        == pytest.approx(32.9e9, rel=2e-3)


def test_state_and_cache_at_128_streams(model):
    assert falcon_h1.state_bytes(model) == 32 * 128 * 256 * 4 == 4194304
    parts = falcon_h1.cache_bytes(model, 128, 288)
    assert parts["state"] == pytest.approx(3.22e9, rel=1e-3)
    assert parts["rows"] == 6 * 128 * 288 * 2048
    assert parts["rows"] == pytest.approx(0.45e9, rel=1e-2)
    assert parts["conv"] == 6 * 128 * 3 * 5120 * 2
    assert sum(parts.values()) == pytest.approx(3.70e9, rel=1e-3)
    weights = falcon_h1.DOT_BYTES * falcon_h1.parameters(model)
    assert weights + sum(parts.values()) == pytest.approx(14.2e9, rel=2e-3)


def test_a_decode_steps_bytes_by_part(model):
    """6 x 0.860 GB of layers + 2.67 GB of head + 128 x 6 x 8.39 MB of
    state + the rows in reach: the state is 43-44% at 128 streams, 37%
    at 96 and 28-29% at 64."""
    parts = falcon_h1.decode_step_bytes(model, 128 * 6, 128 * 6 * 210)
    assert parts["weights"] == pytest.approx(6 * 0.860e9, rel=1e-3)
    assert parts["head"] == pytest.approx(2.67e9, rel=2e-3)
    assert parts["state"] == 128 * 6 * 2 * 4194304
    assert parts["state"] == pytest.approx(6.44e9, rel=1e-3)
    assert parts["rows"] == 128 * 6 * 210 * 2048
    assert 0.43 < parts["state"] / sum(parts.values()) < 0.445
    for streams, share in ((96, 0.37), (64, 0.285)):
        p = falcon_h1.decode_step_bytes(model, streams * 6,
                                        streams * 6 * 210)
        assert p["state"] / sum(p.values()) == pytest.approx(share,
                                                             abs=0.01)


def test_the_scan_counts_valid_positions_and_the_causal_half(model):
    """One sequence in one layer: whole chunks of 128 and a ragged
    rest, the causal half of a chunk's two products and a position's
    read of and write to the state."""
    n, p, q = 256, 128, 128
    per_pos = 2 * 2 * 32 * n * p
    pairs = q * (q + 1) // 2
    assert falcon_h1.scan_flops(model, 128) \
        == 2 * pairs * (2 * n + 32 * p) + 128 * per_pos
    rest = 79 * 80 // 2
    assert falcon_h1.scan_flops(model, 207) \
        == 2 * (pairs + rest) * (2 * n + 32 * p) + 207 * per_pos
    assert falcon_h1.scan_flops(model, 0) == 0
    # under 1% of what a prefix position needs in a layer's products
    assert falcon_h1.scan_flops(model, 207) / 207 \
        < 0.01 * 2 * falcon_h1.position_params(model) / 6
    assert falcon_h1.scan_bytes(model, 207) \
        == 207 * 2 * (2 * 4096 + 1024) + 4 * 207 * 32 + 4194304
    assert falcon_h1.step_bytes(model) == 2 * 4194304


def test_a_calls_operations(model):
    """27,136 positions x 5.16 GFLOP = 140 TFLOP if every position were
    valid; the drawn lengths need less, and the state's updates and the
    head count per emitted token."""
    assert 2 * falcon_h1.position_params(model) == pytest.approx(5.16e9,
                                                                 rel=1e-3)
    one = falcon_h1.stream_flops(model, 1696, 60)
    a, s = 212, 272
    assert one == (a * 2 * 1288 * D
                   + s * 2 * falcon_h1.position_params(model)
                   + falcon_h1.mixing_flops(model, s)
                   + 6 * (falcon_h1.scan_flops(model, a)
                          + 60 * 4 * 4096 * 256)
                   + 60 * 2 * D * V)
    assert falcon_h1.call_flops_valid(model, [1696, 1200], [60, 44]) \
        == one + falcon_h1.stream_flops(model, 1200, 44)
    flops, moved = falcon_h1.prefill_scan_cost(model, [1696, 1200])
    assert flops == 6 * (falcon_h1.scan_flops(model, 212)
                         + falcon_h1.scan_flops(model, 150))
    assert moved == 6 * (falcon_h1.scan_bytes(model, 212)
                         + falcon_h1.scan_bytes(model, 150))
