"""costs/lfm2.py: its operations equal a hand count at the published
widths, six times the parameters a valid position touches, and scale
with what is valid and held, not with what is padded or absent."""

import pytest

import conftest  # noqa: F401  (puts the checkout on sys.path)
from benchmark.costs import lfm2


@pytest.fixture(scope="module")
def model():
    from deepspeech_tpu.config import get_config

    return get_config("lfm2_24b_a2b").model


def test_per_position_operations_are_twice_the_parameters(model):
    d = 2048
    assert lfm2.conv_position_flops(model) == 2 * (d * 6144 + d * d)
    assert lfm2.attention_projection_flops(model) == \
        2 * (2 * d * d + 2 * d * 512)
    assert lfm2.dense_ffn_position_flops(model) == 2 * 3 * d * 11776
    assert lfm2.router_position_flops(model) == 2 * d * 64
    assert lfm2.expert_pair_flops(model) == 2 * 3 * d * 1536
    assert lfm2.head_position_flops(model) == 2 * d * 8192
    # The parameters behind them (ISSUE 30's count): operator 16.8 M or
    # 10.5 M, dense feed-forward 72.4 M, one expert 9.44 M.
    assert lfm2.conv_position_flops(model) / 2 == pytest.approx(16.78e6,
                                                                rel=1e-3)
    assert lfm2.dense_ffn_position_flops(model) / 2 == pytest.approx(
        72.35e6, rel=1e-3)
    assert lfm2.expert_pair_flops(model) / 2 == pytest.approx(9.44e6,
                                                              rel=1e-3)


def test_positions_of_an_utterance(model):
    assert lfm2.prefix_positions(model, 1696) == 212
    assert lfm2.prefix_positions(model, 1201) == 151
    assert lfm2.valid_positions(model, 1650, 59) == 207 + 1 + 59


def test_attention_mixing_counts_the_causal_half(model):
    # Position p attends to p + 1 keys: 1 + 2 + ... + s, over 2048
    # channels, for q k^T and for the probabilities times v.
    assert lfm2.attention_mixing_flops(model, 1) == 2 * 2 * 2048
    assert lfm2.attention_mixing_flops(model, 272) == \
        2 * 2 * 2048 * (272 * 273 // 2)


def test_a_step_needs_three_forwards_of_what_is_valid_and_held(model):
    one = lfm2.utterance_forward_flops(model, 1650, 59)
    s, a = 267, 207
    by_hand = (a * 2 * 1288 * 2048
               + 4 * s * lfm2.conv_position_flops(model)
               + s * lfm2.attention_projection_flops(model)
               + lfm2.attention_mixing_flops(model, s)
               + s * lfm2.dense_ffn_position_flops(model)
               + 4 * s * lfm2.router_position_flops(model)
               + 60 * lfm2.head_position_flops(model))
    assert one == by_hand
    step = lfm2.train_flops_valid(model, [1650] * 128, [59] * 128, 0)
    assert step == 3 * 128 * one
    # Pairs on held experts add their three matrices, nothing else does:
    # the absent experts' share and the padding are not needed work.
    more = lfm2.train_flops_valid(model, [1650] * 128, [59] * 128, 1000)
    assert more - step == 3 * 1000 * lfm2.expert_pair_flops(model)
    # The cell's step: about 31 TFLOP, a tenth of it the held experts.
    frames = [1201 + (450 * i) // 128 for i in range(128)]
    labels = [round(0.036 * f) for f in frames]
    valid = sum(lfm2.valid_positions(model, f, u)
                for f, u in zip(frames, labels))
    pairs = valid * 4 * 8 // 64 * 4
    total = lfm2.train_flops_valid(model, frames, labels, pairs)
    assert total == pytest.approx(31.2e12, rel=0.02)
    assert 3 * pairs * lfm2.expert_pair_flops(model) / total == \
        pytest.approx(0.107, abs=0.005)


@pytest.mark.parametrize("rows", [0, 1850, 14800])
def test_grouped_product_costs(rows):
    k, n, g = 2048, 3072, 8
    fwd = lfm2.gmm_call_cost("moe_gmm", k, n, g, rows)
    assert fwd["flops"] == 2 * rows * k * n
    assert fwd["bytes"] == rows * k * 2 + g * k * n * 2 + rows * n * 2
    back = lfm2.gmm_call_cost("moe_tgmm", k, n, g, rows)
    assert back["flops"] == fwd["flops"]
    assert back["bytes"] == rows * (k + n) * 2 + g * k * n * 2
    # At the cell's rows the call is compute-bound on a v5e (ridge 240
    # operations a byte); with nothing routed only the weights move.
    secs, bound = lfm2.roofline_seconds(fwd, 197e12, 819e9)
    assert bound == ("compute" if rows >= 1850 * 8 else "memory")
    with pytest.raises(ValueError):
        lfm2.gmm_call_cost("gru_scan_fwd", k, n, g, rows)
