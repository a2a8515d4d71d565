"""costs/axk1.py: its operations equal a hand count at the published
widths, twice the parameters a valid position touches, and scale with
what is valid and held, not with what is padded, idle or absent."""

import pytest

import conftest  # noqa: F401  (puts the checkout on sys.path)
from benchmark.costs import axk1


@pytest.fixture(scope="module")
def model():
    from deepspeech_tpu.config import get_config

    return get_config("ax_k1").model


def test_parameters_are_the_issues_count(model):
    # ISSUE 32: latent attention 11.01 + 18.87 + 4.13 + 8.39 + 58.72 =
    # 101.1 M; one expert 44.04 M.
    assert axk1.attention_params(model) == (
        7168 * 1536 + 1536 * 12288 + 7168 * 576 + 512 * 16384
        + 8192 * 7168)
    assert axk1.attention_params(model) == pytest.approx(101.1e6, rel=1e-3)
    assert axk1.expert_params(model) == 3 * 7168 * 2048
    assert axk1.sparse_layers(model) == 7
    # every layer's attention, the dense layer's 396.4 M, and a router
    # (1.376 M) + the shared expert in each of the 7 expert layers
    assert axk1.position_params(model) == (
        8 * axk1.attention_params(model) + 3 * 7168 * 18432
        + 7 * (7168 * 192 + axk1.expert_params(model)))


def test_a_prefill_position_is_the_issues_3_37_gflop(model):
    # 54,272 positions x 3.37 GFLOP = 183 TFLOP (ISSUE 32), counting a
    # position's even share of routed pairs (8 x 12 / 192 = half a pair
    # a layer) and no mixing.
    per = 2 * axk1.position_params(model) + 2 * 1288 * 7168 \
        + 7 * 0.5 * 2 * axk1.expert_params(model)
    assert per == pytest.approx(3.37e9, rel=0.01)


def test_mixing_counts_the_causal_half_in_the_expanded_form(model):
    per_key = 2 * 64 * (128 + 64 + 128)
    assert axk1.mixing_flops(model, 1) == 8 * per_key
    assert axk1.mixing_flops(model, 267) == 8 * per_key * (267 * 268 // 2)


def test_a_call_needs_what_is_valid_and_held(model):
    one = axk1.stream_flops(model, 1650, 60)
    a, s = 207, 267
    by_hand = (a * 2 * 1288 * 7168 + s * 2 * axk1.position_params(model)
               + axk1.mixing_flops(model, s) + 60 * 2 * 7168 * 20480)
    assert one == by_hand
    # padding of the bucket costs nothing; a longer utterance costs more
    assert axk1.stream_flops(model, 1201, 44) < one
    call = axk1.call_flops_valid(model, [1650, 1201], [60, 44], 1000)
    assert call == one + axk1.stream_flops(model, 1201, 44) \
        + 1000 * 2 * axk1.expert_params(model)


def test_a_grouped_product_reads_only_the_experts_it_hits():
    full = axk1.gmm_call_cost(7168, 4096, 12, 128)
    assert full["flops"] == 2 * 128 * 7168 * 4096
    assert full["bytes"] == 2 * (128 * (7168 + 4096) + 12 * 7168 * 4096)
    fewer = axk1.gmm_call_cost(7168, 4096, 9, 128)
    assert full["bytes"] - fewer["bytes"] == 2 * 3 * 7168 * 4096
    # a decode step's call is bound by its bytes, a prefill's by the MXU
    peak, bw = 197e12, 819e9
    assert axk1.roofline_seconds(full, peak, bw)[1] == "memory"
    prefill = axk1.gmm_call_cost(7168, 4096, 12, 3400)
    assert axk1.roofline_seconds(prefill, peak, bw)[1] == "compute"


def test_a_decode_step_reads_10_7_gb_when_every_expert_is_hit(model):
    # ISSUE 32: 10.8 GB of weights a step, 69% of it the held experts'
    # 7.4 GB; the cache up to 0.68 GB more.
    weights = axk1.decode_step_bytes(model, 84, 0)
    assert weights == pytest.approx(10.74e9, rel=0.01)
    assert 84 * 2 * axk1.expert_params(model) / weights == pytest.approx(
        0.69, abs=0.01)
    full_cache = axk1.decode_step_bytes(model, 84, 256 * 288) - weights
    assert full_cache == pytest.approx(0.68e9, rel=0.01)
    assert axk1.decode_step_bytes(model, 70, 0) < weights
