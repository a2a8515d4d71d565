"""costs/smallthinker.py: ISSUE 45's arithmetic (659.8 M parameters
held, 10.56 GB of state, 21.5 B whole), per-position operations as
twice the parameters, keys in reach by layer kind, a step as three
forwards of what is valid and held, and the attention kernels' cost
from their own facts."""

import pytest

import conftest  # noqa: F401  (puts the checkout on sys.path)
from benchmark.costs import smallthinker as cost


@pytest.fixture(scope="module")
def model():
    from deepspeech_tpu.config import get_config

    return get_config("smallthinker_21b_a3b").model


def test_the_issues_arithmetic(model):
    assert cost.attention_params(model) == 2 * 2560 * 3584 + 2 * 2560 * 512
    assert cost.attention_params(model) / 1e6 == pytest.approx(20.97,
                                                               abs=0.005)
    assert cost.router_params(model) == 163840
    assert cost.expert_params(model) / 1e6 == pytest.approx(5.898,
                                                            abs=0.0005)
    assert cost.layer_params(model, 16) / 1e6 == pytest.approx(115.5,
                                                               abs=0.05)
    assert 4 * cost.layer_params(model, 16) / 1e6 == pytest.approx(
        462.0, abs=0.05)
    assert 2 * 37984 * 2560 / 1e6 == pytest.approx(194.5, abs=0.05)
    assert cost.parameters(model) / 1e6 == pytest.approx(659.8, abs=0.05)
    assert cost.state_bytes(model) / 1e9 == pytest.approx(10.56, abs=0.005)


def test_the_whole_model_by_the_same_formulas(model):
    assert cost.layer_params(model, 64) / 1e6 == pytest.approx(398.6,
                                                               abs=0.05)
    whole = cost.published_parameters(model, 52, 151936)
    assert whole / 1e9 == pytest.approx(21.5, abs=0.05)


def test_the_configuration_file_states_the_same(model):
    import json
    import os

    with open(os.path.join(conftest.ROOT, "benchmark", "configs",
                           "smallthinker_21b_a3b.json")) as f:
        stated = json.load(f)
    assert "659.8 M parameters x 16 B = 10.56 GB" in stated["parameters"]
    assert "= 21.5 B" in stated["parameters"]
    assert stated["moe_num_primary_experts"] == model.experts_held
    assert stated["published"]["moe_num_primary_experts"] \
        == model.lfm_experts
    assert stated["vocab_size"] * 4 == stated["published"]["vocab_size"]


def test_keys_in_reach_by_layer_kind(model):
    assert cost.pairs_in_reach(6784, 0) == 6784 * 6785 // 2
    assert cost.pairs_in_reach(6784, 4096) == \
        4096 * 4097 // 2 + (6784 - 4096) * 4096
    assert cost.pairs_in_reach(100, 4096) == 100 * 101 // 2
    # ISSUE 45: mean reach 2,707 keys on a sliding layer and 3,020 on
    # the global one at 6,039 valid positions
    reach = cost.keys_in_reach(model, 6039)
    assert reach["sliding_attention"] / 6039 == pytest.approx(2707, abs=1)
    assert reach["full_attention"] / 6039 == pytest.approx(3020, abs=1)
    per_pair = 4 * 28 * 128
    assert cost.mixing_flops(model, 6039) == per_pair * (
        3 * reach["sliding_attention"] + reach["full_attention"])


def test_a_position_and_a_pair(model):
    # ISSUE 45's 60 MFLOP a position and layer: projections 41.9,
    # router 0.3, 1.5 held experts of the 6 chosen 17.7
    assert cost.position_flops(model) / 4 / 1e6 == pytest.approx(
        41.9 + 0.3, abs=0.1)
    assert 1.5 * 2 * cost.expert_params(model) / 1e6 == pytest.approx(
        17.7, abs=0.05)


def test_a_step_needs_three_forwards_of_what_is_valid_and_held(model):
    one = cost.utterance_forward_flops(model, 37500, 1350)
    a, s = 4688, 4688 + 1351
    assert one == (a * 2 * 1288 * 2560 + s * cost.position_flops(model)
                   + cost.mixing_flops(model, s)
                   + 1351 * 2 * 2560 * 37984)
    step = cost.train_flops_valid(model, [37500] * 4, [1350] * 4, 36000)
    assert step == 3 * (4 * one + 36000 * 2 * cost.expert_params(model))
    # padding and the absent experts' share count for nothing
    assert cost.train_flops_valid(model, [37500], [1350], 0) == 3 * one
    # ISSUE 45's forecast: about 35 TFLOP a step
    assert 30e12 < cost.train_flops_valid(
        model, [37500] * 4, [1350] * 4, 4 * 36234) < 36e12


@pytest.mark.parametrize("kernel, products, wide, narrow", [
    ("gqa_attn_fwd", 2, 2, 2), ("gqa_attn_bwd_dq", 2, 3, 2),
    ("gqa_attn_bwd_dkv", 2, 2, 4)])
@pytest.mark.parametrize("window", [4096, 0])
def test_an_attention_calls_cost_from_its_facts(kernel, products, wide,
                                                narrow, window):
    facts = {"kernel": kernel, "b": "4", "s": "6784", "kv": "4",
             "rep": "7", "head": "128", "window": str(window),
             "q_tile": "256", "k_tile": "512", "key_tiles": "171"}
    got = cost.attn_call_cost(facts)
    pairs = cost.pairs_in_reach(6784, window)
    assert got["flops"] == products * 2 * 128 * 4 * 28 * pairs
    assert got["bytes"] == 2 * 4 * 6784 * (wide * 3584 + narrow * 512)
    # never more than the tiles the grid computes hold
    tiles = {4096: 171, 0: 196}[window]
    assert pairs <= tiles * 256 * 512
