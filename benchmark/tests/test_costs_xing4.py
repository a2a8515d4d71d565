"""costs/xing4.py: its parameters are ISSUE 39's count at the published
widths, its operations twice the parameters an EMITTED position of the
model touches (the draft pass counts for nothing), and its bytes those
of a step that drafts."""

import pytest

import conftest  # noqa: F401  (puts the checkout on sys.path)
from benchmark.costs import axk1, xing4

D, V = 3584, 131072


@pytest.fixture(scope="module")
def model():
    from deepspeech_tpu.config import get_config

    return get_config("xing4_29b_a4b").model


def test_parameters_are_the_issues_count(model):
    # ISSUE 39: latent attention 2.75 + 4.72 + 2.06 + 4.19 + 14.68 =
    # 28.4 M; an expert 11.01 M; hyper-connections 2 x 14336 x 24 =
    # 0.69 M a layer (+ the two norms' gains here).
    assert xing4.attention_params(model) == (
        D * 768 + 768 * 32 * 192 + D * 576 + 512 * 32 * 256 + 32 * 128 * D)
    assert xing4.attention_params(model) == pytest.approx(28.4e6, rel=1e-3)
    assert xing4.expert_params(model) == 3 * D * 1024
    assert xing4.hc_params(model) == 14336 * 24 + 14336
    assert xing4.sparse_layers(model) == 6 and xing4.sub_layers(model) == 16
    dense = xing4.attention_params(model) + 3 * D * 9216 \
        + 2 * xing4.hc_params(model)
    assert dense == pytest.approx(128.2e6, rel=2e-3)
    layer = xing4.attention_params(model) + D * 64 \
        + 65 * xing4.expert_params(model) + 2 * xing4.hc_params(model)
    assert layer == pytest.approx(744.9e6, rel=1e-3)
    module = xing4.draft_params(model) + 64 * xing4.expert_params(model)
    assert module == layer + 2 * D * D == pytest.approx(770.6e6, rel=1e-3)
    total = xing4.parameters(model)
    assert total == 2 * V * D + 1288 * D + dense + 6 * layer + module
    # 6,312 M parameters, 12.6 GB in bfloat16
    assert total == pytest.approx(6312e6, rel=1e-3)
    assert xing4.DOT_BYTES * total == pytest.approx(12.6e9, rel=5e-3)
    assert xing4.position_params(model) == dense + 6 * (
        layer - 64 * xing4.expert_params(model))


def test_one_layer_less_is_the_rules_other_depth(model):
    import dataclasses

    shallower = dataclasses.replace(
        model, lfm_layer_types=model.lfm_layer_types[:-1])
    assert xing4.DOT_BYTES * xing4.parameters(shallower) \
        == pytest.approx(11.1e9, rel=1e-2)      # 1 + 5: ISSUE 39


def test_a_prefill_position_is_the_issues_active_parameters(model):
    # ISSUE 39: ~0.74 G active parameters a position, module included:
    # the model's 0.634 G is what a NEEDED position counts.
    active = xing4.position_params(model) + 6 * 4 * xing4.expert_params(model)
    assert active == pytest.approx(0.634e9, rel=5e-3)
    with_module = active + xing4.draft_params(model) \
        + 4 * xing4.expert_params(model)
    assert with_module == pytest.approx(0.744e9, rel=5e-3)


def test_a_call_needs_what_plain_greedy_needs(model):
    one = xing4.stream_flops(model, 1650, 60)
    a, s = 207, 267
    by_hand = (a * 2 * 1288 * D + s * 2 * xing4.position_params(model)
               + axk1.mixing_flops(model, s) + 60 * 2 * D * V)
    assert one == by_hand
    assert xing4.stream_flops(model, 1201, 44) < one
    call = xing4.call_flops_valid(model, [1650, 1201], [60, 44], 1000)
    assert call == one + xing4.stream_flops(model, 1201, 44) \
        + 1000 * 2 * xing4.expert_params(model)
    # the draft module is in no term: a preset without it needs the same
    import dataclasses
    assert xing4.call_flops_valid(
        dataclasses.replace(model, lm_draft_layers=0),
        [1650, 1201], [60, 44], 1000) == call


def test_a_drafting_step_reads_the_module_and_the_head_twice(model):
    # ISSUE 39: 12.6 GB of weights - the embedding + the head again =
    # 12.6 GB a step when every expert is hit; cache rows beside.
    every = 7 * 64
    weights = xing4.decode_step_bytes(model, every, 0)
    held = xing4.DOT_BYTES * xing4.parameters(model)
    assert weights == pytest.approx(held - 2 * 1288 * D, rel=1e-9)
    assert weights == pytest.approx(12.6e9, rel=5e-3)
    rows = 256 * 260
    assert xing4.decode_step_bytes(model, every, rows) - weights \
        == 2 * 8 * rows * 576
    # an expert without a pair needs nothing
    assert weights - xing4.decode_step_bytes(model, every - 10, 0) \
        == 10 * 2 * xing4.expert_params(model)


def test_a_grouped_product_of_64_groups(model):
    # a drafting step's up product: 2,048 rows, every expert hit
    cost = xing4.gmm_call_cost(D, 2048, 64, 2048)
    assert cost["flops"] == 2 * 2048 * D * 2048
    assert cost["bytes"] == 2 * (2048 * (D + 2048) + 64 * D * 2048)
    t, bound = xing4.roofline_seconds(cost, 197e12, 819e9)
    assert bound == "memory" and t == pytest.approx(1.175e-3, rel=1e-2)
    # a prefill sub-batch's: 27,136 rows, compute-bound
    t, bound = xing4.roofline_seconds(
        xing4.gmm_call_cost(D, 2048, 64, 27136), 197e12, 819e9)
    assert bound == "compute"


def test_hyper_connections_read_and_write_the_streams_once(model):
    # a prefill sub-batch: [6784, 4, 3584] bf16 = 194 MB, read + written
    # by each of 16 sub-layers
    assert xing4.mhc_bytes(model, 6784) == 16 * 2 * 6784 * 4 * D * 2
    assert xing4.mhc_bytes(model, 6784) / 32 == pytest.approx(194.5e6,
                                                              rel=1e-3)
