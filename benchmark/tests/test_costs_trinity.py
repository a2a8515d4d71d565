"""costs/trinity.py: its parameters are ISSUE 41's count at the
published widths (and reproduce the published size of the whole model),
its cache a ring a sliding layer beside a full cache, its operations
twice the parameters a valid position touches plus attention's mixing
over the KEYS IN REACH of each layer kind, and its bytes those of a
decode step that reads the rows in reach."""

import dataclasses

import pytest

import conftest  # noqa: F401  (puts the checkout on sys.path)
from benchmark.costs import trinity

D, V, W = 3072, 25024, 4096


@pytest.fixture(scope="module")
def model():
    from deepspeech_tpu.config import get_config

    return get_config("trinity_large").model


def test_parameters_are_the_issues_count(model):
    # ISSUE 41: attention 18.87 (q) + 3.15 (k) + 3.15 (v) + 18.87 (o) +
    # 18.87 (gate) = 62.9 M; dense feed-forward 113.2 M; an expert
    # 28.31 M; router 0.79 M.
    assert trinity.head_dim(model) == 128
    assert trinity.attention_params(model) == 3 * D * 6144 + 2 * D * 1024
    assert trinity.attention_params(model) == pytest.approx(62.9e6, rel=1e-3)
    assert trinity.expert_params(model) == 3 * D * 3072
    assert trinity.expert_params(model) == pytest.approx(28.31e6, rel=1e-3)
    assert trinity.sparse_layers(model) == 4
    dense = trinity.attention_params(model) + 3 * D * 12288
    assert dense == pytest.approx(176.2e6, rel=1e-3)
    sparse = trinity.attention_params(model) + D * 256 \
        + 33 * trinity.expert_params(model)
    assert sparse == pytest.approx(998.0e6, rel=1e-3)
    total = trinity.parameters(model)
    assert total == 2 * V * D + 1288 * D + dense + 4 * sparse
    # 4,326 M parameters, 8.65 GB in bfloat16
    assert total == pytest.approx(4326e6, rel=1e-3)
    assert trinity.DOT_BYTES * total == pytest.approx(8.65e9, rel=2e-3)
    assert trinity.position_params(model) == dense + 4 * (
        sparse - 32 * trinity.expert_params(model))
    # without the gate (the driver's 73 M a layer less the gate) the
    # count misses the published size; with it:
    no_gate = dataclasses.replace(model, lfm_attn_gate=False)
    assert trinity.attention_params(model) \
        - trinity.attention_params(no_gate) == D * 6144


def test_the_whole_model_is_the_published_size(model):
    """The same formulas at the published depth, experts and
    vocabulary: 398.6 B in all (published: 400 B), 13.4 B active
    (published: A13B)."""
    whole = dataclasses.replace(
        model, lfm_layer_types=("sliding_attention",) * 60,
        lfm_dense_layers=6, experts_held=256, vocab_size=200192)
    total = trinity.parameters(whole, num_features=0)
    assert total == pytest.approx(398.6e9, rel=1e-3)
    active = trinity.position_params(whole) \
        + 54 * 4 * trinity.expert_params(whole) + 2 * 200192 * D
    assert active == pytest.approx(13.4e9, rel=1e-2)


def test_a_second_period_does_not_fit(model):
    deeper = dataclasses.replace(
        model, lfm_layer_types=model.lfm_layer_types
        + model.lfm_layer_types[1:])
    assert trinity.DOT_BYTES * trinity.parameters(deeper) \
        == pytest.approx(16.6e9, rel=5e-3)      # 1 + 8: ISSUE 41


def test_the_cache_is_four_rings_and_a_full_cache(model):
    assert trinity.cache_row_bytes(model) == 2 * 8 * 128 * 2 == 4096
    stream = trinity.cache_bytes(model, 1, 6784)
    assert stream == (4 * W + 6784) * 4096
    assert stream == pytest.approx(94.9e6, rel=1e-3)
    assert trinity.cache_bytes(model, 16, 6784) \
        == pytest.approx(1.52e9, rel=2e-3)
    # every layer a full cache: 2.2 GB
    full = dataclasses.replace(model, lfm_window=1 << 20)
    assert trinity.cache_bytes(full, 16, 6784) == 16 * 5 * 6784 * 4096
    # a cache shorter than the window makes rings of its own length
    assert trinity.cache_bytes(model, 1, 1024) == 5 * 1024 * 4096


def test_keys_in_reach(model):
    reach = trinity.keys_in_reach(model, 5250)
    assert reach["full_attention"] == 5250 * 5251 // 2
    assert reach["sliding_attention"] == W * (W + 1) // 2 + (5250 - W) * W
    short = trinity.keys_in_reach(model, 100)
    assert short["sliding_attention"] == short["full_attention"] == 5050
    # the window saves about a tenth of four layers' prefill scores
    saved = 1 - reach["sliding_attention"] / reach["full_attention"]
    assert 0.04 < saved < 0.12
    per_key = 4 * 48 * 128
    assert trinity.mixing_flops(model, 5250) == per_key * (
        4 * reach["sliding_attention"] + reach["full_attention"])
    # the steps' share: positions 5250 .. 6762 see W keys in a sliding
    # layer and everything in the global one
    steps = trinity.mixing_flops(model, 6763, start=5250)
    assert steps == per_key * (4 * 1513 * W + sum(range(5251, 6764)))


def test_a_call_needs_its_valid_positions(model):
    one = trinity.stream_flops(model, 42000, 1513)
    a, s = 5250, 6763
    by_hand = (a * 2 * 1288 * D + s * 2 * trinity.position_params(model)
               + trinity.mixing_flops(model, s) + 1513 * 2 * D * V)
    assert one == by_hand
    assert trinity.stream_flops(model, 33001, 1189) < one
    call = trinity.call_flops_valid(model, [42000, 33001], [1513, 1189],
                                    1000)
    assert call == one + trinity.stream_flops(model, 33001, 1189) \
        + 1000 * 2 * trinity.expert_params(model)
    # ISSUE 41: prefill of 84,000 positions x (1.2 GFLOP of products +
    # 0.35 GFLOP of attention) = 130 TFLOP
    active = trinity.position_params(model) \
        + 4 * 4 * 32 / 256 * trinity.expert_params(model)
    assert 2 * active == pytest.approx(1.2e9, rel=0.1)
    assert trinity.prefill_attention_flops(model, [42000]) / 5250 \
        == pytest.approx(0.35e9, rel=0.15)


def test_a_decode_step_reads_weights_touched_experts_and_rows_in_reach(
        model):
    # ISSUE 41: 0.35 (dense) + 4 x (0.18 outside experts + about 7
    # touched experts x 56.6 MB) + 0.15 (head) = 2.9 GB of weights
    weights = trinity.decode_step_bytes(model, 4 * 7, 0)
    assert weights == pytest.approx(2.9e9, rel=0.05)
    none = trinity.decode_step_bytes(model, 0, 0)
    assert none == 2 * (trinity.position_params(model) + D * V)
    assert weights - none == 28 * 2 * trinity.expert_params(model)
    assert 2 * trinity.expert_params(model) == pytest.approx(56.6e6, rel=1e-3)
    # 16 streams far past the window: 4 x 4096 + the position, 4 kB a row
    rows = 16 * (4 * W + 6000)
    assert trinity.decode_step_bytes(model, 0, rows) - none == rows * 4096
    assert trinity.decode_attention_bytes(model, rows) \
        == pytest.approx(1.47e9, rel=1e-2)
    # 4.3-4.4 GB a step, 5.3 ms at 819 GB/s
    step = trinity.decode_step_bytes(model, 28, rows)
    assert step / 819e9 == pytest.approx(5.3e-3, rel=0.05)


def test_a_grouped_product_of_32_groups_mostly_empty(model):
    # a decode step's up product: 2 routed rows in 2 of 32 groups
    cost = trinity.gmm_call_cost(D, 6144, 2, 2)
    assert cost["flops"] == 2 * 2 * D * 6144
    assert cost["bytes"] == 2 * (2 * (D + 6144) + 2 * D * 6144)
    t, bound = trinity.roofline_seconds(cost, 197e12, 819e9)
    assert bound == "memory" and t == pytest.approx(92.2e-6, rel=1e-2)
    # no row, no need
    assert trinity.gmm_call_cost(D, 6144, 0, 0) == {"flops": 0, "bytes": 0}
    # a prefill sub-batch's: 5,250 rows over 32 groups, 164 an expert:
    # still under the ridge (240 operations a byte), so the experts'
    # matrices bound it too; 4 sub-batches' rows at once would not
    t, bound = trinity.roofline_seconds(
        trinity.gmm_call_cost(D, 6144, 32, 5250), 197e12, 819e9)
    assert bound == "memory" and t == pytest.approx(1.6e-3, rel=0.05)
    assert trinity.roofline_seconds(
        trinity.gmm_call_cost(D, 6144, 32, 4 * 5250), 197e12, 819e9
        )[1] == "compute"
