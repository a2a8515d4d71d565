"""``benchmark/costs/minicpm_sala.py`` by hand: parameter counts against
the issue's arithmetic, the selection's rows against the rule, the
linear layer's chunked recurrence, and the bytes a decode step needs."""

import numpy as np
import pytest

import conftest  # noqa: F401  (puts the checkout on sys.path)
from benchmark.costs import minicpm_sala as costs


@pytest.fixture(scope="module")
def m():
    from deepspeech_tpu.config import get_config

    return get_config("minicpm_sala").model


def test_parameters_are_the_issues_arithmetic(m):
    assert costs.mlp_params(m) == 3 * 4096 * 16384 == 201_326_592
    assert costs.linear_params(m) == 5 * 4096 * 4096 == 83_886_080
    assert costs.attention_params(m) == 3 * 4096 * 4096 + 2 * 4096 * 256 \
        == 52_428_800
    layers = costs.position_params(m)
    assert layers == 52_428_800 + 3 * 83_886_080 + 4 * 201_326_592
    assert round(layers / 1e6) == 1109
    head = 2 * 73448 * 4096
    assert round(head / 1e6) == 602
    assert costs.parameters(m) == layers + head + 1288 * 4096
    # trained at 16 bytes a parameter the one period does not fit a chip
    assert costs.training_floor_bytes(m) > 27e9


def test_cache_by_part(m):
    got = costs.cache_bytes(m, 32, 19328)
    assert got["state"] == 32 * 3 * 4 * 32 * 128 * 128
    assert got["rows"] == 32 * 19328 * 1024
    assert got["pooled"] == 32 * 1208 * 512
    assert costs.pooled_windows(m, 19328) == 1207
    assert costs.pooled_windows(m, 31) == 0
    assert costs.pooled_windows(m, 32) == 1


def test_rows_selected_follow_the_rule(m):
    # a decode step's sequence is the rows up to its own
    pos = np.asarray([0, 100, 8191, 8192, 8255, 8256, 15000, 19320])
    want = [1, 101, 8192, 96 * 64 + 1, 96 * 64 + 64, 96 * 64 + 1,
            96 * 64 + 15000 % 64 + 1, 96 * 64 + 19320 % 64 + 1]
    np.testing.assert_array_equal(costs.rows_selected(m, pos), want)
    # a prefix query's sequence is the prefix: past dense_len every
    # query selects, and one with fewer blocks in reach keeps them all
    got = costs.rows_selected(m, np.asarray([0, 100, 6207, 6208, 9000]),
                              12000)
    np.testing.assert_array_equal(
        got, [1, 101, 6208, 96 * 64 + 1, 96 * 64 + 9000 % 64 + 1])
    np.testing.assert_array_equal(
        costs.rows_selected(m, np.asarray([0, 5000, 8000]), 8192),
        [1, 5001, 8001])
    np.testing.assert_array_equal(
        costs.windows_ranked(m, np.asarray([0, 30, 31, 46, 47, 19320])),
        [0, 0, 1, 1, 2, (19320 - 31) // 16 + 1])


def test_a_streams_rows_and_the_share_read(m):
    rows = costs.stream_rows(m, 120000, 4321)
    pos = 15000 + np.arange(4321)
    assert rows["decode_held"] == int((pos + 1).sum())
    assert rows["decode_pairs"] == int((96 * 64 + pos % 64 + 1).sum())
    # 20 minutes: 36% of the rows held are read; 15 minutes: 48%
    assert 0.35 < rows["decode_pairs"] / rows["decode_held"] < 0.37
    short = costs.stream_rows(m, 90008, 3241)
    assert 0.47 < short["decode_pairs"] / short["decode_held"] < 0.49
    assert rows["prefill_pairs"] == int(costs.rows_selected(
        m, np.arange(15000), 15000).sum())
    # a prefix under dense_len ranks nothing and reads every pair
    dense = costs.stream_rows(m, 8 * 8000, 10)
    assert dense["prefill_ranked"] == 0 and dense["decode_ranked"] == 0
    assert dense["prefill_pairs"] == 8000 * 8001 // 2


def test_scan_and_step(m):
    # one whole chunk of 128: the causal half of q k^T and of its
    # product with v a head, and the carry's read and write
    assert costs.scan_flops(m, 128) == 32 * (
        2 * (128 * 129 // 2) * 2 * 128 + 4 * 128 * 128 * 128)
    assert costs.scan_flops(m, 130) - costs.scan_flops(m, 128) == 32 * (
        2 * 3 * 2 * 128 + 4 * 2 * 128 * 128)
    assert costs.scan_bytes(m, 100) == 100 * 2 * 4 * 4096 + 2_097_152
    assert costs.step_bytes(m) == 2 * 2_097_152
    flops, moved = costs.prefill_scan_cost(m, [120000, 90008])
    assert flops == 3 * (costs.scan_flops(m, 15000)
                         + costs.scan_flops(m, 11251))
    assert moved == 3 * (costs.scan_bytes(m, 15000)
                         + costs.scan_bytes(m, 11251))


def test_a_decode_steps_bytes_and_the_mechanisms_share(m):
    # 32 streams at 17,000 rows: the issue's reckoning of a fifth
    pos = np.full(32, 17000)
    parts = costs.decode_step_bytes(
        m, 32 * 3, float(costs.rows_selected(m, pos).sum()),
        float(costs.windows_ranked(m, pos).sum()))
    assert parts["weights"] == 2 * costs.position_params(m)
    assert parts["head"] == 2 * 4096 * 73448
    assert parts["state"] == 96 * 2 * 2_097_152
    assert parts["rows"] == pytest.approx(32 * 6200 * 1024, rel=0.01)
    assert parts["select"] == 32 * 1061 * 512
    new = parts["state"] + parts["rows"] + parts["select"]
    assert 0.17 < new / sum(parts.values()) < 0.20
    assert costs.decode_select_bytes(m, 10) == 10 * 1024


def test_call_flops_add_up(m):
    one = costs.stream_flops(m, 120000, 4321)
    rows = costs.stream_rows(m, 120000, 4321)
    s = 15000 + 4321
    want = (15000 * 2 * 1288 * 4096 + s * 2 * costs.position_params(m)
            + 4 * 4096 * (rows["prefill_pairs"] + rows["decode_pairs"])
            + 2 * 4096 * (rows["prefill_ranked"] + rows["decode_ranked"])
            + 3 * (costs.scan_flops(m, 15000) + 4321 * 4 * 32 * 128 * 128)
            + 4321 * 2 * 4096 * 73448)
    assert one == want
    assert costs.call_flops_valid(m, [120000, 90008], [4321, 3241]) \
        == one + costs.stream_flops(m, 90008, 3241)
    assert costs.prefill_select_flops(m, [120000]) \
        == 4 * 4096 * rows["prefill_pairs"]
