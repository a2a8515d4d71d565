"""``drivers/transcribe_long.balance_step``: the balancing rule that
the cell's set-up applies to the held experts' selection bias, on a
model of a seeded router (an expert's load is ``exp(offset + slope *
bias)`` over the sum): four steps even the held experts' loads and
bring their share of the pairs to ``held / experts``, whatever the
slope within a factor of four of the one the first step assumes."""

import numpy as np
import pytest

import conftest  # noqa: F401  (puts the checkout on sys.path)
from benchmark.drivers.transcribe_long import BALANCE_STEPS, balance_step

EXPERTS, HELD, LAYERS, PAIRS = 256, 32, 4, 96_000


def loads(offsets, bias, slope):
    """Pairs on each held expert and elsewhere, a layer."""
    logit = offsets.copy()
    logit[:, :HELD] += slope * bias
    share = np.exp(logit) / np.exp(logit).sum(1, keepdims=True)
    pairs = PAIRS * share
    return pairs[:, :HELD], pairs[:, HELD:].sum(1)


@pytest.mark.parametrize("slope", [8.0, 33.0, 130.0])
def test_four_steps_even_the_held_experts_loads(slope):
    rng = np.random.default_rng(3)
    offsets = 0.5 * rng.standard_normal((LAYERS, EXPERTS))
    bias = np.zeros((LAYERS, HELD))
    state, reads = None, []
    for _ in range(BALANCE_STEPS + 1):
        step, state, read = balance_step(
            *loads(offsets, bias, slope), EXPERTS, state)
        reads.append(read)
        bias += step
    assert reads[0]["load_spread"] > 0.4
    assert reads[-1]["load_spread"] < 0.1
    assert abs(reads[-1]["held_share"] - HELD / EXPERTS) < 0.002
    assert all(np.isfinite(r["gain"]) for r in reads)


def test_an_expert_without_a_pair_gains_bias():
    pairs = np.full((1, HELD), 300.0)
    pairs[0, 0] = 0.0
    step, _, read = balance_step(pairs, [PAIRS - pairs.sum()], EXPERTS)
    assert step[0, 0] > 0 and np.all(np.isfinite(step))
    assert 0 < read["held_share"] < 1
