"""costs/rnnt.py against a hand count at the published widths of
``rnnt_he2019`` (the numbers of the configuration file, typed out here
so that neither the preset nor the cost functions can move them)."""

import types

import pytest

import conftest  # noqa: F401  (puts the checkout on sys.path)
from benchmark.costs import rnnt

M = types.SimpleNamespace(
    rnn_layers=8, rnn_hidden=2048, rnn_proj=640, frame_stack=3,
    time_reduction_layer=2, time_reduction=2, rnnt_pred_layers=2,
    rnnt_pred_hidden=2048, rnnt_pred_embed=128, rnnt_joint_dim=640,
    vocab_size=4096)


def test_frames_round_up_twice():
    assert rnnt.enc_frames(M, 1700) == (567, 284)
    assert rnnt.enc_frames(M, 1201) == (401, 201)
    assert rnnt.enc_frames(M, 6) == (2, 1)


def test_encoder_layers_at_their_own_rate_and_width():
    assert rnnt.encoder_layers(M, 1700) == (
        [(483, 567), (640, 567), (1280, 284)] + [(640, 284)] * 5)
    # One frame of one layer: x W_x, r W_r, m W_p.
    per = lambda d: 2 * (d * 8192 + 640 * 8192 + 2048 * 640)  # noqa: E731
    assert rnnt.lstmp_frame_flops(640, 2048, 640) == per(640) == 23_592_960
    assert rnnt.encoder_flops(M, 1700) == (
        567 * (per(483) + per(640)) + 284 * (per(1280) + 5 * per(640)))
    assert rnnt.encoder_flops(M, 1700) == pytest.approx(68.8e9, rel=0.01)


def test_prediction_net_and_joint():
    per = lambda d: 2 * (d * 8192 + 640 * 8192 + 2048 * 640)  # noqa: E731
    assert rnnt.prediction_flops(M, 65) == 65 * (per(128) + per(640))
    assert rnnt.joint_node_flops(M) == 2 * 640 * 4096 == 5_242_880
    assert rnnt.joint_flops(M, 284, 65) == (
        (284 + 65) * 2 * 640 * 640 + 284 * 65 * 5_242_880)


def test_a_step_needs_three_forwards_of_the_valid_part_only():
    frames, labels = [1700, 1201], [59, 43]
    fwd = sum(rnnt.encoder_flops(M, t) + rnnt.prediction_flops(M, u + 1)
              + rnnt.joint_flops(M, rnnt.enc_frames(M, t)[1], u + 1)
              for t, u in zip(frames, labels))
    assert rnnt.train_flops_valid(M, frames, labels) == 3 * fwd
    assert rnnt.lattice_nodes(M, frames, labels) == 284 * 60 + 201 * 44
    # The cell: 64 rows x 284 x 65 = 1.18 M nodes computed a step.
    assert rnnt.padded_nodes(M, 64, 1700, 64) == 1_181_440


def test_joint_step_cost_counts_no_recomputation_and_no_logits():
    c = rnnt.joint_step_cost(M, 64, 284, 65)
    assert c["nodes"] == 1_181_440
    # Forward output layer + its two gradients = three matmuls a node.
    assert c["flops"] == 3 * 1_181_440 * 5_242_880
    acts = (64 * 284 * 640 + 64 * 65 * 640) * 2
    w = 640 * 4096 * 2 + 4096 * 4
    scores = 3 * 1_181_440 * 4
    grads = (64 * 284 * 640 + 64 * 65 * 640) * 4 + 640 * 4096 * 4 + 4096 * 4
    assert c["bytes"] == 2 * (acts + w + scores) + grads
    assert c["bytes"] < 4 * 1_181_440 * 4096 / 100  # no [nodes, V] tensor
    secs, bound = rnnt.roofline_seconds(c, 197e12, 819e9)
    assert bound == "compute" and secs == pytest.approx(0.0943, rel=0.01)


def test_lstmp_scan_cost_hand_count():
    fwd = rnnt.lstmp_scan_cost(M, 2048, 64, 284, backward=False)
    step = 2 * 64 * 640 * 8192 + 2 * 64 * 2048 * 640
    assert fwd["flops"] == 284 * step
    w = (640 * 8192 + 2048 * 640) * 2 + 8 * 2048 * 4
    assert fwd["weight_bytes"] == w == 13_172_736
    assert fwd["bytes"] == 284 * (64 * 8192 * 2 + 64 * 4 + 64 * 640 * 4) + w
    bwd = rnnt.lstmp_scan_cost(M, 2048, 64, 284, backward=True)
    assert bwd["flops"] == fwd["flops"]  # the recomputed r W_r is not needed
    assert bwd["bytes"] == 284 * (
        64 * 8192 * 2 + 64 * 4 + (64 * 2048 + 2 * 64 * 640) * 4
        + (64 * 8192 + 64 * 2048 + 64 * 640) * 2) + w
    # A step needs 0.84 GFLOP (4.3 us at the peak) and moves 1.2 MB
    # (1.5 us): bound by arithmetic, if the MXU could be filled.
    secs, bound = rnnt.roofline_seconds(fwd, 197e12, 819e9)
    assert bound == "compute"
    assert secs == pytest.approx(284 * step / 197e12)
