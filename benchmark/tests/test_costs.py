"""costs/ds2.py: its operations equal the program's utils/flops.py on
every preset, and its bytes equal a hand count."""

import types

import pytest

from benchmark.costs import ds2


@pytest.mark.parametrize("frames", [400, 1700])
def test_operations_equal_the_programs(frames):
    from deepspeech_tpu.config import PRESETS, get_config
    from deepspeech_tpu.utils import flops

    for name in PRESETS:
        m = get_config(name).model
        assert ds2.conv_frontend_flops(m, frames) == \
            flops.conv_frontend_flops(m, frames)
        assert ds2.ds2_step_flops(m, 32, frames) == \
            flops.ds2_step_flops(m, 32, frames)
        # Valid-frame accounting degenerates to the padded one when
        # every utterance fills the bucket.
        assert ds2.train_flops_valid(m, [frames] * 32) == \
            flops.ds2_step_flops(m, 32, frames)


def model(**kw):
    base = dict(rnn_type="gru", rnn_hidden=1760, bidirectional=True)
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_bytes_hand_count_blocked_h1760():
    m = model()
    assert ds2.scan_is_blocked(m)  # 1760*5280*2 = 18.6 MB > 10 MiB
    b, t = 32, 850
    cols = 5376  # 5280 padded to the 128-lane width
    w = 1760 * cols * 2 + cols * 4
    assert w == 18_945_024
    fwd = ds2.gru_scan_cost(m, b, t, backward=False)
    per_step = (b * 5280 + b + b * 1760) * 4  # xproj, mask in; h out
    assert per_step == 901_248
    assert fwd["bytes"] == t * per_step + w
    assert fwd["restream_bytes"] == t * (per_step + w)
    assert fwd["flops"] == t * 2 * b * 1760 * 5280
    bwd = ds2.gru_scan_cost(m, b, t, backward=True)
    per_step = (b * 5280 + b + 2 * b * 1760 + 2 * b * 5280) * 4
    assert bwd["bytes"] == t * per_step + w
    assert bwd["flops"] == t * 4 * b * 1760 * 5280
    # What the call needs is compute-bound on a v5e at 32 rows (655
    # operations a byte against a ridge of 240); re-streaming the
    # weights every step would make it memory-bound at 19.6 ms.
    secs, bound = ds2.roofline_seconds(fwd, 197e12, 819e9)
    assert bound == "compute"
    assert secs == pytest.approx(t * 2 * b * 1760 * 5280 / 197e12)
    assert fwd["restream_bytes"] / 819e9 == pytest.approx(0.0206, rel=0.01)


def test_resident_weights_are_read_once():
    m = model(rnn_hidden=800, bidirectional=False)
    assert not ds2.scan_is_blocked(m)  # 800*2400*2 = 3.8 MB
    c = ds2.gru_scan_cost(m, 256, 32, backward=False)
    assert c["weight_bytes"] == 800 * 2432 * 2 + 2432 * 4
    assert c["restream_bytes"] == c["bytes"]
