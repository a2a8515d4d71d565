"""chip_smoke.py off the chip: it must refuse to pass, and its control
flow is rehearsed at toy widths with the device check bypassed HERE,
in the test — the script has no option that does it."""

import dataclasses
import importlib
import json
import os
import sys

import pytest

from deepspeech_tpu import config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAKE = {"platform": "tpu", "kind": "not a chip", "count": 1}


@pytest.fixture()
def smoke(monkeypatch):
    monkeypatch.syspath_prepend(REPO)
    return importlib.import_module("chip_smoke")


def _json_lines(text):
    return [json.loads(l) for l in text.splitlines() if l.startswith("{")]


def _toy(preset):
    """The preset with its widths, depth and buckets cut for the CPU."""
    def build():
        c = preset()
        return dataclasses.replace(
            c,
            model=dataclasses.replace(c.model, rnn_hidden=32, rnn_layers=2,
                                      conv_channels=(4, 4)),
            data=dataclasses.replace(c.data, bucket_frames=(64, 400)))
    return build


def test_cpu_run_fails_at_the_device_phase(smoke, capsys):
    with pytest.raises(SystemExit) as exit_:
        smoke.main([])
    assert exit_.value.code not in (0, None)
    assert "no TPU" in str(exit_.value.code)
    out = capsys.readouterr().out
    assert '"ok": true' not in out and '"phase"' not in out


def test_kernel_check_rejects_oracles_and_interpreted_kernels(smoke):
    """What a CPU resolves to is exactly what the smoke must not
    accept on a chip."""
    route = smoke.kernel_route("ds2_full")
    assert route["rnn_impl"] == "xla" and route["interpret"] is True
    assert route["rnn_route"] == "pinned"  # H=1760, bf16: copied once
    with pytest.raises(SystemExit):
        smoke.check_kernels(route, "stablehlo.custom_call @tpu_custom_call")
    on_chip = dict(route, rnn_impl="pallas", loss_impl="pallas",
                   interpret=False)
    with pytest.raises(SystemExit):
        smoke.check_kernels(on_chip, "no kernel in this step")
    assert smoke.check_kernels(
        on_chip, "@tpu_custom_call @tpu_custom_call") == {
            "tpu_custom_calls": 2}


def test_toy_run_takes_every_phase_in_order(smoke, monkeypatch, capsys):
    for name in ("ds2_full", "ds2_streaming"):
        monkeypatch.setitem(config.PRESETS, name,
                            _toy(config.PRESETS[name]))
    monkeypatch.setattr(smoke, "check_device", lambda want: dict(FAKE))
    monkeypatch.setattr(smoke, "check_kernels",
                        lambda route, text: {"tpu_custom_calls": 0})
    monkeypatch.setattr(smoke, "SYNC_N", 64)
    monkeypatch.setattr(smoke, "SCAN_CALL", (2, 6))
    monkeypatch.setattr(smoke, "MLA_CALL", (1, 8))
    monkeypatch.setattr(smoke, "MHC_CALLS", {"prefill": 40})
    monkeypatch.setattr(smoke, "MHC_STREAMS", (4, 128))
    monkeypatch.setattr(smoke, "MHC_TIMED_CALLS", 1)
    pid = os.getpid()
    smoke.main([])
    assert os.getpid() == pid
    out = capsys.readouterr().out
    recs = _json_lines(out)
    assert [r["phase"] for r in recs if "phase" in r] == [
        "device", "train", "infer", "reference", "serve", "sync", "total"]
    by_phase = {r["phase"]: r for r in recs if "phase" in r}
    train = by_phase["train"]
    assert train["steps"] == 4 and len(train["losses"]) == 4
    assert train["compiles"] > 0 and "first_step_s" in train
    assert by_phase["infer"]["n_utts"] == 32
    # both builds of the H=1760 scan ran, 2 rows x 6 steps of them
    assert by_phase["reference"]["gru_builds_fwd_values"] == 2 * 6 * 1760
    # ... its recurrent weight gradient was read at three precisions
    assert by_phase["reference"]["dw_h_rows"] == 2 * 6
    # ... a whole layer of it backward, as two functions and as one
    assert by_phase["reference"]["pair_dxp_values"] == 2 * 6 * 5280
    assert by_phase["reference"]["pair_dxproj_differing"] == 0
    # ... and ax_k1's attention in both forms at its published widths
    assert by_phase["reference"]["mla_positions_compared"] == 2
    assert by_phase["reference"]["mla_forms_rms_rel"] \
        <= smoke.MLA_FORMS_RTOL
    # ... and a hyper-connection's kernels against the plain form
    assert by_phase["reference"]["mhc_prefill_coef_err"] \
        <= smoke.MHC_COEF_ATOL
    serve = by_phase["serve"]
    assert serve["streams"] == 2 and serve["chunks"] >= 2
    assert serve["stream_vs_offline_cer"] <= smoke.STREAM_CER_MAX
    # serve.main printed partials per chunk and one final per wav.
    assert any("chunk" in r and len(r["partials"]) == 2 for r in recs)
    assert [len(r["final"]) for r in recs if "final" in r] == [2]
    # The last line is the contract's object and nothing more.
    last = json.loads(out.strip().splitlines()[-1])
    assert last == {"ok": True, "device": FAKE}
    assert sorted(last["device"]) == ["count", "kind", "platform"]


def test_a_phase_that_raises_ends_the_run(smoke, monkeypatch, capsys):
    from deepspeech_tpu import train

    monkeypatch.setattr(smoke, "check_device", lambda want: dict(FAKE))

    def boom(argv):
        raise SystemExit("train.main gave up")

    monkeypatch.setattr(train, "main", boom)
    with pytest.raises(SystemExit, match="gave up"):
        smoke.main([])
    assert '"ok": true' not in capsys.readouterr().out


@pytest.mark.parametrize("limit, said", [
    ("SCAN_BUILDS_RTOL", "the builds differ"),
    ("SCAN_ORACLE_RTOL", "differs from the XLA scan")])
def test_scan_builds_holds_both_builds_to_its_limits(smoke, monkeypatch,
                                                     limit, said):
    """The limits are what passes the two builds of ds2_full's scan
    call: interpreted on the CPU the builds differ from each other by
    float32 rounding and from the XLA scan by bf16's, so with a limit
    at nothing the comparison it guards ends the run. (Forward bits are
    held on compiled kernels only: the chip's reading is 0 of 47.9 M
    values, PERF.md section 6, PR 31.)"""
    monkeypatch.setattr(smoke, "SCAN_CALL", (2, 6))
    assert smoke.scan_builds(True)["gru_builds_fwd_values"] == 21120
    zero = 0.0 if limit == "SCAN_BUILDS_RTOL" else dict.fromkeys(
        smoke.SCAN_ORACLE_RTOL, 0.0)
    monkeypatch.setattr(smoke, limit, zero)
    with pytest.raises(SystemExit, match=said):
        smoke.scan_builds(True)


@pytest.mark.parametrize("limit, to", [
    ("DW_H_LIMIT", 0.0), ("DW_H_TIMES_UNDER_NOISE", 1e9)])
def test_dw_h_precision_holds_the_contraction_to_its_limits(
        smoke, monkeypatch, limit, to):
    """ds2_full's recurrent weight gradient on the operands of a real
    backward pass, 2 rows x 6 steps of it. On the CPU every precision
    is float32 arithmetic (1e-7 from the float64 sum, where a chip's
    three bf16 passes read 1e-5, PERF.md section 6, PR 37), a
    thousandth of the 1e-3 by which the bf16-dot program lies from the
    all-float32 one: both limits pass, and with either at nothing the
    comparison it guards ends the run."""
    monkeypatch.setattr(smoke, "SCAN_CALL", (2, 6))
    read = smoke.dw_h_precision(True)
    assert read["dw_h_rows"] == 12
    assert read["dw_h_high_max_rel"] * smoke.DW_H_TIMES_UNDER_NOISE \
        < read["dw_h_bf16_to_float32_max_rel"]
    assert {k for k in read if k.endswith("_ms")} == {
        "dw_h_highest_ms", "dw_h_high_ms", "dw_h_default_ms"}
    monkeypatch.setattr(smoke, limit, to)
    with pytest.raises(SystemExit, match="from the float64 sum"):
        smoke.dw_h_precision(True)


def test_attention_forms_holds_the_two_forms_to_its_limit(smoke,
                                                          monkeypatch):
    """ax_k1's latent attention at its published widths, a few
    positions on the CPU: the decode form against the sequence form
    reads bfloat16's rounding, and with the limit at nothing the
    comparison ends the run."""
    monkeypatch.setattr(smoke, "MLA_CALL", (1, 8))
    read = smoke.attention_forms()
    assert 0 < read["mla_forms_rms_rel"] <= smoke.MLA_FORMS_RTOL
    monkeypatch.setattr(smoke, "MLA_FORMS_RTOL", 0.0)
    with pytest.raises(SystemExit, match="decode form differs"):
        smoke.attention_forms()


def test_pair_input_grad_holds_the_sum_to_xlas_bits(smoke, monkeypatch):
    """A bidirectional layer of ds2_full's scan call backward, 2 rows
    x 6 steps of it, interpreted: the summing call's ``dxp`` is XLA's
    ``(a + b).astype(bfloat16)`` of the two directions' float32 results
    bit for bit, the projection's bias gradient it sums lies within
    the limit of the float64 sum of the float32 values (and nearer
    than the sum of the rounded ones), the four recurrent weight and
    bias gradients are the two functions' own, and the calls' device
    times are None off the chip (not measured). A pair whose second
    direction sees another matrix, or whose bias gradient is another
    array's sums, ends the run."""
    from deepspeech_tpu.ops import rnn_pallas, scan_pallas

    monkeypatch.setattr(smoke, "SCAN_CALL", (2, 6))
    monkeypatch.setattr(smoke, "PAIR_TIMED_CALLS", 1)
    read = smoke.pair_input_grad(True)
    assert read["pair_dxp_values"] == 2 * 6 * 5280
    assert (read["pair_proj_bias_grad_rel_err"] <= 1e-6
            < read["pair_proj_bias_grad_rounded_rel_err"])
    assert {k: v for k, v in read.items() if k.endswith("_differing")} == {
        f"pair_{name}_differing": 0
        for name in ("dxproj", "dw_f", "db_f", "dw_b", "db_b")}
    assert {k: v for k, v in read.items() if k.endswith("_ms")} == {
        f"pair_bwd_{name}_ms": None for name in ("first", "own", "summing")}
    pair = rnn_pallas.gru_scan_pair_pallas
    monkeypatch.setattr(
        rnn_pallas, "gru_scan_pair_pallas",
        lambda x, m, b_x, w_f, b_f, w_b, b_b, *tail: pair(
            x, m, b_x, w_f, b_f, w_b * 1.01, b_b, *tail))
    with pytest.raises(SystemExit, match="differs from XLA's"):
        smoke.pair_input_grad(True)
    monkeypatch.setattr(rnn_pallas, "gru_scan_pair_pallas", pair)
    sums = scan_pallas._add_column_sums
    monkeypatch.setattr(
        scan_pallas, "_add_column_sums",
        lambda acc, rows: sums(acc, rows * (
            1.01 if rows.shape[0] == 2 and acc.shape[0] == 1 else 1.0)))
    with pytest.raises(SystemExit, match="projection's bias gradient"):
        smoke.pair_input_grad(True)


def test_mhc_passes_holds_the_kernels_to_the_plain_form(smoke, monkeypatch):
    """One sub-layer's hyper-connection, 40 and 300 positions of four
    streams of 128, interpreted: the kernels' coefficients, read mix
    and written streams are the ``jax.numpy`` form's within the limits,
    the times are None off the chip (not measured), and kernels whose
    write-back sees other coefficients end the run."""
    from deepspeech_tpu.ops import mhc_pallas

    monkeypatch.setattr(smoke, "MHC_CALLS", {"prefill": 300, "decode": 40})
    monkeypatch.setattr(smoke, "MHC_STREAMS", (4, 128))
    monkeypatch.setattr(smoke, "MHC_TIMED_CALLS", 1)
    read = smoke.mhc_passes(True)
    for call in ("prefill", "decode"):
        assert 0 < read[f"mhc_{call}_coef_err"] <= smoke.MHC_COEF_ATOL
        assert read[f"mhc_{call}_streams_err"] <= smoke.MHC_BF16_RTOL
    timed = {k: v for k, v in read.items() if k.endswith(("_ms", "_gb_s"))}
    assert set(timed) == {
        f"mhc_{call}_{what}" for call in ("prefill", "decode")
        for what in ("read_ms", "read_gb_s", "write_ms", "write_gb_s",
                     "around_ms", "plain_ms")}
    assert set(timed.values()) == {None}
    write = mhc_pallas.write
    monkeypatch.setattr(mhc_pallas, "write",
                        lambda x, y, coef, **kw: write(x, y, coef * 1.01,
                                                       **kw))
    with pytest.raises(SystemExit, match="differ from the jax.numpy form"):
        smoke.mhc_passes(True)
