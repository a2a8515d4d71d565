"""The main path's Pallas kernels, compiled by Mosaic for a described
v5e at the presets' real widths — no chip needed, nothing runs.

Interpret-mode tests cannot see what the TPU compiler refuses (a block
not aligned to the tiling, more scoped VMEM than a kernel may use); a
compile against ``topologies.get_topology_desc("v5e:2x2")`` can. The
shapes are the case builders of tools/aot_kernels.py, not copies.
"""

import os
import re
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from _aot_common import AOT_ENV  # noqa: E402


@pytest.fixture(scope="module")
def v5e():
    """A described v5e:2x2, with the persistent compile cache off (an
    entry written by a compile for a described device cannot be read
    back here, and warns)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        for key, value in AOT_ENV.items():
            mp.setenv(key, value)
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc("v5e:2x2", "tpu")
        except Exception as e:  # no libtpu, or one that cannot describe it
            pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield topo
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def v5e_chip(v5e):
    """Sharding onto one chip of it."""
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e.devices[0])


@pytest.mark.parametrize("case, kernels", [
    # ds2_full's cell: blocked weights, forward + VJP
    ("gru_h1760", ["gru_scan_fwd", "gru_scan_bwd"]),
    # ds2_streaming's serve cell: resident, carried h0
    ("gru_stream_h800", ["gru_scan_stream"]),
    # fused bidirectional cell (ds2_small's width)
    ("bigru_h800", ["bigru_scan_fwd"]),
    # CTC loss at the English vocabulary, forward + VJP
    ("ctc_en", ["ctc_alpha", "ctc_gamma"]),
    # int8 weights at ds2_full's width
    ("gru_q_h1760", ["gru_scan_q_fwd"]),
    # rnnt_he2019's prediction net: 13.1 MB of single-buffered bf16
    # weights under a raised scoped-VMEM limit, forward + VJP
    ("lstmp_t65_b64", ["lstmp_scan_fwd", "lstmp_scan_bwd"]),
    # lfm2_24b_a2b's gate+up projection of 8 held experts over 32,256
    # rows: whole-contraction blocks under a 48 MiB scoped-VMEM limit,
    # a grid as long as the routed tiles, forward + VJP
    ("moe_gmm_w13", ["moe_gmm", "moe_gmm", "moe_tgmm"]),
    # ax_k1's grouped products over 12 held experts, K = 7168 as one
    # contraction block: a prefill sub-batch's (row tiles of 512) and a
    # decode step's (row tiles of 128), both matrices
    ("moe_gmm_axk1_prefill_w13", ["moe_gmm"]),
    ("moe_gmm_axk1_prefill_w2", ["moe_gmm"]),
    ("moe_gmm_axk1_decode_w13", ["moe_gmm"]),
    ("moe_gmm_axk1_decode_w2", ["moe_gmm"]),
    # trinity_large's over 32 held experts: a prefill sub-batch's
    # 10,752 rows and a decode step's single row tile of 128
    ("moe_gmm_trinity_prefill_w13", ["moe_gmm"]),
    ("moe_gmm_trinity_prefill_w2", ["moe_gmm"]),
    ("moe_gmm_trinity_decode_w13", ["moe_gmm"]),
    ("moe_gmm_trinity_decode_w2", ["moe_gmm"]),
    # trinity_large's prefill attention, a sub-batch of 2 recordings of
    # 5,250 positions (20 query tiles of 256 and one of 130 that hangs
    # over the end), 48 / 8 heads of 128: 6 x 256 x 512 float32 scores
    # a tile under a 64 MiB scoped-VMEM limit; a sliding layer (window
    # 4,096) and the global one
    ("gqa_attn_fwd_trinity_window", ["gqa_attn_fwd"]),
    ("gqa_attn_fwd_trinity_global", ["gqa_attn_fwd"]),
    # its decode step, 16 streams: a ring of 4,096 rows in 8 row tiles
    # of 512 (a tile is 4,096 rows of 128: a slot's 8 key/value heads
    # lie together), the global cache's 6,784 in 14, the last hanging
    # over; 48 x 4,096 float32 scores a tile
    ("gqa_attn_decode_trinity_window", ["gqa_attn_decode"]),
    ("gqa_attn_decode_trinity_global", ["gqa_attn_decode"]),
    # smallthinker_21b_a3b's attention in training, 4 recordings of
    # 6,784 positions (26 query tiles of 256 and one of 128, 13 key
    # tiles of 512 and one of 128), 28 / 4 heads of 128, forward with
    # its log-sum-exp (a lane-wide statistic transposed to a row) and
    # backward: transposed scores 512 x 256 float32 a head, dq summed
    # over key tiles and dk / dv over query tiles and 7 query heads in
    # VMEM, under a 64 MiB scoped-VMEM limit; a sliding layer and the
    # global one
    ("gqa_attn_train_smallthinker_window",
     ["gqa_attn_fwd", "gqa_attn_bwd_dq", "gqa_attn_bwd_dkv"]),
    ("gqa_attn_train_smallthinker_global",
     ["gqa_attn_fwd", "gqa_attn_bwd_dq", "gqa_attn_bwd_dkv"]),
    # ... and its grouped products over 16 held experts, 61,440 rows
    ("moe_gmm_smallthinker_w13", ["moe_gmm", "moe_gmm", "moe_tgmm"]),
    ("moe_gmm_smallthinker_w2", ["moe_gmm", "moe_gmm", "moe_tgmm"]),
    # falcon_h1_34b's state-space recurrence: a prefill sub-batch of 32
    # utterances of 212 positions in 2 chunks of 128 (32 heads of 128,
    # state 256: a [256, 128] float32 carry in VMEM, the chunk's decays
    # as [128, 128], a row made a column by its diagonal), and a decode
    # step of 128 streams: a group's 16 heads of state, 2.1 MB, a grid
    # step in and out under a raised scoped-VMEM limit, aliased
    ("ssd_chunk_scan_falcon", ["ssd_chunk_scan"]),
    ("ssd_state_step_falcon", ["ssd_state_step"]),
    # minicpm_sala's sparse layer under its selection: a decode step of
    # 32 streams (the local window's [2048, 128] of K and of V at an
    # element offset; 32 operands of the one cache, a [64, 128] block of
    # K or V each by a scalar-prefetched index; [16, 2048] float32 scores)
    # and a prefill sub-batch of 2 x 15,000 positions (16 query heads a
    # key/value head: 2 MB of float32 accumulators and 4 MB of lane-wide
    # statistics under a 64 MiB scoped-VMEM limit; the selection map's
    # tile spread over the keys by a [256, 128] x [128, 512] product);
    # and a linear layer's recurrence with a group a head
    ("gqa_attn_select_decode_sala", ["gqa_attn_select_decode"]),
    ("gqa_attn_select_fwd_sala", ["gqa_attn_select_fwd"]),
    ("ssd_chunk_scan_sala", ["ssd_chunk_scan"]),
    ("ssd_state_step_sala", ["ssd_state_step"]),
    # xing4_29b_a4b's hyper-connection of one sub-layer, four bfloat16
    # streams of 3,584: a prefill sub-batch's 6,784 positions (53 tiles
    # of 128: 3.67 MB of streams a grid step, in twice and in
    # ``mhc_write`` out twice, the three bf16 parts of ``gain * phi``
    # resident beside them) and a drafting step's 512, at the scoped
    # VMEM the calls compute from their shapes
    ("mhc_xing4_prefill", ["mhc_read", "mhc_write"]),
    ("mhc_xing4_decode", ["mhc_read", "mhc_write"]),
])
def test_kernel_compiles_for_v5e(v5e_chip, case, kernels):
    """Mosaic accepts the kernel, and the compiled instruction still
    says which kernel it is, in the form a device trace's event names
    carry and the benchmark's parser reads."""
    from aot_kernels import compile_case, kernel_cases
    from benchmark.layer_metrics._kernel_id import kernel_facts

    text = compile_case(kernel_cases()[case], v5e_chip).as_text()
    calls = text.split('custom_call_target="tpu_custom_call"')[1:]
    assert sorted(kernel_facts(c)["kernel"] for c in calls) \
        == sorted(kernels)


def test_no_float32_copy_of_the_streams_leaves_a_hyper_connection(
        v5e, monkeypatch, tmp_path, capsys):
    """xing4_29b_a4b's two served programs at the cell's sizes
    (``tools/aot_tpu.py --preset xing4_29b_a4b --batch 256 --frames
    1696 --hlo-out``): each holds the 16 sub-layers' ``mhc_read`` and
    ``mhc_write`` by name, and no float32 array of the streams' size,
    ``[.., 4, 3584]`` or ``[positions, 14336]``, exists outside a
    kernel: before PR 52 XLA gave the write-back's result twice, as
    bf16 and as the float32 copy the next coefficient product read
    (the parent's prefill program matches the first pattern below 456
    times, its decode program 358), and a compiler upgrade cannot
    bring it back unseen."""
    import argparse
    import json

    import aot_tpu
    from benchmark.layer_metrics._kernel_id import kernel_facts
    from deepspeech_tpu.config import get_config

    monkeypatch.setenv("DS2N_ASSUME_TPU", "1")  # serve_lm sets it too
    out = str(tmp_path / "xing4")
    aot_tpu.serve_lm(
        argparse.Namespace(preset="xing4_29b_a4b", batch=256, frames=1696,
                           hlo_out=out),
        get_config("xing4_29b_a4b"), v5e)
    said = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    for program, positions in (("prefill", 32 * 212), ("decode", 2 * 256)):
        with open(f"{out}.{program}") as f:
            text = f.read()
        facts = [kernel_facts(c) for c in text.split(
            'custom_call_target="tpu_custom_call"')[1:]]
        # the lowered text holds ONE function a kernel, called by the 16
        # sub-layers (``mhc_pallas.read`` / ``write`` are jitted so that
        # they are traced and lowered once); compiled, it is 16 calls
        assert said[program]["mosaic_calls"] == 14 + 2
        assert len(facts) == 14 + 32 and all(f.get("kernel") for f in facts)
        ours = [f for f in facts if f["kernel"].startswith("mhc_")]
        assert sorted(f["kernel"] for f in ours) \
            == ["mhc_read"] * 16 + ["mhc_write"] * 16
        assert {f["rows"] for f in ours} == {str(positions)}
        assert not re.findall(r"f32\[(?:\d+,)*4,3584\]", text)
        assert not re.findall(rf"f32\[{positions},14336\]", text)
        assert re.findall(rf"bf16\[{positions},14336\]", text)
        assert said[program]["peak_estimate_bytes"] < 16 * 1024 ** 3


@pytest.mark.parametrize("case, kernels", [
    # evaluation and the benchmark's reference check (b=8, t=400)
    ("gru_h1760", ["gru_scan_bwd", "gru_scan_fwd"]),
    ("gru_h1760_b32", ["gru_scan_bwd", "gru_scan_fwd"]),
    ("gru_h1760_b64", ["gru_scan_bwd", "gru_scan_fwd"]),
    # offline decode: the forward call alone, no VJP; its widest batch
    ("gru_h1760_decode", ["gru_scan_fwd"]),
    ("gru_h1760_decode_b128", ["gru_scan_fwd"]),
])
def test_pinned_scan_fits_the_vmem_it_asks_for(v5e_chip, case, kernels):
    """ds2_full's scans copy their 18.6 MB of bf16 weights into VMEM
    once, consume them whole at every time step (one matmul forward,
    two backward) and raise their own scoped limit from their shapes
    (forward 28 MiB at the cell's b=32 and at b=64, 36 at b=128;
    backward 32 and 40 MiB): Mosaic accepts every one, and the compiled
    call says which build it is. The backward step reads its scratch
    once per matmul; read once for both, Mosaic holds the matrix a
    second time (42 MiB at b=32) and this test fails."""
    from aot_kernels import compile_case, kernel_cases
    from benchmark.layer_metrics._kernel_id import kernel_facts

    text = compile_case(kernel_cases()[case], v5e_chip).as_text()
    calls = text.split('custom_call_target="tpu_custom_call"')[1:]
    assert sorted((f["kernel"], f["variant"]) for f in map(
        kernel_facts, calls)) == [(k, "pinned") for k in kernels]


@pytest.mark.parametrize("case", ["gru_h1760_f32", "gru_h1760_f32_b32"])
def test_float32_scans_at_1760_compile_streamed(v5e_chip, case):
    """A float32 model at ds2_full's width (37.8 MB of weights, run by
    no preset) is past what a call may copy into VMEM, at the cell's 32
    rows and at evaluation's 8, where the need comes to the cap itself:
    Mosaic compiles the streamed build, the one user of the column
    grid among the GRU's float kernels, under its default limit."""
    from aot_kernels import compile_case, kernel_cases
    from benchmark.layer_metrics._kernel_id import kernel_facts

    text = compile_case(kernel_cases()[case], v5e_chip).as_text()
    calls = text.split('custom_call_target="tpu_custom_call"')[1:]
    assert sorted((f["kernel"], f["variant"]) for f in map(
        kernel_facts, calls)) == [("gru_scan_bwd", "blocked"),
                                  ("gru_scan_fwd", "blocked")]


@pytest.mark.parametrize("case, passes", [
    ("gru_h1760", "high"), ("gru_h1760_f32", "highest")])
def test_recurrent_dw_passes_follow_the_dot_type(v5e_chip, case, passes):
    """The GRU's recurrent weight gradient at ds2_full's width, as the
    TPU compiler is handed it: ONE ``f32[1760,5280]`` contraction of
    the VJP, at ``high`` (three bf16 passes of the MXU) where the scan
    multiplies in bf16 and at ``highest`` (six) where it multiplies in
    float32. Six passes at bf16 dots were 233 ms of ds2_full's 572 ms
    step; the compiler's own estimate for the three-pass form is half
    the six-pass one's (12.4 M cycles against 24.3 M at the cell's 32
    rows, PERF.md section 6, PR 37)."""
    from aot_kernels import compile_case, kernel_cases

    text = compile_case(kernel_cases()[case], v5e_chip).as_text()
    dws = [line for line in text.splitlines() if re.search(
        r"= f32\[1760,5280(,1)?\]\S* (convolution|dot)\(", line)]
    assert len(dws) == 1, dws
    assert f"operand_precision={{{passes},{passes}}}" in dws[0]


# gru_scan_bwd at H=1760 as the compiled text names it: dxp, dgates,
# h_prev flat (850 * b rows) and the bias gradient's accumulator
_BWD_RESULTS = (r"%(\S+) = \(f32\[850,{b},5280\]\S*, f32\[850,{b},5280\]\S*, "
                r"f32\[{rows},1760\]\S*, f32\[8,5280\]\S*\) custom-call\(")


@pytest.mark.parametrize("case, rows", [
    ("gru_h1760_b32", 32), ("gru_h1760_b64", 64)])
def test_backward_scan_sums_its_bias_gradient(v5e_chip, case, rows):
    """ds2_full's scan VJP as the TPU compiler is handed it, at the
    cells' call (b=32) and at twice the rows: the only reduction into
    ``f32[5280]`` is the 8 -> 1 sum of the backward kernel's third
    result, the ``[8, 5280]`` accumulator it kept in VMEM. Summed by
    XLA, ``db_h`` was a pass of its own over the kernel's
    ``f32[850, b, 5280]`` ``dgates`` (574 MB at b=32), 14 times a step
    (PERF.md section 6, PR 47); the whole step's program says the same
    (``tools/aot_tpu.py --preset ds2_full --batch 32 --frames 1700
    --hlo-out F``: two minutes, so not run here)."""
    from aot_kernels import compile_case, kernel_cases

    text = compile_case(kernel_cases()[case], v5e_chip).as_text()
    assert re.search(_BWD_RESULTS.format(b=rows, rows=850 * rows), text)
    sums = re.findall(
        r"= f32\[5280\]\S* reduce\([^\n]*dimensions=\{([\d,]+)\}", text)
    assert sums == ["0"], sums


@pytest.mark.parametrize("case, rows", [
    ("gru_h1760_b32", 32), ("gru_h1760_b64", 64)])
def test_backward_scan_hands_back_the_previous_state(v5e_chip, case, rows):
    """ds2_full's scan VJP as the TPU compiler is handed it: the
    recurrent weight gradient's two operands are results of the
    backward Mosaic call itself, ``h_prev``, flat (``[850 * b, H]``),
    and ``dgates`` through a bitcast to the same rows, and the
    contraction takes them as they lie: a convolution with no window
    over the 850 * b rows, no ``copy`` inside its fusion. Nothing
    slices the forward call's state sequence to 849 rows and nothing
    concatenates or pads a zero row onto it: built by XLA, the shifted
    copy was a ``slice`` and a ``copy`` of 191 MB each at b=32, 14
    times a step, and handed ``[850, b, .]`` the contraction turned
    its operands batch-major itself (16.66 ms of 445, then 0.84 ms a
    contraction: PERF.md section 6, PR 48). A copy of the sequence's
    shape alone is not the shifted copy (``dy``'s turn to time-major
    is one), so none is forbidden by shape. The call asks for the
    scoped VMEM ``_pinned_vmem_limit`` counts with the new block in
    it, 32 / 40 MiB, and Mosaic accepts it."""
    from aot_kernels import compile_case, kernel_cases

    from deepspeech_tpu.ops.scan_pallas import scan_route

    text = compile_case(kernel_cases()[case], v5e_chip).as_text()
    bwd = re.search(_BWD_RESULTS.format(b=rows, rows=850 * rows), text)
    assert bwd, "gru_scan_bwd's four results"
    shifted = re.findall(
        rf"= f32\[849,{rows},1760\]\S* slice\(|"
        rf"= f32\[850,{rows},1760\]\S* (?:concatenate|pad)\(", text)
    assert not shifted, shifted
    # the computation that holds the contraction, who feeds it, and
    # the computations its fusion reaches (none may turn a layout)
    home, feeds, bodies, where = None, {}, {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(.*\{$", line)
        if head:
            where = head[1]
            continue
        bodies.setdefault(where, []).append(line)
        if re.search(r"= f32\[1760,5280(,1)?\]\S* (convolution|dot)\(", line):
            assert home is None, "one recurrent weight gradient"
            home = where
            assert "window=" not in line, line
            operands = re.search(r"(?:convolution|dot)\(([^)]*)\)", line)[1]
        fused = re.search(r"fusion\(([^)]*)\).* calls=%(\S+?),? ", line)
        if fused:
            feeds[fused[2]] = fused[1]
    if home in feeds:  # fused: the fusion's operands, and all it holds
        operands, reach = feeds[home], [home]
        for name in reach:
            reach += re.findall(r"calls=%([^\s,]+)", "\n".join(bodies[name]))
        turned = [line for name in reach for line in bodies[name]
                  if re.search(r" (copy|transpose)\(", line)]
        assert not turned, turned

    def result_of_the_call(name):
        through = re.search(
            rf"%{re.escape(name)} = \S+ bitcast\(%([^\s,)]+)\)", text)
        if through:
            return result_of_the_call(through[1])
        return int(re.search(rf"%{re.escape(name)} = \S+ get-tuple-element\("
                             rf"%{re.escape(bwd[1])}\), index=(\d)", text)[1])

    results = sorted(map(result_of_the_call,
                         re.findall(r"%([^\s,)]+)", operands)))
    assert results == [1, 2], results
    limit = scan_route("gru", "pallas", hidden=1760, rows=rows, dot_bytes=2,
                       xproj_bytes=2, backward=True).vmem_limit
    assert limit == {32: 32, 64: 40}[rows] * 1024 * 1024
    asked = re.search(r'"scoped_memory_configs":\[\{[^}]*"size":"(\d+)"',
                      text[bwd.end():])
    assert int(asked[1]) == limit


# the pair's summing call: the sum in xproj's bf16, and the
# projection's bias gradient in an accumulator of its own
_SUM_RESULTS = (r"%(\S+) = \(bf16\[850,{b},5280\]\S*, f32\[850,{b},5280\]\S*, "
                r"f32\[{rows},1760\]\S*, f32\[8,5280\]\S*, f32\[8,5280\]\S*\) "
                r"custom-call\(")


@pytest.mark.parametrize("case, rows", [
    ("gru_pair_h1760_b32", 32), ("gru_pair_h1760_b64", 64)])
def test_pair_backward_scans_sum_the_input_gradient(v5e_chip, case, rows):
    """A whole bidirectional layer of ds2_full as the TPU compiler is
    handed it (``gru_scan_pair_pallas`` + VJP at the cells' b=32 and
    at twice the rows, bf16 ``xproj``, the projection's bias handed
    over apart): four ``pinned`` calls, known by their kernel facts
    (``kernel``, ``sum``, ``t``, ``b``: what the scans' roofline reads
    them by, ``benchmark/layer_metrics/rnn_scan_roofline.py`` ``read``).
    The first backward call returns a one-direction layer's
    ``(f32[850,b,5280], f32[850,b,5280], f32[850*b,1760],
    f32[8,5280])``; the ONE that carries ``sum=pair`` takes that
    float32 ``dxp`` as one more operand and returns
    ``(bf16[850,b,5280], f32[850,b,5280], f32[850*b,1760], f32[8,5280],
    f32[8,5280])``: the pair's sum as the projection's backward reads
    it, and its column sums. Each asks for the scoped VMEM
    ``_pinned_vmem_limit`` counts (32 / 40 MiB, the summing call too:
    one more double-buffered float32 block in, its first result at
    half the bytes, one more accumulator) under ``PINNED_VMEM_CAP``,
    and Mosaic accepts both. No fusion reads two ``f32[850,b,5280]``
    (or ``[b,850,5280]``) arrays (XLA's sum of the two directions'
    ``dxp``: PERF.md section 6, PR 50), none reads one to write a
    ``bf16`` array of that shape (the cast pass, 8.88 ms a step of
    ds2_full: PR 55), and nothing but the calls' own ``[8, 5280]``
    accumulators is reduced into ``[5280]``: three of them, the third
    the projection's bias gradient (a reading of the sum of its own,
    5.42 ms a step)."""
    from aot_kernels import compile_case, kernel_cases
    from benchmark.layer_metrics._kernel_id import kernel_facts

    from deepspeech_tpu.ops.scan_pallas import PINNED_VMEM_CAP, scan_route

    text = compile_case(kernel_cases()[case], v5e_chip).as_text()
    # each call's own line up to its target, then what follows it
    parts = text.split('custom_call_target="tpu_custom_call"')
    calls = [before.rsplit("\n", 1)[-1] + after
             for before, after in zip(parts, parts[1:])]
    assert sorted((f["kernel"], f["variant"], f.get("sum", "own"),
                   f["t"], f["b"]) for f in map(kernel_facts, calls)) == [
        ("gru_scan_bwd", "pinned", "own", "850", str(rows)),
        ("gru_scan_bwd", "pinned", "pair", "850", str(rows)),
        ("gru_scan_fwd", "pinned", "own", "850", str(rows)),
        ("gru_scan_fwd", "pinned", "own", "850", str(rows))]
    backward = [c for c in calls if kernel_facts(c)["kernel"] == "gru_scan_bwd"]
    for call in backward:
        sums_pair = kernel_facts(call).get("sum") == "pair"
        results = _SUM_RESULTS if sums_pair else _BWD_RESULTS
        assert re.search(results.format(b=rows, rows=850 * rows), call)
        limit = scan_route(
            "gru", "pallas", hidden=1760, rows=rows, dot_bytes=2,
            xproj_bytes=2, backward=True, sums_pair=sums_pair).vmem_limit
        assert limit == {32: 32, 64: 40}[rows] * 1024 * 1024
        assert limit < PINNED_VMEM_CAP
        asked = re.search(r'"scoped_memory_configs":\[\{[^}]*"size":"(\d+)"',
                          call)
        assert int(asked[1]) == limit
        operands = call.split(" custom-call(", 1)[1].split("), ", 1)[0]
        assert operands.count("%") == 6 + sums_pair, operands
    wide = re.compile(rf"f32\[(850,{rows}|{rows},850),5280\]")
    narrow = re.compile(rf"= bf16\[(850,{rows}|{rows},850),5280\]")
    shape_of = dict(re.findall(r"%(\S+) = (\(?\w+\[[\d,]*\])", text))
    for line in text.splitlines():
        fused = re.search(r" fusion\(([^)]*)\)", line)
        if fused:
            read = [name for name in re.findall(r"%([^\s,)]+)", fused[1])
                    if wide.match(shape_of.get(name, ""))]
            assert len(read) < 2, line
            assert not (read and narrow.search(line)), line
    sums = re.findall(
        r"= \w+\[5280\]\S* reduce\([^\n]*dimensions=\{([\d,]+)\}", text)
    assert sums == ["0", "0", "0"], sums


def test_ctc_vjp_folds_gamma_without_a_scatter(v5e_chip):
    """CTC backward sums gamma from the extended labels into the
    vocabulary with one f32-exact contraction. A TPU runs a scatter-add
    one update after another (98.8 ms of ds2_full's step, PERF.md
    PR 25), so none may come back: the only scatter left is the
    interleaving of the integer labels with blanks."""
    from aot_kernels import compile_case, kernel_cases
    from benchmark.layer_metrics._kernel_id import kernel_facts

    text = compile_case(kernel_cases()["ctc_en"], v5e_chip).as_text()
    scattered = re.findall(r"= \(?(\w+)\[[^\]]*\]\S* scatter\(", text)
    assert set(scattered) <= {"s32"}, scattered
    folds = [line for line in text.splitlines()
             if re.search(r"= f32\[4,400,29\]\S* (convolution|dot)\(", line)]
    assert len(folds) == 1, folds
    assert "operand_precision={highest,highest}" in folds[0]
    calls = text.split('custom_call_target="tpu_custom_call"')[1:]
    assert sorted(kernel_facts(c)["kernel"] for c in calls) \
        == ["ctc_alpha", "ctc_gamma"]


def test_frontend_convolutions_fill_the_lanes(v5e_chip):
    """ds2_full's conv frontend, forward + backward at the cell's
    ``[32, 1700, 161]``: the compiler sees no frontend convolution
    whose result has fewer than 128 channels (it lays activations out
    channels-minor on 128 lanes; the 32 channels as written ran conv1
    at 8.6% of the MXU's peak), and its own cost model stays under
    90 M cycles over every instruction of the program: 151.4 M as
    written, 54.4 M folded (this compile, PR 34; 90 M is their
    geometric mean, so a compiler update has room on both sides)."""
    import jax.numpy as jnp
    from _aot_common import cycles_by_op

    from deepspeech_tpu.config import get_config
    from deepspeech_tpu.models.conv import ConvFrontend

    frontend = ConvFrontend(get_config("ds2_full").model)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=v5e_chip)
    variables = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: frontend.init(
            jax.random.PRNGKey(0), jnp.zeros((2, 64, 161)),
            jnp.array([64, 64]), False)))

    def step(params, stats, feats, lens, ct):
        def loss(p):
            (y, _), new = frontend.apply(
                {"params": p, "batch_stats": stats}, feats, lens, True,
                mutable=["batch_stats"])
            return jnp.sum(y.astype(jnp.float32) * ct), new
        return jax.value_and_grad(loss, has_aux=True)(params)

    text = jax.jit(step).lower(
        variables["params"], variables["batch_stats"],
        sds((32, 1700, 161), jnp.float32), sds((32,), jnp.int32),
        sds((32, 850, 41 * 32), jnp.float32)).compile().as_text()
    convs = re.findall(
        r"= \w+\[([\d,]+)\]\{(\d+)[^ ]* convolution\([^\n]*"
        r'op_name="[^"]*/conv_general_dilated"', text)
    assert len(convs) == 5, convs  # two forward, two filter, one input
    for dims, minor in convs:
        assert int(dims.split(",")[int(minor)]) >= 128, (dims, minor)
    cycles = sum(c for c, _ in cycles_by_op(text).values())
    assert cycles < 90e6, cycles


def test_store_blob_of_a_described_chip_names_its_device(v5e_chip):
    """What ``tools/aot_*.py --emit-store`` relies on: an executable
    compiled for a described chip (never loaded) serializes, and its
    blob carries that chip's device id for the host that loads it."""
    import pickle

    import jax.numpy as jnp

    from deepspeech_tpu.utils import aotstore

    comp = jax.jit(lambda v: v * 2, in_shardings=v5e_chip,
                   out_shardings=v5e_chip).lower(
        jax.ShapeDtypeStruct((8, 128), jnp.float32)).compile()
    ids = pickle.loads(aotstore.serialize_compiled(comp))[3]
    assert ids == [d.id for d in v5e_chip.device_set]


def test_on_tpu_assume_override(monkeypatch):
    """DS2N_ASSUME_TPU=1 (tools/aot_tpu.py): 'auto' impls must resolve
    exactly as on the chip while the runtime backend is cpu, so the
    AOT lowering emits the Pallas/Mosaic kernels."""
    from deepspeech_tpu.utils import impl

    monkeypatch.delenv("DS2N_ASSUME_TPU", raising=False)
    assert impl.on_tpu() is False  # conftest pins the cpu backend
    assert impl.resolve_impl("auto", oracle="xla") == "xla"
    assert impl.interpret_default() is True
    monkeypatch.setenv("DS2N_ASSUME_TPU", "1")
    assert impl.on_tpu() is True
    assert impl.resolve_impl("auto", oracle="xla") == "pallas"
    assert impl.interpret_default() is False


def test_aot_topology_constructs(monkeypatch):
    """The AOT compiler oracle's foundation: a v5e TopologyDescription
    builds locally from the installed libtpu (no chip attached).
    tools/aot_tpu.py compiles the real train step against it; here we
    pin the cheap part — topology + device kind — so a libtpu/jax
    upgrade that breaks AOT is caught early."""
    for key, value in AOT_ENV.items():
        monkeypatch.setenv(key, value)
    from jax.experimental import topologies

    topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    assert len(topo.devices) == 4
    assert "v5" in str(topo.devices[0].device_kind).lower()
