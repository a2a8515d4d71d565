"""The readers of device time by layer (``layer_metrics/_layers.py``) on
a hand-made record: ``op_seconds`` keys as ``reduce/xplane.short_name``
makes them from the trace's instruction texts, and a layer table as a
driver would store it under ``counters["layer_table"]`` (two programs,
so that the same instruction name is in both), or, without one, the
program's own ``obs.layers.programs()``."""

import importlib

import pytest

import conftest  # noqa: F401  (puts the checkout on sys.path)
from benchmark.layer_metrics import _layers
from benchmark.reduce import xplane

STEP = "jit(step_fn)/"
FWD = STEP + "jvp(DeepSpeech2)/"
BWD = STEP + "transpose(jvp(DeepSpeech2))/"
KERNEL = ('custom-call(%a), custom_call_target="tpu_custom_call", '
          'frontend_attributes={kernel_metadata={"kernel":"gru_scan_bwd"}}')
LONG = "(" + ", ".join(["f32[850,32,5280]{2,1,0}"] * 2 + [
    "f32[27200,1760]{1,0}", "/*index=3*/f32[8,5280]{1,0}"]) + ")"

# (instruction text as the trace names the event, seconds over the
# window on one chip, the program that holds it, its op_name)
EVENTS = [
    ("%fusion.638 = bf16[32,850,14,256]{3,2,1,0:T(8,128)(2,1)} "
     "fusion(%p), kind=kOutput", 0.044, "train_step",
     FWD + "conv/conv1/conv_general_dilated"),
    ("%fusion.640 = bf16[32,850,14,256]{3,2,1,0} fusion(%p)", 0.056,
     "train_step", BWD + "conv/conv1/conv_general_dilated"),
    ("%fusion.641 = f32[1312]{0} fusion(%p)", 0.004, "train_step",
     FWD + "conv/bn1/reduce_sum"),
    ("%multiply_reduce_fusion.11 = (f32[], f32[1760,5280]{1,0}) "
     "fusion(%h, %g)", 0.165, "train_step",
     BWD + "rnn/rnn3/rnn_scan/dw_h/dot_general"),
    ("%fusion.277 = (f32[], bf16[1760,5280]{1,0}) fusion(%x, %g)", 0.09,
     "train_step", BWD + "rnn/rnn3/wx/dot_general"),
    ("%fusion.300 = bf16[32,850,1760]{2,1,0} fusion(%w, %g)", 0.06,
     "train_step", BWD + "rnn/rnn3/wx/dot_general"),
    ("%fusion.245 = bf16[32,850,5280]{2,1,0} fusion(%x, %w)", 0.064,
     "train_step", FWD + "rnn/rnn3/wx/dot_general"),
    (f"%gru_scan_bwd.19 = {LONG} {KERNEL}", 0.172, "train_step",
     BWD + "rnn/rnn3/rnn_scan/pallas_call"),
    ("%fusion.900 = f32[1760,5280]{1,0} fusion(%m, %g)", 0.004,
     "train_step", STEP + "optimizer/mul"),
    ("%fusion.901 = f32[]{} fusion(%g)", 0.001, "train_step",
     STEP + "grad_norm/reduce_sum"),
    ("%fusion.902 = f32[32,850,29]{2,1,0} fusion(%x)", 0.006,
     "train_step", STEP + "jvp(DeepSpeech2)/add"),
    # a loop and its body: the body's events are events of their own
    ("%while.371 = (s32[], f32[8,64]{1,0}) while(%t), body=%b", 0.5,
     "lm_decode", "jit(_decode)/while"),
    ("%fusion.12 = f32[256,163840]{1,0} fusion(%h, %e)", 0.2, "lm_decode",
     "jit(_decode)/while/body/LFM2ASR.step/lm_head/dot_general"),
    ("%fusion.13 = s32[256]{0} fusion(%l)", 0.05, "lm_decode",
     "jit(_decode)/while/body/lm_head/argmax"),
    ("%fusion.14 = f32[2048,7168]{1,0} fusion(%y, %w)", 0.105,
     "lm_decode", "jit(_decode)/while/body/LFM2ASR.step/checkpoint/layer1/"
     "layer1.residual/moe/moe_combine/scatter-add"),
    ("%fusion.15 = s32[2048]{0} fusion(%k)", 0.03, "lm_decode",
     "jit(_decode)/while/body/LFM2ASR.step/checkpoint/layer1/"
     "layer1.residual/moe/moe_dispatch/jit(argsort)/sort"),
    ("%fusion.16 = f32[256,192]{1,0} fusion(%x, %r)", 0.02, "lm_decode",
     "jit(_decode)/while/body/LFM2ASR.step/checkpoint/layer1/"
     "layer1.residual/moe/moe.route/moe_route/dot_general"),
    ("%fusion.17 = bf16[2048,4096]{1,0} fusion(%x, %w)", 0.3, "lm_decode",
     "jit(_decode)/while/body/LFM2ASR.step/checkpoint/layer1/"
     "layer1.residual/moe/moe_gmm/ragged_dot"),
    # nobody's: an eager operation between two programs
    ("%concatenate.3 = s32[256]{0} concatenate(%a, %b)", 0.002, None, ""),
]
# The same name and shape in a second program, under another layer.
TWICE = ("%fusion.7 = f32[8,64]{1,0} fusion(%a)", 0.01,
         {"lm_prefill": "jit(_prefill)/LFM2ASR.prefill/embed/prefix/"
          "dot_general",
          "lm_decode": "jit(_decode)/while/body/lm_head/argmax"})
# The same name in both, another shape in the other: told apart.
APART = ("%fusion.8 = f32[32,212,7168]{2,1,0} fusion(%a)", 0.02,
         {"lm_prefill": ("jit(_prefill)/LFM2ASR.prefill/checkpoint/layer1/"
                         "layer1.residual/latent_attention/attn/attn_out/"
                         "dot_general", "f32[32,212,7168]{2,1,0}"),
          "lm_decode": ("jit(_decode)/while/body/lm_head/argmax",
                        "s32[256]{0}")})

UNITS = 2


def scope(text, op_name, shape=None):
    head, _, rest = text.partition(" = ")
    if shape is None:
        shape = rest[:rest.index(") ") + 1] if rest.startswith("(") \
            else rest.split(" ", 1)[0]
    opcode = rest[len(shape):].strip().split("(", 1)[0]
    return head, [op_name, shape, opcode]


def record(table=True, units=UNITS):
    op_seconds, stored = {}, {}
    for text, seconds, program, op_name in EVENTS:
        op_seconds[xplane.short_name(text)] = seconds
        if program is not None:
            head, row = scope(text, op_name)
            stored.setdefault(program, {})[head] = row
    text, seconds, where = TWICE
    op_seconds[xplane.short_name(text)] = seconds
    for program, op_name in where.items():
        head, row = scope(text, op_name)
        stored.setdefault(program, {})[head] = row
    text, seconds, where = APART
    op_seconds[xplane.short_name(text)] = seconds
    for program, (op_name, shape) in where.items():
        head, row = scope(text, op_name, shape)
        stored[program][head] = row
    return {"driver": "train", "units": units, "chips": 1,
            "counters": {"layer_table": stored} if table else {},
            "trace": {"op_seconds": op_seconds}}


def read(name, rec):
    return importlib.import_module(
        f"benchmark.layer_metrics.{name}").read(rec)


WANT = {  # ms a unit and chip
    "conv_frontend_ms": 1e3 * (0.044 + 0.056 + 0.004) / UNITS,
    "rnn_dw_h_ms": 1e3 * 0.165 / UNITS,
    "rnn_wx_bwd_ms": 1e3 * (0.09 + 0.06) / UNITS,
    "optimizer_ms": 1e3 * (0.004 + 0.001) / UNITS,
    "lm_head_ms": 1e3 * (0.2 + 0.05) / UNITS,
    "moe_dispatch_ms": 1e3 * (0.105 + 0.03 + 0.02) / UNITS,
}
LEAF = sum(s for _, s, _, _ in EVENTS) - 0.5 + TWICE[1] + APART[1]
OUTSIDE = 0.006 + 0.002 + TWICE[1]   # unnamed, unmatched, ambiguous


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_layers_time_is_read_per_unit_and_chip(name):
    assert read(name, record()) == pytest.approx(WANT[name])
    # twice the units, half the milliseconds a unit
    assert read(name, record(units=2 * UNITS)) \
        == pytest.approx(WANT[name] / 2)


def test_named_share_leaves_containers_out_and_counts_the_rest():
    rec = record()
    assert read("layer_named_device_pct", rec) == pytest.approx(
        100 * (LEAF - OUTSIDE) / LEAF)
    joined = rec["trace"]["layers"]
    assert joined["leaf_ms"] == pytest.approx(1e3 * LEAF / UNITS)
    # The loop's 0.5 s is nowhere: its body's events were counted.
    assert sum(joined["ms"].values()) == pytest.approx(joined["leaf_ms"])
    assert not any("while" in k for k in joined["ms"])


def test_the_join_keeps_what_it_could_not_name_apart():
    rec = record()
    ms = _layers.by_layer(rec)["ms"]
    assert ms["(ambiguous).fwd"] == pytest.approx(1e3 * TWICE[1] / UNITS)
    assert ms["(unmatched).fwd"] == pytest.approx(1e3 * 0.002 / UNITS)
    assert ms["(unnamed).fwd"] == pytest.approx(1e3 * 0.006 / UNITS)
    # One name, two programs, two shapes: the shape tells whose it is.
    assert ms["attn_out.fwd"] == pytest.approx(1e3 * APART[1] / UNITS)
    assert ms["rnn_wx.fwd"] == pytest.approx(1e3 * 0.064 / UNITS)
    assert ms["moe_gmm.fwd"] == pytest.approx(1e3 * 0.3 / UNITS)


def test_the_mosaic_share_of_a_layer_is_kept_beside_it():
    joined = _layers.by_layer(record())
    assert joined["mosaic_ms"] == {
        "rnn_scan": pytest.approx(1e3 * 0.172 / UNITS)}
    assert joined["ms"]["rnn_scan.bwd"] == pytest.approx(
        1e3 * 0.172 / UNITS)


def test_a_key_cut_at_96_characters_still_finds_its_instruction():
    key = xplane.short_name(EVENTS[7][0])
    assert len(key) == 96 and "[mosaic]" in key
    head, opcode, shape, mosaic = _layers.parse(key)
    assert (head, opcode, mosaic) == ("%gru_scan_bwd.19", "custom-call",
                                      True)
    assert _layers._shape(LONG).startswith(shape) and "{" not in shape


def test_the_join_is_made_once_a_record():
    rec = record()
    first = _layers.by_layer(rec)
    rec["counters"]["layer_table"] = {}
    assert _layers.by_layer(rec) is first
    assert read("rnn_dw_h_ms", rec) == pytest.approx(WANT["rnn_dw_h_ms"])


@pytest.mark.parametrize("name", sorted(WANT) + ["layer_named_device_pct"])
def test_no_table_no_value_and_nothing_raised(name):
    """A record of a run that watched nothing (or of a program without
    ``obs/layers.py``), an untraced record, a record without units."""
    from deepspeech_tpu.obs import layers

    layers.reset()
    assert read(name, record(table=False)) is None
    assert read(name, {**record(), "trace": None}) is None
    assert read(name, record(units=0)) is None


def test_a_program_without_the_layer_reads_none_not_zero():
    rec = record()
    del rec["counters"]["layer_table"]["lm_decode"]
    del rec["counters"]["layer_table"]["lm_prefill"]
    assert read("lm_head_ms", rec) is None
    assert read("moe_dispatch_ms", rec) is None
    assert read("conv_frontend_ms", rec) == pytest.approx(
        WANT["conv_frontend_ms"])


def test_an_unknown_layer_name_raises():
    with pytest.raises(ValueError, match="LAYERS"):
        _layers.ms(record(), ["conv"])


def test_without_a_stored_table_the_programs_own_is_read():
    """The table of this process (``obs.layers.programs()``) where the
    record stores none: a jitted function watched as a dispatch site
    does, its compiled instructions as the trace would name them."""
    import jax
    import jax.numpy as jnp

    from deepspeech_tpu.obs import layers

    @jax.jit
    def step(x, w):
        with jax.named_scope("optimizer"):
            return jnp.tanh(x @ w) * 2.0

    x, w = jnp.ones((8, 16)), jnp.ones((16, 4))
    step(x, w).block_until_ready()
    layers.reset()
    try:
        layers.watch("train_step", step, (x, w))
        scopes = layers.programs()["train_step"].scopes
        ops = {name: s for name, s in scopes.items()
               if layers.layer_of(s.op_name)[0] == "optimizer"
               and s.opcode not in _layers.CONTAINERS}
        assert ops
        rec = {"units": 4, "counters": {}, "trace": {"op_seconds": {
            f"{name} {s.opcode} {_layers._LAYOUT.sub('', s.shape)}": 0.01
            for name, s in ops.items()}}}
        assert read("optimizer_ms", rec) == pytest.approx(
            1e3 * 0.01 * len(ops) / 4)
        assert read("layer_named_device_pct", rec) == pytest.approx(100)
        assert read("conv_frontend_ms", rec) is None
        assert rec["trace"]["layers"]["table_s"] == {
            "train_step": pytest.approx(0, abs=5), "lowerings": 0,
            "compiles": 0}
    finally:
        layers.reset()
