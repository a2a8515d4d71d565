"""Device time under the program's own layer names (``obs/layers.py``):
the instruction-to-scope table of the programs the loops compiled, the
scopes the code opens, and the watch that costs nothing when the tracer
is off.

Real loops at toy sizes on the CPU, as ``tests/test_host_turn.py``
builds them: ``Trainer.fit`` of the ``ctc`` objective (through the
interpreted Pallas scan and CTC kernels, so that the scan's VJP and its
``dw_h`` are in the program) and of the ``lm`` objective, and
``LMGreedy.transcribe``. Nothing here reads a clock or a device time.
"""

import collections
import dataclasses
import io
import types

import jax
import jax.monitoring
import pytest

from deepspeech_tpu import obs
from deepspeech_tpu.obs import layers

LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
_heard = collections.Counter()
jax.monitoring.register_event_duration_secs_listener(
    lambda event, duration, **kw: _heard.update([event]))


def ctc_trainer():
    """``tests/test_host_turn.ctc_trainer`` through the kernels."""
    from deepspeech_tpu.config import get_config
    from deepspeech_tpu.data import CharTokenizer
    from deepspeech_tpu.train import Trainer, _SyntheticPipeline
    from deepspeech_tpu.utils.logging import JsonlLogger

    cfg = get_config("dev_slice")
    cfg = dataclasses.replace(
        cfg,
        model=dataclasses.replace(cfg.model, rnn_hidden=16, rnn_layers=1,
                                  conv_channels=(4, 4), dtype="float32",
                                  rnn_impl="pallas"),
        data=dataclasses.replace(cfg.data, batch_size=8,
                                 bucket_frames=(64,), max_label_len=16),
        train=dataclasses.replace(cfg.train, checkpoint_dir="",
                                  log_every=1, warmup_steps=10,
                                  loss_impl="pallas"))
    pipe = _SyntheticPipeline(cfg, n_utts=16, frames=64, label_len=4)
    return Trainer(cfg, pipe, CharTokenizer.english(),
                   logger=JsonlLogger(echo=False))


def lm_trainer():
    from test_host_turn import lm_trainer

    return lm_trainer()


def engine_call():
    from test_axk1 import batch, init, toy

    from deepspeech_tpu.decode.lm_greedy import LMGreedy

    cfg, b = toy(), batch()
    engine = LMGreedy(cfg, init(cfg, b), {})
    return lambda: engine.transcribe(b[0], b[1])


def traced(run):
    """``run()`` once with the tracer off and once with it on: what was
    watched after each, the tables, and what jax reported while they
    were resolved."""
    layers.reset()
    run()
    off = dict(layers._watched)
    obs.configure(enabled=True, sink=io.StringIO())
    try:
        run()
    finally:
        obs.configure(enabled=False)
    watched = dict(layers._watched)
    before = collections.Counter(_heard)
    programs = layers.programs()
    heard = collections.Counter(_heard) - before
    again = layers.programs()
    layers.reset()
    return types.SimpleNamespace(off=off, watched=watched, heard=heard,
                                 programs=programs, again=again)


@pytest.fixture(scope="module")
def ctc():
    trainer = ctc_trainer()
    epochs = iter(range(1, 3))
    return traced(lambda: trainer.fit(next(epochs)))


@pytest.fixture(scope="module")
def lm():
    trainer = lm_trainer()
    epochs = iter(range(1, 3))
    return traced(lambda: trainer.fit(next(epochs)))


@pytest.fixture(scope="module")
def serving():
    return traced(engine_call())


@pytest.fixture(scope="module")
def runs(ctc, lm, serving):
    return {"ctc": ctc, "lm": lm, "serving": serving}


RUNS = ["ctc", "lm", "serving"]
PROGRAMS = {"ctc": {"train_step"}, "lm": {"train_step"},
            "serving": {"lm_prefill", "lm_decode"}}


def found(run):
    """{(layer, direction): instructions} over a run's programs."""
    out = collections.Counter()
    for program in run.programs.values():
        for scope in program.scopes.values():
            out[layers.layer_of_instruction(scope.opcode,
                                            scope.op_name)] += 1
    return out


@pytest.mark.parametrize("which", RUNS)
def test_the_dispatched_programs_are_watched_and_resolved(runs, which):
    run = runs[which]
    assert set(run.watched) == set(run.programs) == PROGRAMS[which]
    for program in run.programs.values():
        assert program.seconds >= 0 and len(program.scopes) > 100
        assert all(name.startswith("%") for name in program.scopes)
    # Asked again, nothing is resolved again.
    assert all(run.again[k] is run.programs[k] for k in run.programs)


@pytest.mark.parametrize("which", RUNS)
def test_every_instruction_maps_into_the_vocabulary(runs, which):
    fusions = 0
    for program in runs[which].programs.values():
        for scope in program.scopes.values():
            layer, direction = layers.layer_of_instruction(
                scope.opcode, scope.op_name)
            assert layer in layers.LAYERS or layer == layers.UNNAMED
            assert direction in layers.DIRECTIONS
            fusions += scope.opcode == "fusion"
    assert fusions > 20


OWNED = {
    "ctc": [("optimizer", "fwd"), ("ctc_loss", "fwd"), ("ctc_loss", "bwd"),
            ("rnn_dw_h", "bwd"), ("rnn_scan", "fwd"), ("rnn_scan", "bwd"),
            ("rnn_wx", "fwd"), ("rnn_wx", "bwd"), ("conv_frontend", "fwd"),
            ("conv_frontend", "bwd"), ("norm", "fwd"), ("head", "bwd")],
    "lm": [("optimizer", "fwd"), ("lm_head", "fwd"), ("lm_head", "bwd"),
           ("moe_dispatch", "fwd"), ("moe_dispatch", "recompute"),
           ("moe_combine", "fwd"), ("moe_combine", "bwd"),
           ("moe_route", "fwd"), ("moe_gmm", "bwd"), ("embed", "fwd"),
           ("attention", "recompute"), ("attn_out", "bwd"),
           ("short_conv", "fwd"), ("mlp", "bwd"), ("norm", "recompute")],
    "serving": [("lm_head", "fwd"), ("cache_update", "fwd"),
                ("latent_attention", "fwd"), ("attn_out", "fwd"),
                ("moe_shared", "fwd"), ("moe_dispatch", "fwd"),
                ("moe_combine", "fwd"), ("moe_route", "fwd"),
                ("embed", "fwd"), ("mlp", "fwd")],
}


@pytest.mark.parametrize("which, layer, direction", [
    (which, *pair) for which in RUNS for pair in OWNED[which]])
def test_a_scope_owns_instructions(runs, which, layer, direction):
    assert found(runs[which])[(layer, direction)] > 0


@pytest.mark.parametrize("which", RUNS)
def test_a_served_program_has_no_backward(runs, which):
    directions = {d for (_, d), n in found(runs[which]).items() if n}
    assert ("bwd" in directions) == (which != "serving")


@pytest.mark.parametrize("which", RUNS)
def test_every_product_with_a_name_has_a_layer(runs, which):
    """A ``dot`` or a ``convolution`` that carries an ``op_name`` is in
    a layer, every one. (The CPU compiler rewrites a convolution's
    gradients into new instructions without metadata, at most two a
    conv layer; the chip's are fusions that keep theirs.)"""
    named = bare = 0
    for program in runs[which].programs.values():
        for name, scope in program.scopes.items():
            if scope.opcode not in ("dot", "convolution"):
                continue
            if not scope.op_name:
                bare += 1
                continue
            layer, _ = layers.layer_of(scope.op_name)
            assert layer != layers.UNNAMED, (name, scope.op_name)
            named += 1
    assert named >= 8 and bare <= (4 if which == "ctc" else 0)


@pytest.mark.parametrize("which", RUNS)
def test_tracer_off_nothing_is_watched_or_kept(runs, which):
    assert runs[which].off == {}


@pytest.mark.parametrize("which", RUNS)
def test_watch_keeps_no_device_buffer(runs, which):
    for jitted, args in runs[which].watched.values():
        leaves = jax.tree.leaves(args)
        assert len(leaves) > 5
        assert not any(isinstance(x, jax.Array) for x in leaves)
        assert any(isinstance(x, jax.ShapeDtypeStruct) for x in leaves)


@pytest.mark.parametrize("which", RUNS)
def test_resolving_the_tables_lowers_and_compiles_nothing(runs, which):
    heard = runs[which].heard
    assert heard[LOWER] == 0 and heard[COMPILE] == 0


def test_watch_keeps_the_first_call_of_a_program():
    layers.reset()
    try:
        layers.watch("p", "first", (1, None))
        layers.watch("p", "second", (2,))
        assert layers._watched == {"p": ("first", (1, None))}
    finally:
        layers.reset()
    assert layers._watched == {} and layers.programs() == {}


@pytest.mark.parametrize("name", ["", "rnn", "conv", "moe_dispatch_ms",
                                  layers.UNNAMED])
def test_an_unknown_layer_name_raises(name):
    with pytest.raises(ValueError, match="LAYERS"):
        layers.check(name)
    assert all(layers.check(known) == known for known in layers.LAYERS)


STEP = "jit(step_fn)/"
FWD, BWD = STEP + "jvp(DeepSpeech2)/", STEP + "transpose(jvp(DeepSpeech2))/"
LM = "jit(step_fn)/jvp(LFM2ASR.loss)/LFM2ASR.hidden/"
LM_BWD = ("jit(step_fn)/transpose(jvp(LFM2ASR.loss))/LFM2ASR.hidden/"
          "jvp(LFM2ASR.loss)/LFM2ASR.hidden/checkpoint/")
STEP_OF = "jit(_decode)/while/body/LFM2ASR.step/checkpoint/"


@pytest.mark.parametrize("op_name, want", [
    (FWD + "conv/conv1/conv_general_dilated", ("conv_frontend", "fwd")),
    (BWD + "conv/bn0/mul", ("conv_frontend", "bwd")),
    (FWD + "rnn/rnn3/wx/dot_general", ("rnn_wx", "fwd")),
    (BWD + "rnn/rnn3/wx/dot_general", ("rnn_wx", "bwd")),
    (FWD + "rnn/rnn3/bn/rsqrt", ("norm", "fwd")),
    (FWD + "rnn/rnn3/rnn_scan/pallas_call", ("rnn_scan", "fwd")),
    (BWD + "rnn/rnn3/rnn_scan/dw_h/dot_general", ("rnn_dw_h", "bwd")),
    (BWD + "rnn/rnn3/mul", ("rnn_scan", "bwd")),
    # the primitive ``transpose`` is no direction
    (FWD + "rnn/rnn3/rnn_scan/transpose", ("rnn_scan", "fwd")),
    (FWD + "bn_out/sub", ("norm", "fwd")),
    (BWD + "head/dot_general", ("head", "bwd")),
    (STEP + "transpose(jvp())/ctc_loss/while/body/add", ("ctc_loss", "bwd")),
    (STEP + "optimizer/mul", ("optimizer", "fwd")),
    (STEP + "grad_norm/reduce_sum", ("grad_norm", "fwd")),
    (STEP + "add", (layers.UNNAMED, "fwd")),
    ("", (layers.UNNAMED, "fwd")),
    ("state.params['rnn']['rnn0']['wx']['kernel']", ("rnn_wx", "fwd")),
    ("state.opt_state.inner_state[0][0].mu[\\'head\\'][\\'kernel\\']",
     ("optimizer", "fwd")),
    ("batch['features']", (layers.UNNAMED, "fwd")),
    (LM + "embed/prefix/dot_general", ("embed", "fwd")),
    (LM + "layer2/layer2.residual/conv/in_proj/dot_general",
     ("short_conv", "fwd")),
    (LM + "layer2/layer2.residual/attn/gqa_attn_global/exp",
     ("attention", "fwd")),
    (LM + "layer2/layer2.residual/attn/attn_out/o/dot_general",
     ("attn_out", "fwd")),
    (LM + "layer2/layer2.residual/latent_attention/attn/dot_general",
     ("latent_attention", "fwd")),
    ("params['layer0']['attn']['kv_b']", ("latent_attention", "fwd")),
    ("params['layer0']['attn']['q']['kernel']", ("attention", "fwd")),
    ("params['layer0']['attn']['o']", ("attn_out", "fwd")),
    (LM + "layer2/layer2.residual/ffn/w1/dot_general", ("mlp", "fwd")),
    (LM + "layer2/layer2.residual/ffn_norm/mul", ("norm", "fwd")),
    (LM + "layer2/layer2.residual/add", (layers.UNNAMED, "fwd")),
    (LM + "layer2/layer2.residual/moe/moe.route/moe_route/top_k",
     ("moe_route", "fwd")),
    (LM + "layer2/moe_route_pre_attn/moe/moe.route/moe_route/dot_general",
     ("moe_route", "fwd")),
    (LM + "layer2/layer2.residual/moe/moe_dispatch/jit(argsort)/sort",
     ("moe_dispatch", "fwd")),
    (LM + "layer2/layer2.residual/moe/reshape", ("moe_dispatch", "fwd")),
    (LM + "layer2/layer2.residual/moe/moe_gmm/ragged_dot",
     ("moe_gmm", "fwd")),
    ("params['layer1']['moe']['w13']", ("moe_gmm", "fwd")),
    (LM_BWD + "layer2/layer2.residual/moe/moe_combine/scatter-add",
     ("moe_combine", "bwd")),
    (LM_BWD + "rematted_computation/layer2/layer2.residual/moe/"
     "moe_dispatch/jit(_take)/gather", ("moe_dispatch", "recompute")),
    (LM + "layer2/layer2.residual/moe/moe_shared/shared/w1/dot_general",
     ("moe_shared", "fwd")),
    (LM + "layer2/layer2.residual/mhc/op_hc/dot_general", ("mhc", "fwd")),
    # the hyper-connection's Mosaic calls, as the compiled programs of
    # xing4_29b_a4b name them (PR 52): in the module and beside it
    ("jit(_prefill)/LFM2ASR.prefill/checkpoint/layer2/layer2.residual/mhc/"
     "ffn_hc/jit(read)/mhc_read/pallas_call", ("mhc", "fwd")),
    ("jit(_decode)/while/body/verify/LFM2ASR.verify/checkpoint/layer0/"
     "layer0.residual/mhc/jit(write)/mhc_write/pallas_call", ("mhc", "fwd")),
    ("jit(_decode)/while/body/mtp_draft/LFM2ASR.draft/draft0/layer/"
     "layer.residual/mhc/op_hc/jit(read)/mhc_read/pallas_call", ("mhc", "fwd")),
    (LM + "layer2/layer2.residual/ssm_mixer/mixer/ssd_scan/pallas_call",
     ("ssm_mixer", "fwd")),
    (LM + "out_norm/mul", ("norm", "fwd")),
    ("jit(step_fn)/jvp(LFM2ASR.loss)/lm_head/dot_general",
     ("lm_head", "fwd")),
    ("jit(_decode)/while/body/lm_head/argmax", ("lm_head", "fwd")),
    (STEP_OF + "layer1/layer1.residual/latent_attention/attn/"
     "cache_update/scatter", ("cache_update", "fwd")),
    ("jit(_decode)/while/body/mtp_draft/LFM2ASR.draft/draft0/eh_proj/"
     "dot_general", ("draft", "fwd")),
    ("jit(_decode)/while/body/mtp_draft/LFM2ASR.draft/draft0/layer/"
     "layer.residual/moe/moe_combine/scatter-add", ("moe_combine", "fwd")),
    ("jit(_decode)/while/body/verify/LFM2ASR.verify/embed/jit(_take)/gather",
     ("embed", "fwd")),
    ("jit(step_fn)/jvp(RNNTModel.loss)/enc/lstmp2/wx/dot_general",
     ("rnn_wx", "fwd")),
    ("jit(step_fn)/transpose(jvp(RNNTModel.loss))/enc/lstmp2/rnn_scan/"
     "dw_h/dot_general", ("rnn_dw_h", "bwd")),
    ("jit(step_fn)/jvp(RNNTModel.loss)/joint/rnnt_joint/while/body/tanh",
     ("rnnt_joint", "fwd")),
    ("jit(step_fn)/transpose(jvp(RNNTModel.loss))/joint/rnnt_lattice/"
     "while/body/logaddexp", ("rnnt_lattice", "bwd")),
    # a fusion that merged two paths: the first that has a name
    (STEP + "mul;" + FWD + "head/add", ("head", "fwd")),
])
def test_layer_of_reads_the_path_by_its_segments(op_name, want):
    assert layers.layer_of(op_name) == want


def test_a_collective_is_a_collective_wherever_it_was_put():
    path = BWD + "rnn/rnn3/wx/dot_general"
    assert layers.layer_of_instruction("all-reduce-start", path) \
        == ("collective", "bwd")
    assert layers.layer_of_instruction("fusion", path) == ("rnn_wx", "bwd")


HLO = """HloModule jit_step_fn, is_scheduled=true, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%fused_computation.1 (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  ROOT %multiply.3 = f32[4]{0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(step_fn)/optimizer/mul" stack_frame_id=3}
}

%body.7 (arg: (s32[], f32[4])) -> (s32[], f32[4]) {
  %arg = (s32[], f32[4]{0}) parameter(0)
  %dot.9 = f32[4]{0:T(128)} dot(%x, %y), lhs_contracting_dims={}, metadata={op_name="jit(step_fn)/jvp(DeepSpeech2)/rnn/rnn0/wx/dot_general"}
  ROOT %tuple.2 = (s32[], f32[4]{0}) tuple(%i, %dot.9)
}

ENTRY %main.65 (x.1: f32[4]) -> f32[4] {
  %x.1 = f32[4]{0} parameter(0), metadata={op_name="x"}
  %while.3 = (s32[], f32[4]{0}) while(%t), condition=%cond.5, body=%body.7, metadata={op_name="jit(step_fn)/jvp(DeepSpeech2)/rnn/rnn0/while"}
  %gru_scan_bwd.19 = (f32[850,32,5280]{2,1,0}, /*index=1*/f32[8,5280]{1,0}) custom-call(%a), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
"kernel":"gru_scan_bwd",
"variant":"pinned"
}}, metadata={op_name="jit(step_fn)/transpose(jvp(DeepSpeech2))/rnn/rnn0/rnn_scan/gru_scan_bwd/pallas_call" stack_frame_id=279}, backend_config={"custom_call_config":{"body":"TUzvUg = FNTElS"}}
  %pallas_call.35 = f32[850,32,5280]{2,1,0} get-tuple-element(%gru_scan_bwd.19), index=0, frontend_attributes={kernel_metadata={
"kernel":"gru_scan_bwd"
}}
  ROOT %fusion.638 = f32[4]{0} fusion(%x.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step_fn)/optimizer/mul"}
}
"""


def test_instruction_scopes_reads_every_computation_of_a_module():
    table = layers.instruction_scopes(HLO)
    assert set(table) == {
        "%param_0.1", "%multiply.3", "%arg", "%dot.9", "%tuple.2", "%x.1",
        "%while.3", "%gru_scan_bwd.19", "%pallas_call.35", "%fusion.638"}
    assert table["%fusion.638"] == layers.Scope(
        "jit(step_fn)/optimizer/mul", "f32[4]{0}", "fusion")
    assert table["%dot.9"].shape == "f32[4]{0:T(128)}"
    assert table["%tuple.2"] == layers.Scope(
        "", "(s32[], f32[4]{0})", "tuple")
    kernel = table["%gru_scan_bwd.19"]
    assert kernel.opcode == "custom-call" and kernel.shape.endswith("})")
    assert layers.layer_of(kernel.op_name) == ("rnn_scan", "bwd")
    assert table["%while.3"].opcode == "while"
    # The next instruction's facts are its own, not the call's name.
    assert table["%pallas_call.35"] == layers.Scope(
        "", "f32[850,32,5280]{2,1,0}", "get-tuple-element")
    assert table["%arg"].op_name == "" and table["%x.1"].op_name == "x"
