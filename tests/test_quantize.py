"""Weight-only int8 PTQ (utils/quantize.py): round-trip bounds, byte
accounting, and decode-surface behavior."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeech_tpu.config import get_config
from deepspeech_tpu.models import create_model
from deepspeech_tpu.utils.quantize import (dequantize_params,
                                           quantization_error,
                                           quantize_params)


@pytest.fixture(scope="module")
def model_and_vars():
    cfg = get_config("dev_slice")
    cfg = dataclasses.replace(
        cfg, model=dataclasses.replace(
            cfg.model, rnn_layers=2, rnn_hidden=32, conv_channels=(4, 4),
            vocab_size=16, dtype="float32"))
    model = create_model(cfg.model)
    rng = np.random.default_rng(0)
    feats = jnp.asarray(rng.normal(size=(2, 64, 161)), jnp.float32)
    lens = jnp.asarray([64, 48], jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), feats[:1], lens[:1],
                           train=False)
    return cfg, model, variables, feats, lens


def test_roundtrip_error_bound(model_and_vars):
    _, _, variables, _, _ = model_and_vars
    qtree, report = quantize_params(variables["params"])
    assert report["quantized"] > 0
    # int8 symmetric absmax: relative L2 error well under 1%.
    assert quantization_error(variables["params"], qtree) < 0.01


def test_byte_accounting(model_and_vars):
    _, _, variables, _, _ = model_and_vars
    _, report = quantize_params(variables["params"])
    # Kernels dominate this tree; int8 storage must land near 1/4 of
    # the f32 bytes (scales + unquantized leaves add the slack).
    assert report["bytes_after"] < 0.4 * report["bytes_before"]


def test_selective_quantization(model_and_vars):
    _, _, variables, _, _ = model_and_vars
    qtree, _ = quantize_params(variables["params"])
    # Recurrent + projection kernels quantized; biases and BN leaves
    # untouched.
    rnn0 = qtree["rnn"]["rnn0"]
    assert set(rnn0["wh_fw"]) == {"q", "scale"}
    assert rnn0["wh_fw"]["q"].dtype == jnp.int8
    assert set(rnn0["wx"]["kernel"]) == {"q", "scale"}
    assert isinstance(rnn0["bh_fw"], jnp.ndarray)
    assert isinstance(qtree["bn_out"]["scale"], jnp.ndarray)
    deq = dequantize_params(qtree)
    assert deq["rnn"]["rnn0"]["wh_fw"].dtype == jnp.float32


def test_stacked_pipeline_leaves_get_per_layer_scales():
    """Pipeline-stacked [L, d, G] recurrent leaves: one scale per
    (layer, channel), not one shared across layers — a wide layer must
    not coarsen a narrow layer's grid (ADVICE r3 #2)."""
    rng = np.random.default_rng(3)
    big = rng.normal(size=(16, 24)) * 10.0    # layer 0: wide range
    small = rng.normal(size=(16, 24)) * 0.01  # layer 1: narrow range
    stacked = {"rnn_pipe": {"wh_fw": jnp.asarray(
        np.stack([big, small]), jnp.float32)}}
    qtree, report = quantize_params(stacked)
    qleaf = qtree["rnn_pipe"]["wh_fw"]
    assert report["quantized"] == 1
    assert qleaf["scale"].shape == (2, 1, 24)
    deq = np.asarray(dequantize_params(qtree)["rnn_pipe"]["wh_fw"])
    # Per-layer scales keep the narrow layer's relative error at int8
    # grid level; a layer-shared scale would blow it up ~1000x.
    rel = (np.linalg.norm(deq[1] - small)
           / np.linalg.norm(small))
    assert rel < 0.01
    # Unstacked 2-D leaves keep the per-channel [C] scale shape.
    q2, _ = quantize_params({"wh_fw": jnp.asarray(big, jnp.float32)})
    assert q2["wh_fw"]["scale"].shape == (24,)


def test_quantized_forward_close(model_and_vars):
    cfg, model, variables, feats, lens = model_and_vars
    qtree, _ = quantize_params(variables["params"])
    ref, _ = model.apply(variables, feats, lens, train=False)

    @jax.jit
    def fwd(q):
        return model.apply(
            {"params": dequantize_params(q),
             "batch_stats": variables["batch_stats"]},
            feats, lens, train=False)[0]

    got = fwd(qtree)
    # ~0.4% weight perturbation stays a small logits perturbation.
    denom = float(jnp.abs(ref).max())
    assert float(jnp.abs(ref - got).max()) / denom < 0.05


def test_inferencer_quantize_mode_guards(model_and_vars):
    """sp decode modes still reject PTQ (they thread raw trees);
    invalid quantize values fail fast in every mode, including
    streaming (whose int8 support arrived in r4)."""
    from deepspeech_tpu.data import CharTokenizer
    from deepspeech_tpu.infer import Inferencer

    cfg, _, variables, _, _ = model_and_vars
    base = dataclasses.replace(
        cfg, model=dataclasses.replace(cfg.model, vocab_size=29))
    sp_cfg = dataclasses.replace(
        base, decode=dataclasses.replace(base.decode, mode="sp_greedy"))
    with pytest.raises(ValueError, match="offline"):
        Inferencer(sp_cfg, CharTokenizer.english(), variables["params"],
                   variables["batch_stats"], quantize="int8")
    stream_cfg = dataclasses.replace(
        base, decode=dataclasses.replace(base.decode, mode="streaming"))
    with pytest.raises(ValueError, match="int8"):
        Inferencer(stream_cfg, CharTokenizer.english(),
                   variables["params"], variables["batch_stats"],
                   quantize="int4")


def test_inferencer_quantized_greedy_runs(model_and_vars):
    from deepspeech_tpu.data import CharTokenizer
    from deepspeech_tpu.infer import Inferencer

    cfg, _, variables, feats, lens = model_and_vars
    cfg = dataclasses.replace(
        cfg, model=dataclasses.replace(cfg.model, vocab_size=29))
    model = create_model(cfg.model)
    variables = model.init(jax.random.PRNGKey(1), feats[:1], lens[:1],
                           train=False)
    inf = Inferencer(cfg, CharTokenizer.english(), variables["params"],
                     variables["batch_stats"], quantize="int8")
    batch = {"features": np.asarray(feats), "feat_lens": np.asarray(lens)}
    out = inf.decode_batch(batch)
    assert len(out) == 2 and all(isinstance(t, str) for t in out)


def test_inferencer_int8_lstm_kernel_path_matches_dequant(model_and_vars):
    """LSTM models get the same int8-in-kernel serving regime
    (lstm_scan_pallas_q): transcripts equal the XLA dequant path."""
    from deepspeech_tpu.data import CharTokenizer
    from deepspeech_tpu.infer import Inferencer
    from deepspeech_tpu.utils.quantize import keep_recurrent_q

    cfg, _, _, feats, lens = model_and_vars
    base = dataclasses.replace(cfg.model, vocab_size=29, rnn_type="lstm")
    model = create_model(base)
    variables = model.init(jax.random.PRNGKey(4), feats[:1], lens[:1],
                           train=False)
    batch = {"features": np.asarray(feats), "feat_lens": np.asarray(lens)}
    outs = {}
    for impl in ("pallas", "xla"):
        mc = dataclasses.replace(base, rnn_impl=impl)
        assert (keep_recurrent_q(mc) is not None) == (impl == "pallas")
        inf = Inferencer(dataclasses.replace(cfg, model=mc),
                         CharTokenizer.english(), variables["params"],
                         variables["batch_stats"], quantize="int8")
        outs[impl] = inf.decode_batch(batch)
    assert outs["pallas"] == outs["xla"]


def test_inferencer_int8_pipeline_ckpt_dequants_at_entry(model_and_vars):
    """pipeline_stages>1 + int8 + pallas: pipe_stack threads wh_*
    straight into gru_scan, so keep_q must stay off and the stacked
    leaves dequantize at entry (code-review r4 finding)."""
    from deepspeech_tpu.data import CharTokenizer
    from deepspeech_tpu.infer import Inferencer

    cfg, _, _, feats, lens = model_and_vars
    model_cfg = dataclasses.replace(cfg.model, vocab_size=29,
                                    rnn_impl="pallas", rnn_layers=3,
                                    pipeline_stages=2)
    model = create_model(model_cfg)
    variables = model.init(jax.random.PRNGKey(3), feats[:1], lens[:1],
                           train=False)
    inf = Inferencer(dataclasses.replace(cfg, model=model_cfg),
                     CharTokenizer.english(), variables["params"],
                     variables["batch_stats"], quantize="int8")
    out = inf.decode_batch({"features": np.asarray(feats),
                            "feat_lens": np.asarray(lens)})
    assert len(out) == 2 and all(isinstance(t, str) for t in out)


def test_inferencer_int8_kernel_path_matches_dequant(model_and_vars):
    """rnn_impl=pallas + int8 PTQ routes the recurrent matrices into
    gru_scan_pallas_q (in-kernel dequant, VERDICT r3 #7): transcripts
    must equal the dequantize-at-entry XLA path on the same qtree."""
    from deepspeech_tpu.data import CharTokenizer
    from deepspeech_tpu.infer import Inferencer
    from deepspeech_tpu.ops.scan_pallas import scan_route

    cfg, _, variables, feats, lens = model_and_vars
    assert scan_route("gru", "pallas", hidden=cfg.model.rnn_hidden,
                      int8=True).variant == "resident_q"
    model_cfg = dataclasses.replace(cfg.model, vocab_size=29)
    model = create_model(model_cfg)
    variables = model.init(jax.random.PRNGKey(2), feats[:1], lens[:1],
                           train=False)
    batch = {"features": np.asarray(feats), "feat_lens": np.asarray(lens)}
    outs = {}
    for impl in ("pallas", "xla"):
        c = dataclasses.replace(
            cfg, model=dataclasses.replace(model_cfg, rnn_impl=impl))
        inf = Inferencer(c, CharTokenizer.english(), variables["params"],
                         variables["batch_stats"], quantize="int8")
        if impl == "pallas":
            # The serving regime really engaged: wh leaves reach the
            # model still quantized.
            from deepspeech_tpu.utils.quantize import dequantize_params
            kept = dequantize_params(
                inf.params, keep=lambda p: p.endswith(("wh_fw", "wh_bw")))
            assert any(
                isinstance(l, dict) for l in
                jax.tree.leaves(kept, is_leaf=lambda x: isinstance(x, dict)
                                and set(x) == {"q", "scale"}))
        outs[impl] = inf.decode_batch(batch)
    assert outs["pallas"] == outs["xla"]


# -- scenario: a premium and a bulk replica behind one gateway ------------

def test_scenario_two_tier_pool_quantizes_once_and_keeps_tiers_apart(
        tiny_offline, obs_lint):
    """Full-precision ``premium`` and int8 ``bulk`` replicas (Pallas
    recurrent kernels) behind one tier-aware scheduler under a seeded
    mixed-tier replay: PTQ ran exactly once, at engine build, and
    quantized leaves; the int8 logits stay within the deterministic
    bound of ``test_quantized_forward_close``; under one byte budget
    the bulk ladder is strictly taller; each tier's gateway transcripts
    equal that tier's own solo decode (bulk is never upgraded); and
    the tier-labeled telemetry lints clean."""
    from scenario import (EDGES, NF, ManualClock, poisson_requests,
                          replay, solo_decode)
    from deepspeech_tpu.serving import (MicroBatchScheduler, Replica,
                                        ReplicaPool, ServingTelemetry,
                                        tier_max_batches)
    from deepspeech_tpu.utils import quantize as quant

    cfg = dataclasses.replace(tiny_offline.cfg, model=dataclasses.replace(
        tiny_offline.cfg.model, rnn_impl="pallas"))
    calls0 = quant.QUANTIZE_CALLS
    premium = tiny_offline.inferencer(cfg)
    bulk = tiny_offline.inferencer(cfg, quantize="int8")
    assert quant.QUANTIZE_CALLS - calls0 == 1
    assert (premium.quantize_calls, bulk.quantize_calls) == (0, 1)
    assert (premium.kernel_regime, bulk.kernel_regime) \
        == ("fp", "resident-q")
    report = bulk.quantize_report
    assert report["quantized"] > 0
    assert report["bytes_after"] < report["bytes_before"]

    model = create_model(cfg.model)
    rng = np.random.default_rng(0)
    feats = jnp.asarray(rng.normal(size=(2, 64, NF)), jnp.float32)
    lens = jnp.asarray([64, 48], jnp.int32)

    def logits(params):
        return model.apply({"params": params,
                            "batch_stats": tiny_offline.stats},
                           feats, lens, train=False)[0]

    ref = logits(tiny_offline.params)
    got = logits(dequantize_params(bulk.params))
    assert float(jnp.abs(ref - got).max()) \
        / float(jnp.abs(ref).max()) < 0.05

    per_row = max((report["bytes_before"] - report["bytes_after"]) // 8,
                  1)
    ladder = tier_max_batches(report, per_row,
                              report["bytes_before"] + 8 * per_row)
    assert ladder["bulk"] > ladder["premium"] > 0

    clock = ManualClock()
    tel = ServingTelemetry()
    pool = ReplicaPool(
        [Replica.from_inferencer("r0", premium, tier="premium",
                                 telemetry=tel, clock=clock),
         Replica.from_inferencer("r1", bulk, tier="bulk",
                                 telemetry=tel, clock=clock)],
        telemetry=tel, clock=clock)
    n = 12
    arrivals, reqs = poisson_requests(n)
    tiers = ["premium" if j % 2 == 0 else "bulk" for j in range(n)]
    sched = MicroBatchScheduler(
        EDGES, 4, clock=clock, pool=pool, telemetry=tel, max_queue=16,
        default_deadline=0.02,
        tier_max_batch={t: max(1, min(4, ladder[t])) for t in ladder})
    results = replay(sched, clock, arrivals, reqs, tiers=tiers)
    assert quant.QUANTIZE_CALLS - calls0 == 1      # none while serving
    assert len(results) == n
    engine = {"premium": premium, "bulk": bulk}
    for rid, r in results.items():
        j = int(rid[1:])
        assert r.status == "ok"
        assert r.text == solo_decode(engine[tiers[j]], reqs[j])
    c = tel.snapshot()["counters"]
    for t in ("premium", "bulk"):
        assert int(c[f'requests_ok{{tier="{t}"}}']) == n // 2
    assert {r.rid: r.stats()["rows"] > 0 for r in pool} \
        == {"r0": True, "r1": True}
    assert obs_lint(tel) == []
