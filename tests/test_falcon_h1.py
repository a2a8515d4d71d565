"""The Falcon-H1 block (a Mamba-2 mixer beside grouped-query attention
under one norm, muP multipliers; ``models/lfm2.py`` ``Mamba2Mixer``,
``DecoderLayer`` kind "ssm_attention"; ``ops/ssd_pallas.py``;
``decode/lm_greedy.py``) against the plain reference
(``benchmark/reference/falcon_h1_ref.py``) at the configuration file's
rehearsal widths on the CPU, float32, chunks of 8 positions: the two
kernels interpreted against their oracles over lengths and masks; the
decode form continuing a prefill's state against the sequence form
across a chunk's edge; prefill + forced steps through ``LMGreedy``
against the reference's full forward pass (logits, state, convolution
inputs, rows, both branches); every multiplier; a preset without
expert layers through the loop and its counters; ``Trainer`` saying why
it does not train the kernel path."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import falcon_h1_ref as ref
from deepspeech_tpu.config import get_config
from deepspeech_tpu.decode.lm_greedy import LMGreedy, _watched
from deepspeech_tpu.models import lfm2
from deepspeech_tpu.models.lfm2 import Mamba2Mixer, create_lfm2_model
from deepspeech_tpu.ops import ssd_pallas as ssd

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
U = 10           # max_label_len
FRAMES = 96      # 12 prefix positions of 8 frames: a chunk and a half
S = 24           # cache rows


def toy(**kw):
    """The preset at the configuration file's rehearsal widths."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "falcon_h1_34b.json")) as f:
        model = json.load(f)["rehearsal"]
    model = {k: tuple(v) if isinstance(v, list) else v
             for k, v in model.items()}
    model.update(lfm_seq_positions=S, **kw)
    c = get_config("falcon_h1_34b")
    return dataclasses.replace(
        c, model=dataclasses.replace(c.model, **model),
        data=dataclasses.replace(c.data, max_label_len=U, batch_size=4,
                                 bucket_frames=(FRAMES,)),
        decode=dataclasses.replace(c.decode, lm_prefill_rows=2,
                                   lm_watch_rows=4))


# Prefix positions 12 (the whole bucket), 8 (a chunk), 5 and 2 (shorter
# than the convolution's three inputs).
def batch(seed=0, lens=(96, 64, 40, 16), label_lens=(10, 3, 0, 9), v=64):
    rng = np.random.default_rng(seed)
    lens = np.asarray(lens, np.int32)
    ll = np.asarray(label_lens, np.int32)
    feats = rng.standard_normal((len(lens), FRAMES, 161)
                                ).astype(np.float32)
    feats *= np.arange(FRAMES)[None, :, None] < lens[:, None, None]
    labels = rng.integers(1, v, (len(lens), U)).astype(np.int32)
    labels *= np.arange(U)[None, :] < ll[:, None]
    return feats, lens, labels, ll


def init(cfg, b, seed=1):
    """The modules' own initialisers, matrices of size 1 after every
    product (the preset's std 0.02 at a width of 32 would leave every
    nonlinearity near its middle)."""
    v = create_lfm2_model(cfg.model, U).init(
        jax.random.PRNGKey(seed), *b, method="loss")
    return jax.tree.map(
        lambda x: x * (x.shape[-2] ** -0.5 / 0.02) if x.ndim >= 2 else x,
        v["params"])


def forced(labels, ll):
    out = np.full((labels.shape[0], U + 1), -1, np.int32)
    out[:, 0] = 0
    out[:, 1:] = np.where(np.arange(U)[None, :] < ll[:, None], labels, -1)
    return out


def served(cfg, params, b):
    engine = LMGreedy(cfg, params, {})
    feats, lens, labels, ll = b
    out = engine.transcribe(feats, lens, max_tokens=ll + 1,
                            forced=forced(labels, ll),
                            watch=np.arange(len(lens)))
    return engine, out


# -- the kernels ---------------------------------------------------------------

def scan_inputs(s, lens, b=2, h=4, p=8, g=2, n=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    lens = np.asarray(lens)
    return (jax.random.normal(ks[0], (b, s, h, p)),
            jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) - 2),
            -jnp.exp(jax.random.uniform(ks[2], (h,), maxval=2.7)),
            jax.random.normal(ks[3], (b, s, g, n)),
            jax.random.normal(ks[4], (b, s, g, n)),
            jax.random.normal(ks[5], (h,)),
            jnp.arange(s)[None, :] < lens[:, None])


@pytest.mark.parametrize("s, lens", [
    (5, (5, 2)),        # under one chunk; shorter than three positions
    (16, (16, 8)),      # whole chunks; a tail of one padded chunk
    (21, (21, 1)),      # a ragged tail; one position
    (8, (0, 8)),        # a stream without a position
    (37, (30, 17)),     # padded tails of different lengths in one batch
])
def test_chunk_scan_equals_the_positionwise_recurrence(s, lens):
    """``ssd_chunk_scan`` in chunks of 8, interpreted, against
    ``lax.scan`` over the positions: the outputs at every valid
    position and the state after each stream's LAST VALID position
    (padding leaves it alone)."""
    args = scan_inputs(s, lens)
    want_y, want_state = ssd.scan_oracle(*args)
    got_y, got_state = ssd.chunk_scan(*args, chunk=8, interpret=True)
    valid = np.asarray(args[-1])
    np.testing.assert_allclose(np.asarray(got_y)[valid],
                               np.asarray(want_y)[valid], atol=5e-6)
    np.testing.assert_allclose(got_state, want_state, atol=2e-6)
    # the state given out is the one after a - 1: the oracle over the
    # valid positions alone gives the same
    for r, a in enumerate(lens):
        alone = [v[r:r + 1, :a] if v.ndim > 1 and v.shape[0] == 2
                 else v for v in args]
        if a:
            np.testing.assert_allclose(
                got_state[r], ssd.scan_oracle(*alone)[1][0], atol=2e-6)
        else:
            assert not np.any(np.asarray(got_state[r]))


def step_inputs(b=5, h=4, p=8, g=2, n=16, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (jax.random.normal(ks[0], (b, h, n, p)),
            jax.random.normal(ks[1], (b, h, p)),
            jax.nn.softplus(jax.random.normal(ks[2], (b, h))),
            -jnp.exp(jax.random.normal(ks[3], (h,))),
            jax.random.normal(ks[4], (b, g, n)),
            jax.random.normal(ks[5], (b, g, n)),
            jax.random.normal(ks[6], (h,)))


@pytest.mark.parametrize("live", [
    (1, 1, 1, 1, 1), (0, 1, 0, 0, 1), (0, 0, 1, 1, 0), (1, 0, 0, 0, 0),
    (0, 0, 0, 0, 1), (0, 0, 0, 0, 0)])
def test_state_step_equals_its_oracle_and_leaves_idle_streams(live):
    """``ssd_state_step``, interpreted: a live stream's state and output
    are the plain update's; a stream that is not live keeps its state
    BIT for bit and gives zeros, wherever it stands among the live
    ones, and with none live at all."""
    args = step_inputs()
    live = jnp.asarray(live, bool)
    want_y, want_state = ssd.step_oracle(*args, live)
    got_y, got_state = ssd.state_step(*args, live, interpret=True)
    np.testing.assert_allclose(got_y, want_y, atol=5e-6)
    np.testing.assert_allclose(got_state, want_state, atol=2e-6)
    idle = ~np.asarray(live)
    assert np.array_equal(np.asarray(got_state)[idle],
                          np.asarray(args[0])[idle])
    assert not np.any(np.asarray(got_y)[idle])


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["oracles", "kernels"])
def test_steps_continue_a_prefills_state_across_a_chunks_edge(kernels):
    """The sequence form over 13 positions of a stream gives the state
    the decode form reaches from the sequence form over the first 6 and
    7 steps, which cross the chunk's edge at 8; and the same outputs."""
    x, dt, a, bm, cm, d, _ = scan_inputs(13, (13, 13))
    everything = jnp.ones((2, 13), bool)
    scan = (lambda *v: ssd.chunk_scan(*v, chunk=8, interpret=True)) \
        if kernels else ssd.scan_oracle
    step = (lambda *v: ssd.state_step(*v, interpret=True)) \
        if kernels else ssd.step_oracle
    want_y, want_state = scan(x, dt, a, bm, cm, d, everything)
    y, state = scan(x[:, :6], dt[:, :6], a, bm[:, :6], cm[:, :6], d,
                    everything[:, :6])
    ys = [y]
    for t in range(6, 13):
        y, state = step(state, x[:, t], dt[:, t], a, bm[:, t], cm[:, t],
                        d, jnp.ones(2, bool))
        ys.append(y[:, None])
    np.testing.assert_allclose(jnp.concatenate(ys, 1), want_y, atol=5e-6)
    np.testing.assert_allclose(state, want_state, atol=2e-6)


def test_the_mixer_takes_the_kernels_where_it_says(monkeypatch):
    """Heads of 128 on an assumed TPU: the mixer's two forms lower to
    the two named kernels, and give what the oracles give."""
    from jax.experimental.pallas import tpu as pltpu

    cfg = toy(ssm_d_ssm=256, ssm_heads=2, ssm_state=8, ssm_groups=1)
    mixer = Mamba2Mixer(cfg.model)
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 11, 32))
    valid = jnp.arange(11)[None, :] < jnp.asarray([11, 4])[:, None]
    params = mixer.init(jax.random.PRNGKey(1), u, valid)
    want, cache = mixer.apply(params, u, valid)
    new = jax.random.normal(jax.random.PRNGKey(2), (2, 1, 32))
    live = jnp.asarray([[True], [False]])
    want_step, want_cache = mixer.apply(params, new, live, cache)
    monkeypatch.setenv("DS2N_ASSUME_TPU", "1")
    with pltpu.force_tpu_interpret_mode():
        assert "name=ssd_chunk_scan" in str(jax.make_jaxpr(
            lambda: mixer.apply(params, u, valid))())
        assert "name=ssd_state_step" in str(jax.make_jaxpr(
            lambda: mixer.apply(params, new, live, cache))())
        got, got_cache = mixer.apply(params, u, valid)
        got_step, after = mixer.apply(params, new, live, got_cache)
    np.testing.assert_allclose(np.asarray(got)[np.asarray(valid)],
                               np.asarray(want)[np.asarray(valid)],
                               atol=2e-5)
    for g, w in zip(got_cache + after, cache + want_cache):
        np.testing.assert_allclose(g, w, atol=2e-5)
    np.testing.assert_allclose(got_step, want_step, atol=2e-5)
    # the stream that is not live kept its state and its inputs
    assert np.array_equal(after[0][1], got_cache[0][1])
    assert np.array_equal(after[1][1], got_cache[1][1])


# -- the served path against the reference ---------------------------------------

def test_prefill_then_decode_through_the_cache_equals_the_reference():
    """The served path with forced tokens: what decode step j emits
    after prefill and j steps through keys, values, the float32 state
    and the convolution's inputs is the reference's logit at that
    position of its full forward pass; the last layer's state and
    convolution inputs after prefill and after the last step, its rows
    and its two branches are the reference's."""
    cfg = toy()
    m = cfg.model
    b = batch()
    params = init(cfg, b)
    engine, out = served(cfg, params, b)
    last = engine.last_call
    want = jax.device_get(ref.forward(m, params, *b, S))
    seen, pre = last["decode_watch"], last["prefill_watch"]
    assert ref.rms_rel(seen["logits"], want["logits"],
                       want["steps"]) < 2e-5
    a_lens = -(-b[1] // 8)
    keys, values, state, conv = last["cache"][-1]
    assert state.dtype == jnp.float32 and state.shape == (4, 4, 16, 8)
    assert conv.shape == (4, 3, 96) and keys.shape == (4, S, 2, 8)
    # the state is kept [state, head]: the transpose of the equations'
    np.testing.assert_allclose(np.swapaxes(state, -1, -2),
                               want["state_last"], atol=2e-5)
    np.testing.assert_allclose(conv, want["conv_last"], atol=2e-5)
    # after prefill (rows 0-1: the first sub-batch's watched rows)
    np.testing.assert_allclose(np.swapaxes(pre["state"], -1, -2),
                               want["state_prefill"][:2], atol=2e-5)
    np.testing.assert_allclose(pre["conv"], want["conv_prefill"][:2],
                               atol=2e-5)
    held = np.arange(S)[None, :] <= (a_lens + b[3])[:, None]
    assert ref.rms_rel(keys, want["k"], held) < 2e-5
    assert ref.rms_rel(values, want["v"], held) < 2e-5
    at = np.clip(a_lens[:, None] + np.arange(U + 1)[None, :], 0, S - 1)
    prefix = np.arange(12)[None, :] < a_lens[:2, None]
    for key, name in (("branch_mixer", "mixer"), ("branch_attn", "attn")):
        at_text = np.take_along_axis(np.asarray(want[name]),
                                     at[..., None], 1)
        assert ref.rms_rel(seen[key], at_text, want["steps"]) < 2e-5
        assert ref.rms_rel(pre[key], np.asarray(want[name])[:2, :12],
                           prefix) < 2e-5
    # the stream of 2 prefix positions holds zeros before them
    assert not np.any(np.asarray(want["conv_prefill"])[3, 0])
    stats = out["stats"]
    np.testing.assert_array_equal(out["tokens"], b[3] + 1)
    assert stats["decode_steps"] == 11
    steps = int(np.sum(b[3] + 1))
    assert stats["state_updates"] == 2 * steps
    assert stats["idle_slot_steps"] == 11 * 4 - steps
    reach = sum(a + j + 1 for a, u in zip(a_lens, b[3])
                for j in range(u + 1))
    assert stats["rows_attended_global"] == 2 * reach
    parts = stats["decode_bytes"]
    assert parts["state"] == 2 * steps * 2 * 4 * 32 * 16
    assert parts["rows"] == 2 * reach * 2 * 2 * 8 * 4
    assert parts["head"] == 11 * 64 * 32 * 4
    layer = sum(x.size for x in jax.tree.leaves(params["layer0"]))
    assert parts["weights"] == 11 * 2 * layer * 4


def test_training_path_equals_reference():
    """``LFM2ASR.hidden`` over the packed sequences (the sequence form
    alone, no cache) gives the reference's logits."""
    cfg = toy()
    b = batch()
    params = init(cfg, b)
    model = create_lfm2_model(cfg.model, U)
    h, head, layout, _ = model.apply({"params": params}, *b,
                                     method="hidden")
    want = ref.forward(cfg.model, params, *b,
                       lfm2.seq_positions(cfg.model, FRAMES, U))
    at = np.clip(np.asarray(layout["a_lens"])[:, None]
                 + np.arange(U + 1)[None, :], 0, h.shape[1] - 1)
    got = np.take_along_axis(np.asarray(h), at[..., None], 1) \
        @ np.asarray(head).T * cfg.model.mup_lm_head
    assert ref.rms_rel(got, want["logits"], want["steps"]) < 2e-5


MULTIPLIERS = [("mup_embedding", None), ("mup_lm_head", None),
               ("mup_attn_in", None), ("mup_key", None),
               ("mup_attn_out", None), ("mup_ssm_in", None),
               ("mup_ssm_out", None)] \
    + [("mup_ssm", i) for i in range(5)] \
    + [("mup_mlp", i) for i in range(2)]


@pytest.mark.parametrize("name, at", MULTIPLIERS,
                         ids=[n if i is None else f"{n}{i}"
                              for n, i in MULTIPLIERS])
def test_each_multiplier_changes_the_output(name, at):
    """All fourteen, each set to a value that is not 1 in a preset
    whose others are 1: the logits move."""
    ones = {"mup_embedding": 1.0, "mup_lm_head": 1.0, "mup_attn_in": 1.0,
            "mup_key": 1.0, "mup_attn_out": 1.0, "mup_ssm_in": 1.0,
            "mup_ssm_out": 1.0, "mup_ssm": (1.0,) * 5,
            "mup_mlp": (1.0, 1.0)}
    value = 0.7 if at is None else tuple(
        0.7 if i == at else 1.0 for i in range(len(ones[name])))
    b = batch()
    base = toy(**ones)
    params = init(base, b)

    def logits(cfg):
        model = create_lfm2_model(cfg.model, U)
        h, head, _, _ = model.apply({"params": params}, *b,
                                    method="hidden")
        return model.apply({"params": params}, h[:, 12], method="logits")

    moved = logits(toy(**{**ones, name: value}))
    assert ref.rms_rel(moved, logits(base)) > 1e-3


def test_a_preset_without_expert_layers_goes_through_the_loop():
    """No expert layer: ``LMGreedy.sparse`` is empty, ``_watched`` gives
    no router outputs (and still the attention's and the branches'),
    ``observe_lm_call`` is handed no routing counters, and
    ``Inferencer.decode_batch`` transcribes."""
    from deepspeech_tpu import obs
    from deepspeech_tpu.data import CharTokenizer
    from deepspeech_tpu.infer import Inferencer
    from deepspeech_tpu.models.lfm2 import seeded_variables

    cfg = toy()
    assert cfg.decode.mode == "lm_greedy"
    params, buffers = seeded_variables(cfg, 3)
    assert buffers == {}
    assert params["layer0"]["mixer"]["in_proj"]["kernel"].shape \
        == (32, 2 * 32 + 2 * 2 * 16 + 4)
    inf = Inferencer(cfg, CharTokenizer.synthetic_zh(63), params, buffers)
    engine = inf.lm_greedy
    assert engine.sparse == [] and engine.hybrid == "layer1"
    assert _watched({}, (slice(0, 2), 12), []) == {}
    feats, lens, _, ll = batch()
    texts = inf.decode_batch({"features": feats, "feat_lens": lens,
                              "max_tokens": ll + 1})
    assert len(texts) == 4 and all(isinstance(t, str) for t in texts)
    stats = engine.last_call["stats"]
    assert "expert_pairs" not in stats["decode"]
    assert "experts_hit" not in stats and stats["dropped_pairs"] == 0
    assert sorted(engine.last_call["decode_watch"]) == [
        "branch_attn", "branch_mixer", "branch_mlp", "gated0", "logits"]
    assert "branch_mlp" not in engine.last_call["prefill_watch"]
    gauges = obs.registry().snapshot()["gauges"]
    assert gauges["lm_cache_bytes_state"] == 2 * 4 * 4 * 16 * 8 * 4
    assert gauges["lm_cache_bytes_conv"] == 2 * 4 * 3 * 96 * 4
    assert gauges["lm_cache_bytes"] == gauges["lm_cache_bytes_state"] \
        + gauges["lm_cache_bytes_conv"] + gauges["lm_cache_bytes_global"]


def test_the_trainer_says_why_it_does_not_train_the_kernel(monkeypatch):
    """Nothing is claimed of training this block: on the CPU ``loss``
    differentiates through the plain scan; where the sequence form is
    the kernel (heads of 128 on a TPU), which has no backward pass,
    ``Trainer`` raises at construction."""
    from deepspeech_tpu.config import apply_overrides
    from deepspeech_tpu.data import CharTokenizer
    from deepspeech_tpu.parallel.mesh import make_mesh
    from deepspeech_tpu.train import Trainer

    b = batch()
    cfg = apply_overrides(toy(), {
        "train.checkpoint_dir": "", "train.epochs": 1})
    grads = jax.grad(lambda p: jnp.sum(create_lfm2_model(
        cfg.model, U).apply({"params": p}, *b, method="loss")[0]))(
            init(cfg, b))
    assert all(np.isfinite(np.asarray(g)).all() and np.any(np.asarray(g))
               for g in jax.tree.leaves(grads["layer0"]["mixer"]))

    class Pipeline:
        provides_global_batches = True

        def peek(self):
            return dict(zip(("features", "feat_lens", "labels",
                             "label_lens"), b))

    wide = apply_overrides(toy(ssm_d_ssm=256, ssm_heads=2, ssm_state=8,
                               ssm_groups=1), {"train.checkpoint_dir": ""})
    monkeypatch.setenv("DS2N_ASSUME_TPU", "1")
    with pytest.raises(NotImplementedError, match="no backward pass"):
        Trainer(wide, Pipeline(), CharTokenizer.synthetic_zh(63),
                mesh=make_mesh((1, 1)))
