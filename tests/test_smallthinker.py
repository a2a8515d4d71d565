"""The SmallThinker block (``models/lfm2.py``: a router that reads the
layer's input before attention, softmax over the chosen logits, gated
ReLU experts, no q/k norm, a period that starts with the global layer)
against the plain reference (``benchmark/reference/smallthinker_ref.py``)
at the configuration file's rehearsal widths on the CPU, float32: loss
and every parameter's gradient through ``Trainer``'s own step, in one
block and past one block and the window; the routing read before
attention against routing computed by hand from the layer's input; the
four chips' shares of a layer's experts adding up to the uncut
reference's layer; ``jax.grad`` past the window through the attention
kernels (interpreted) against the loop; the training counters."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lfm2_ref
from benchmark.reference import smallthinker_ref as ref
from deepspeech_tpu.config import apply_overrides, get_config
from deepspeech_tpu.models import lfm2

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
U = 12           # max_label_len
W = 8            # the rehearsal's window


def toy(**kw):
    """The preset at the configuration file's ``rehearsal`` widths."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "smallthinker_21b_a3b.json")) as f:
        small = json.load(f)["rehearsal"]
    small.update({"lfm_seq_positions": 0, "moe_impl": "xla", **kw})
    return dataclasses.replace(get_config("smallthinker_21b_a3b").model,
                               **small)


def batch(frames=200, seed=0):
    """Three recordings, the longest the whole bucket: 25, 21 and 13
    prefix positions at 200 frames, past the window of 8."""
    rng = np.random.default_rng(seed)
    lens = np.array([frames, frames - 37, frames // 2], np.int32)
    ll = np.array([12, 7, 9], np.int32)
    feats = rng.standard_normal((3, frames, 161)).astype(np.float32)
    feats *= np.arange(frames)[None, :, None] < lens[:, None, None]
    labels = rng.integers(1, 64, (3, U)).astype(np.int32)
    labels *= np.arange(U)[None, :] < ll[:, None]
    return feats, lens, labels, ll


def trainer_for(m, b):
    from deepspeech_tpu.data import CharTokenizer
    from deepspeech_tpu.parallel import make_mesh
    from deepspeech_tpu.train import Trainer

    cfg = dataclasses.replace(get_config("smallthinker_21b_a3b"), model=m)
    cfg = apply_overrides(cfg, {
        "data.batch_size": 3, "data.bucket_frames": (b[0].shape[1],),
        "data.max_label_len": U, "train.checkpoint_dir": "",
        "train.log_every": 1, "train.epochs": 1})
    pool = [dict(zip(("features", "feat_lens", "labels", "label_lens"),
                     b))]

    class Pipeline:
        provides_global_batches = True

        def peek(self):
            return pool[0]

        def epoch(self, epoch_idx):
            return iter(pool)

        def batches_per_epoch(self, epoch_idx):
            return 1

    class Quiet:
        def log(self, event, **fields):
            pass

    return cfg, Trainer(cfg, Pipeline(), CharTokenizer.synthetic_zh(63),
                        logger=Quiet(), mesh=make_mesh((1, 1)))


@pytest.mark.parametrize("frames, positions", [
    (200, 0), (4600, 640)], ids=["one_block", "past_one_block"])
def test_trainers_step_equals_the_reference(frames, positions):
    """``Trainer.train_step`` itself, once from the seed's state: its
    loss, and for EVERY parameter the gradient its optimizer saw (the
    first moment of a first step over 1 - b1: clipped), against the
    reference's loss and clipped gradients routed by the step's own
    chosen sets. 4,600 frames are 575 prefix positions in 640: past one
    block of 512 queries (the blockwise loop on the CPU), every
    sequence past the window."""
    from benchmark.drivers.train_lfm2 import adam_moments
    from deepspeech_tpu.parallel import shard_batch

    m = toy(lfm_seq_positions=positions)
    b = batch(frames)
    cfg, trainer = trainer_for(m, b)
    params = jax.device_get(trainer.state.params)
    s = lfm2.seq_positions(m, frames, U)
    _, mid = trainer.model.apply(
        {"params": params}, *b, method="loss", mutable=["intermediates"])
    pinned = [mid["intermediates"][f"layer{i}"]["moe"]["experts"][0]
              for i in range(4)]
    state, metrics = trainer.train_step(
        trainer.state, shard_batch(trainer.mesh, dict(zip(
            ("features", "feat_lens", "labels", "label_lens"), b))))
    loss, grads, out = ref.loss_and_grads(
        m, params, *(jnp.asarray(x) for x in b), s, pinned=pinned,
        q_block=64)
    norm, grads = ref.clip_by_global_norm(grads, cfg.train.grad_clip_norm)
    assert ref.chosen_differ_share(pinned, out["chosen"],
                                   out["valid"]) == 0.0
    assert abs(float(metrics["loss"]) - float(loss)) < 2e-5 * float(loss)
    assert abs(float(metrics["grad_norm"]) - float(norm)) \
        < 2e-5 * float(norm)
    seen = jax.tree.map(lambda x: x / (1 - lfm2_ref.ADAM_B1),
                        adam_moments(state.opt_state).mu)
    errs = jax.tree.map(ref.rms_rel, seen, grads)
    assert max(jax.tree.leaves(errs)) < 2e-5, errs
    counters = jax.device_get(metrics["routing"])
    np.testing.assert_array_equal(counters["expert_pairs"],
                                  out["pairs_held"])
    lens = np.asarray(out["valid"]).sum(1)
    near = np.minimum(lens, W)
    assert int(counters["reach_pairs_global"]) == int(
        (lens * (lens + 1) // 2).sum())
    assert int(counters["reach_pairs_window"]) == int(
        (near * (near + 1) // 2 + (lens - near) * W).sum())


def layer_and_input(kind="sliding_attention", seed=3, s=29, **kw):
    m = toy(**kw)
    layer = lfm2.DecoderLayer(m, kind, True)
    h = jax.random.normal(jax.random.PRNGKey(seed), (2, s, m.lfm_hidden))
    valid = jnp.arange(s)[None, :] < jnp.asarray([[s], [s - 6]])
    pos = jnp.broadcast_to(jnp.arange(s)[None, :], (2, s))
    params = layer.init(jax.random.PRNGKey(seed + 1), h, valid, pos)[
        "params"]
    return m, layer, params, h, valid, pos


@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
def test_routing_is_read_from_the_layers_input_before_attention(kind):
    """The chosen experts and their weights are those computed by hand
    from the layer's INPUT: top-3 of ``h W_r`` and a softmax over the
    three chosen logits; not from its norm, and not from the stream
    after attention."""
    m, layer, params, h, valid, pos = layer_and_input(kind)
    (out, counters, _), mid = layer.apply(
        {"params": params}, h, valid, pos, mutable=["intermediates"])
    got = mid["intermediates"]["moe"]
    logits = jnp.einsum("bsd,de->bse", h, params["moe"]["router"],
                        precision="highest").reshape(-1, m.lfm_experts)
    top, chosen = jax.lax.top_k(logits, m.lfm_top_k)
    np.testing.assert_array_equal(got["experts"][0], chosen)
    np.testing.assert_allclose(got["scores"][0], logits, atol=1e-6)
    np.testing.assert_allclose(got["weights"][0],
                               jax.nn.softmax(top, axis=-1), atol=1e-6)
    # ... a softmax over all 64, the chosen renormalised
    full = jnp.take_along_axis(jax.nn.softmax(logits, -1), chosen, 1)
    np.testing.assert_allclose(
        got["weights"][0], full / full.sum(-1, keepdims=True), atol=1e-6)
    # The norm's gain is 1 on seeded weights, so the normed input would
    # choose the same experts; it would weigh them otherwise.
    normed = ref.rms_norm(h, params["op_norm"]["scale"], m.lfm_norm_eps)
    other = jax.nn.softmax(jax.lax.top_k(jnp.einsum(
        "bsd,de->bse", normed, params["moe"]["router"],
        precision="highest").reshape(-1, m.lfm_experts),
        m.lfm_top_k)[0], axis=-1)
    assert float(jnp.max(jnp.abs(other - got["weights"][0]))) > 1e-3
    assert set(params["attn"]) == {"q", "k", "v", "o"}      # no q/k norm


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Guide section 4: the four chips' shares (ids 0-3, 4-7, 8-11,
    12-15 of 16 here; 0-15, 16-31, 32-47, 48-63 at the published width)
    of one layer's expert output sum to what the uncut reference gives
    for the whole layer."""
    whole = toy(experts_held=16, expert_offset=0)
    _, _, params, h, valid, pos = layer_and_input(
        experts_held=16, expert_offset=0)
    p = params["moe"]
    x = jax.random.normal(jax.random.PRNGKey(9), h.shape)
    _, own, chosen, w = ref.routing(whole, p["router"], h, ())
    want, pairs = ref.experts(whole, p, x, own, chosen, w,
                              valid.astype(jnp.float32), ())
    total, held = jnp.zeros_like(x), []
    for c in range(4):
        share = dataclasses.replace(whole, experts_held=4,
                                    expert_offset=4 * c)
        block = lfm2.SparseExperts(share)
        variables = {"params": dict(p, w13=p["w13"][4 * c:4 * c + 4],
                                    w2=p["w2"][4 * c:4 * c + 4])}
        routing = block.apply(variables, h, method="route")
        out, counters = block.apply(variables, x, valid, routing)
        assert int(jnp.sum(counters["expert_pairs"])
                   + counters["pairs_elsewhere"]) == 3 * int(valid.sum())
        held.append(counters["expert_pairs"])
        total = total + out
    assert ref.rms_rel(total, want) < 2e-5
    np.testing.assert_array_equal(np.concatenate(held), pairs)


@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
def test_grad_past_the_window_agrees_between_kernels_and_loop(
        kind, monkeypatch):
    """One attention layer of the block's kind (no q/k norm, rotation
    on the sliding kind only) at heads of 128, 600 positions past one
    block of 512 and a window of 100: output and every gradient through
    ``gqa_attn_fwd`` / ``gqa_attn_bwd_dq`` / ``gqa_attn_bwd_dkv``
    (interpreted) are the blockwise loop's."""
    from jax.experimental.pallas import tpu as pltpu

    m = toy(lfm_heads=7, lfm_kv_heads=1, lfm_head_dim=128, lfm_window=100)
    layer = lfm2.Attention(m, kind)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 600, m.lfm_hidden))
    params = layer.init(jax.random.PRNGKey(4), x)["params"]
    assert set(params) == {"q", "k", "v", "o"}

    def loss(p, x):
        return jnp.sum(jnp.tanh(layer.apply({"params": p}, x)[0]))

    want = jax.grad(loss, (0, 1))(params, x)
    monkeypatch.setenv("DS2N_ASSUME_TPU", "1")
    text = str(jax.make_jaxpr(jax.grad(loss))(params, x))
    for name in ("gqa_attn_fwd", "gqa_attn_bwd_dq", "gqa_attn_bwd_dkv"):
        assert name in text
    with pltpu.force_tpu_interpret_mode():
        got = jax.grad(loss, (0, 1))(params, x)
    jax.tree.map(lambda g, w: np.testing.assert_allclose(
        g, w, rtol=2e-5, atol=3e-5), got, want)


def test_the_preset_is_the_published_block():
    m = get_config("smallthinker_21b_a3b").model
    assert m.lfm_layer_types == ("full_attention",) \
        + ("sliding_attention",) * 3
    assert m.lfm_rope_kinds == ("sliding_attention",)
    assert (m.lfm_heads, m.lfm_kv_heads, m.lfm_head_dim) == (28, 4, 128)
    assert (m.lfm_experts, m.lfm_top_k, m.experts_held) == (64, 6, 16)
    assert (m.moe_score_func, m.moe_expert_act) == ("softmax", "relu")
    assert m.moe_route_pre_attn and not m.lfm_qk_norm
    assert not m.moe_select_bias and not m.moe_shared_experts
    assert lfm2.remat_policy(m) is lfm2.LONG_REMAT_POLICY
    assert lfm2.remat_policy(get_config("lfm2_24b_a2b").model) \
        is lfm2.REMAT_POLICY
    with pytest.raises(NotImplementedError):
        lfm2.create_lfm2_model(dataclasses.replace(m, hc_streams=4), U)


def test_route_refuses_an_unknown_scoring_function():
    from deepspeech_tpu.ops import moe

    with pytest.raises(ValueError):
        moe.route(jnp.zeros((2, 4)), jnp.zeros((4, 8)), None, 2,
                  score_func="tanh")
