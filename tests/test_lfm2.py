"""The LFM2 decoder-only recogniser (``models/lfm2.py``, ``ops/moe.py``,
``ops/moe_pallas.py``) against the plain reference
(``benchmark/reference/lfm2_ref.py``) at a toy width on the CPU:
forward, loss and every gradient for two shares of 16 experts; the
shares add up to the uncut layer; right-padding and batch order change
nothing valid; dropless under skew; the grouped-product kernels in
interpret mode against ``jax.lax.ragged_dot``; training through
``Trainer``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lfm2_ref
from deepspeech_tpu.config import apply_overrides, get_config
from deepspeech_tpu.models.lfm2 import create_lfm2_model, seq_positions
from deepspeech_tpu.ops import moe, moe_pallas

U = 6            # max_label_len
V = 50


def toy(**kw):
    base = dict(lfm_hidden=64, lfm_heads=4, lfm_kv_heads=2, lfm_ffn_dim=96,
                lfm_expert_dim=128, lfm_experts=16, experts_held=8,
                expert_offset=0, vocab_size=V, dtype="float32",
                lfm_seq_positions=0, moe_rows_bound=0.0, moe_impl="xla")
    base.update(kw)
    return dataclasses.replace(get_config("lfm2_24b_a2b").model, **base)


def batch(seed=0, rows=4, frames=40, lens=(40, 33, 17, 25),
          label_lens=(6, 3, 0, 5)):
    rng = np.random.default_rng(seed)
    lens = np.asarray(lens, np.int32)
    ll = np.asarray(label_lens, np.int32)
    feats = rng.standard_normal((rows, frames, 161)).astype(np.float32)
    feats *= np.arange(frames)[None, :, None] < lens[:, None, None]
    labels = rng.integers(1, V, (rows, U)).astype(np.int32)
    labels *= np.arange(U)[None, :] < ll[:, None]
    return feats, lens, labels, ll


def init(m, b, seed=1):
    v = create_lfm2_model(m, U).init(jax.random.PRNGKey(seed), *b,
                                     method="loss")
    return v["params"], v["buffers"]


def system(m, params, buffers, b):
    model = create_lfm2_model(m, U)

    def mean_nll(p):
        nll, stats = model.apply({"params": p, "buffers": buffers}, *b,
                                 method="loss")
        return jnp.mean(nll), (nll, stats)

    (_, (nll, stats)), grads = jax.jit(
        jax.value_and_grad(mean_nll, has_aux=True))(params)
    h, _, layout, _ = model.apply({"params": params, "buffers": buffers},
                                  *b, method="hidden")
    return nll, grads, h, layout, stats


def close(got, want, tol=2e-5):
    return lfm2_ref.rms_rel(got, want) <= tol


@pytest.mark.parametrize("offset, impl", [(0, "xla"), (8, "xla"),
                                          (8, "pallas")])
def test_system_equals_reference(offset, impl):
    """Forward, loss and all gradients for each share of 16 experts,
    and through the interpreted kernels."""
    m = toy(expert_offset=offset, moe_impl=impl)
    b = batch()
    params, buffers = init(m, b)
    nll, grads, h, layout, _ = system(m, params, buffers, b)
    s = seq_positions(m, b[0].shape[1], U)
    want = lfm2_ref.forward(m, params, buffers, *b, s)
    np.testing.assert_array_equal(layout["valid"], want["valid"])
    assert lfm2_ref.rms_rel(h, want["hidden"], want["valid"]) < 2e-5
    assert close(nll, want["nll"])
    _, want_grads = lfm2_ref.loss_and_grads(m, params, buffers, *b, s)
    errs = jax.tree.map(lfm2_ref.rms_rel, grads, want_grads)
    assert max(jax.tree.leaves(errs)) < 2e-5, errs


def _layer_inputs(seed=3, n=96, d=64, e=16, f=128):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((1, n, d)).astype(np.float32)
    valid = np.ones((1, n), bool)
    valid[0, -7:] = False
    p = {"router": rng.standard_normal((d, e)).astype(np.float32) * 0.3,
         "w13": rng.standard_normal((e, d, 2 * f)).astype(np.float32) * 0.1,
         "w2": rng.standard_normal((e, f, d)).astype(np.float32) * 0.1}
    bias = rng.standard_normal(e).astype(np.float32) * 0.05
    return x, valid, p, bias


@pytest.mark.parametrize("shares", [2, 4])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """The partial results of all shares of one expert layer equal what
    the uncut reference layer gives (nothing here is computed alike by
    every share: the layer has no shared expert, and the router only
    chooses)."""
    x, valid, p, bias = _layer_inputs()
    uncut = toy(experts_held=16, expert_offset=0)
    want, _, _, _ = lfm2_ref.experts(uncut, p, bias, jnp.asarray(x),
                                  jnp.asarray(valid), ())
    held = 16 // shares
    routing = moe.route(x[0], p["router"], bias, uncut.lfm_top_k)
    total = 0.0
    pairs = 0
    for i in range(shares):
        lo = i * held
        part, counters = moe.expert_layer(
            jnp.asarray(x[0]), jnp.asarray(valid[0]), routing,
            p["w13"][lo:lo + held], p["w2"][lo:lo + held], offset=lo,
            impl="xla")
        ref_part, _, _, _ = lfm2_ref.experts(
            toy(experts_held=held, expert_offset=lo),
            {**p, "w13": p["w13"][lo:lo + held],
             "w2": p["w2"][lo:lo + held]},
            bias, jnp.asarray(x), jnp.asarray(valid), ())
        assert close(part, ref_part[0])
        total = total + part
        pairs += int(jnp.sum(counters["expert_pairs"]))
        assert int(counters["pairs_elsewhere"]) == \
            int(valid.sum()) * uncut.lfm_top_k - \
            int(jnp.sum(counters["expert_pairs"]))
    assert close(total, want[0])
    assert pairs == int(valid.sum()) * uncut.lfm_top_k
    assert not np.any(np.asarray(total)[~valid[0]])


def test_right_padding_and_batch_order_change_nothing_valid():
    m = toy()
    b = batch()
    params, buffers = init(m, b)
    nll, grads, h, layout, _ = system(m, params, buffers, b)
    # A longer bucket (zero frames on the right), more positions, rows
    # in another order.
    perm = np.array([2, 0, 3, 1])
    feats = np.pad(b[0], [(0, 0), (0, 24), (0, 0)])[perm]
    wide = dataclasses.replace(m, lfm_seq_positions=32)
    b2 = (feats, b[1][perm], b[2][perm], b[3][perm])
    nll2, grads2, h2, layout2, _ = system(wide, params, buffers, b2)
    assert h2.shape[1] == 32 and h.shape[1] < 32
    assert close(nll2, np.asarray(nll)[perm])
    s = h.shape[1]
    valid = np.asarray(layout["valid"])[perm]
    np.testing.assert_array_equal(np.asarray(layout2["valid"])[:, :s], valid)
    assert not np.asarray(layout2["valid"])[:, s:].any()
    assert lfm2_ref.rms_rel(np.asarray(h2)[:, :s], np.asarray(h)[perm],
                            valid) < 2e-5
    errs = jax.tree.map(lfm2_ref.rms_rel, grads2, grads)
    assert max(jax.tree.leaves(errs)) < 2e-5, errs


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_dropless_under_skew(impl):
    """A bias that sends nearly every pair to one held expert: no pair
    is lost, the counters agree, the result is the reference's."""
    x, valid, p, bias = _layer_inputs(n=160)
    bias = bias.copy()
    bias[5] += 10.0
    m = toy(experts_held=8, expert_offset=0)
    routing = moe.route(x[0], p["router"], bias, m.lfm_top_k)
    out, c = moe.expert_layer(
        jnp.asarray(x[0]), jnp.asarray(valid[0]), routing, p["w13"][:8],
        p["w2"][:8], offset=0, impl=impl)
    n_valid = int(valid.sum())
    pairs = np.asarray(c["expert_pairs"])
    assert pairs[5] == n_valid                 # every position chose it
    assert int(c["dropped"]) == 0
    assert int(c["rows_high_water"]) == pairs.sum() \
        <= int(c["rows_capacity"])
    assert pairs.sum() + int(c["pairs_elsewhere"]) == n_valid * m.lfm_top_k
    chosen = np.asarray(routing.experts)[valid[0]]
    np.testing.assert_array_equal(
        pairs, [(chosen == e).sum() for e in range(8)])
    want, _, _, _ = lfm2_ref.experts(
        m, {**p, "w13": p["w13"][:8], "w2": p["w2"][:8]}, bias,
        jnp.asarray(x), jnp.asarray(valid), ())
    assert close(out, want[0])


def test_a_stated_bound_counts_what_does_not_fit():
    """Under ``moe_rows_bound`` the rows are cut to the capacity and the
    layer says how many pairs that cost; at the worst case, none."""
    x, valid, p, bias = _layer_inputs(n=1024)
    bias = bias.copy()
    bias[:4] += 10.0                           # all four chosen are held
    routing = moe.route(x[0], p["router"], bias, 4)
    args = (jnp.asarray(x[0]), jnp.asarray(valid[0]), routing,
            p["w13"][:8], p["w2"][:8])
    _, c = moe.expert_layer(*args, offset=0, rows_bound=0.25, impl="xla")
    assert int(c["rows_capacity"]) == 1024
    assert int(c["rows_high_water"]) == 4 * int(valid.sum())
    assert int(c["dropped"]) == 4 * int(valid.sum()) - 1024
    _, c = moe.expert_layer(*args, offset=0, rows_bound=0.0, impl="xla")
    assert int(c["dropped"]) == 0 and int(c["rows_capacity"]) == 4096


GROUPS = {
    "remainder_tile": [300, 0, 212, 100, 0, 7],   # 619 of 1024 rows
    "empty_first_and_last": [0, 512, 200, 0],
    "one_group_past_a_tile": [0, 0, 700],
    "full": [512, 256, 256],
    "nothing_routed": [0, 0, 0],
}


@pytest.mark.parametrize("case", list(GROUPS))
def test_grouped_kernels_equal_ragged_dot(case):
    """``moe_gmm`` forward and both gradients (``moe_gmm`` against the
    transposed matrices, ``moe_tgmm``) in interpret mode."""
    sizes = jnp.asarray(GROUPS[case], jnp.int32)
    g, m, k, n = len(GROUPS[case]), 1024, 64, 256
    rng = np.random.default_rng(7)
    lhs = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((g, k, n)), jnp.float32)
    cot = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)

    def pallas(a, b):
        return moe_pallas.gmm(a, b, sizes, jnp.float32, True)

    def oracle(a, b):
        return jax.lax.ragged_dot(a, b, sizes)

    got, got_vjp = jax.vjp(pallas, lhs, rhs)
    want, want_vjp = jax.vjp(oracle, lhs, rhs)
    routed = int(sizes.sum())
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    assert not np.asarray(got)[routed:].any()
    for a, b in zip(got_vjp(cot), want_vjp(cot)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(
        moe_pallas.tgmm(lhs, cot, sizes, jnp.float32, True),
        want_vjp(cot)[1], rtol=1e-4, atol=1e-3)


def toy_trainer(events=None, **overrides):
    """A ``Trainer`` of the lm objective at the toy width on 8
    synthetic utterances (2 steps an epoch), its log lines kept in
    ``events``."""
    from deepspeech_tpu.data import CharTokenizer
    from deepspeech_tpu.parallel import make_mesh
    from deepspeech_tpu.train import Trainer, _SyntheticPipeline

    extra = overrides.pop("trainer_args", {})
    rows = overrides.pop("rows", 4)
    cfg = get_config("lfm2_24b_a2b")
    cfg = dataclasses.replace(cfg, model=toy(**{
        "moe_rows_bound": 0.5, **overrides.pop("model", {})}))
    cfg = apply_overrides(cfg, {
        "data.batch_size": rows, "data.bucket_frames": (64,),
        "data.max_label_len": 8, "train.checkpoint_dir": "",
        "train.log_every": 1, "train.epochs": 6,
        "train.learning_rate": 3e-3, "train.warmup_steps": 1,
        **overrides})

    class Log:
        def log(self, event, **fields):
            if events is not None:
                events.append((event, fields))

    pipeline = _SyntheticPipeline(cfg, 2 * rows, frames=64, label_len=6)
    return cfg, Trainer(cfg, pipeline, CharTokenizer.synthetic_zh(V - 1),
                        logger=Log(), mesh=make_mesh((1, 1)), **extra)


def test_trainer_trains_the_lm_objective():
    """``Trainer.fit`` on the lm objective: AdamW lowers the loss, the
    selection bias stays out of the optimizer, the step's routing
    counters reach the log line and the registry."""
    from deepspeech_tpu import obs

    events = []
    obs.registry().reset()
    cfg, trainer = toy_trainer(events)
    bias0 = jax.device_get(trainer.state.batch_stats)
    assert "expert_bias" in str(jax.tree.structure(bias0))
    assert "expert_bias" not in str(jax.tree.structure(
        trainer.state.opt_state))
    trainer.fit()
    steps = [f for e, f in events if e == "train_step"]
    assert len(steps) == 12
    assert steps[-1]["loss"] < 0.7 * steps[0]["loss"]
    assert steps[0]["dropped_pairs"] == 0
    assert np.shape(steps[0]["expert_pairs"]) == (4, 8)
    assert steps[0]["valid_positions"] + steps[0]["padded_positions"] \
        == 4 * seq_positions(cfg.model, 64, 8)
    jax.tree.map(np.testing.assert_array_equal, bias0,
                 jax.device_get(trainer.state.batch_stats))
    snap = obs.registry().snapshot()
    assert snap["counters"]["moe_dropped_pairs"] == 0
    assert snap["counters"]["lm_valid_positions"] == sum(
        s["valid_positions"] for s in steps)
    assert sum(v for k, v in snap["counters"].items()
               if k.startswith("moe_expert_pairs")) == sum(
        np.sum(s["expert_pairs"]) for s in steps)
    with pytest.raises(NotImplementedError):
        trainer.evaluate()


def test_tensorboard_gets_the_scalar_routing_fields(tmp_path):
    """The per-expert lists of a logged step stay in the log line; the
    TensorBoard writer is handed scalars only."""
    _, trainer = toy_trainer(**{
        "train.tensorboard_dir": str(tmp_path / "tb"), "train.epochs": 1})
    wrote = {}
    scalars = trainer.tb.scalars
    trainer.tb.scalars = lambda step, **v: (wrote.update(v),
                                            scalars(step, **v))
    trainer.fit()
    assert {"loss", "valid_positions", "rows_high_water",
            "dropped_pairs"} <= set(wrote)
    assert "expert_pairs" not in wrote and "pairs_elsewhere" not in wrote
    files = list((tmp_path / "tb").glob("events.out.tfevents.*"))
    assert files and files[0].stat().st_size > 0


@pytest.mark.parametrize("log_every", (1, 5))
def test_a_dropped_pair_ends_the_run_at_the_next_sync(log_every):
    """Under a row bound the traffic exceeds (one tile of 512 rows for
    some thousand pairs, every expert held), every step drops pairs;
    the run ends at its first sync, whether or not the dropping steps
    were logged ones (with ``log_every`` 5 and 2 steps an epoch that
    sync is the epoch's end)."""
    events = []
    _, trainer = toy_trainer(
        events, rows=32, model={"moe_rows_bound": 0.01,
                                "experts_held": 16},
        **{"train.log_every": log_every, "train.epochs": 2})
    with pytest.raises(RuntimeError, match="did not fit"):
        trainer.fit()
    logged = [f for e, f in events if e == "train_step"]
    assert not logged and not any(e == "epoch_end" and log_every == 1
                                  for e, _ in events)


def test_an_eval_pipeline_is_refused_at_construction():
    with pytest.raises(ValueError, match="no eval_pipeline"):
        toy_trainer(trainer_args={"eval_pipeline": object()})


def test_the_objective_check_names_all_three():
    from deepspeech_tpu.data import CharTokenizer
    from deepspeech_tpu.parallel import make_mesh
    from deepspeech_tpu.train import Trainer, _SyntheticPipeline

    cfg = apply_overrides(get_config("ds2_small"), {
        "train.objective": "ml", "data.batch_size": 2,
        "model.rnn_hidden": 8, "model.rnn_layers": 1})
    with pytest.raises(ValueError, match="'ctc', 'rnnt' or 'lm'"):
        Trainer(cfg, _SyntheticPipeline(cfg, 2), CharTokenizer.english(),
                mesh=make_mesh((1, 1)))
