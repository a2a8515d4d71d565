"""Driver ``train_lfm2``: decoder-only speech recognition training
through the loop users run, fed by the benchmark's own pipeline.

The same loop as ``drivers/train.py`` (its ``Pipeline``, ``Window`` and
``SpanSink``; ``Trainer(cfg, pipeline, tokenizer, logger=...,
preempt=...).fit()``; batches from ``gen/batches.py``), for a preset
whose ``train.objective`` is ``lm``: the Trainer then builds
``models/lfm2.LFM2ASR``, differentiates its loss and updates with the
repo's AdamW. Nothing of the program is patched and no ``*_impl`` is
set. The record says ``"driver": "train_lfm2"``: the readers of the
other drivers skip it, and the ``lfm2_*`` readers take it.

Outside the window, every run compares the system with the plain
reference (``reference/lfm2_ref.py``) at the configuration's widths on
a seeded ragged sample the reference can hold, which the system sees
tiled to the cell's batch: through a forward-and-backward program of
the step's shapes and ONCE through the compiled step the window then
times (``ReferenceCheck``).
"""

from __future__ import annotations

import math
import time

import numpy as np

from benchmark import harness
from benchmark.drivers.train import Pipeline, SpanSink, Window
from benchmark.drivers.train_rnnt import kernel_calls
from benchmark.gen import batches as gen_batches
from benchmark.reference import lfm2_ref

# The sample the reference holds at the published widths: 8 utterances
# in the cell's own bucket and sequence length (1696 frames, 288
# positions), valid audio from half the bucket up, 32-64 labels: about
# 1,850 valid positions. The SYSTEM sees it tiled to the cell's batch
# (16 times, 128 rows: the same mean loss and gradient), so what is
# compared is the compiled step the window times and a program of the
# same shapes: 32,256 rows of dispatch capacity, 13-16 k pairs on the 8
# held experts a layer, 27-33 row tiles of 512 with a remainder.
REF_ROWS = 8

# System (bfloat16 matmul operands and activations, float32
# accumulation, float32 router) against the plain float32 reference on
# the chip at the published widths, each as root-mean-square difference
# over the reference's root mean square. A limit is twice the largest
# reading over the seeds read, where nothing else is said (weights and
# sample from the seed: ``tools/lfm2_ref_seeds.py`` over twelve seeds
# and fourteen runs of the cell; PERF.md section 6, PR 30, lists them);
# beside each, the range read and what the reference with float8
# (e4m3) weights, the nearest precision below the configuration's,
# reads against the reference on the chip, three seeds, as a multiple
# of the limit. The controls of
# ``benchmark/tests/test_lfm2_ref_control.py`` put one fault each into
# the reference and must fail these limits.
REF_TOL = {
    # -- ``LFM2ASR.loss`` forward and backward at the step's shapes,
    #    all 16 tiles; the reference's gradients routed by that pass's
    #    chosen sets --
    # bf16 rounds every operand by up to 2^-9 = 0.2%; through five
    # residual layers, after the last RMSNorm, over the valid
    # positions: 2.07-2.45%; float8 3.6 x
    "hidden": 0.049,
    # the targets' log-probabilities (mostly the constant -log V, so a
    # small share of their rms): 0.22-0.32%; float8 3.4 x
    "logp": 0.0064,
    # per-utterance loss, 33-65 such terms summed: 0.013-0.059%;
    # float8 2.2 x
    "nll": 0.0012,
    # the last expert layer's 64 float32 router scores on inputs that
    # four bf16 layers rounded: 0.74-0.86%; float8 3.7 x
    "scores": 0.0175,
    # clipped gradients: the busiest held expert's W2 in the last
    # layer (``moe_tgmm``) 2.70-2.83%, float8 5.7 x; the first expert
    # layer's W_g (back through the transposed ``moe_gmm``) 2.74-3.51%,
    # float8 5.0 x; layer 0's W_in 2.49-2.54%, float8 5.9 x, and the
    # prefix projection 2.70-2.87%, float8 5.4 x (back through all five
    # layers); the worst of all parameters (a router, or the q/k norm
    # gains) 3.34-3.97%, float8 5.2 x
    "grad_w2": 0.057,
    "grad_router": 0.07,
    "grad_w_in": 0.051,
    "grad_prefix": 0.057,
    "grad_worst": 0.08,
    # -- the timed step itself, run once on the tiled sample --
    # Its loss, one number: 0.001-0.030%. It cannot be further off
    # than the per-utterance losses it is the mean of, so it has their
    # limit (twice its own worst reading would be three standard
    # deviations of one number: a sound run in 300 over it).
    "step_loss": 0.0012,
    # its gradients' global norm before the clip, one number:
    # 0.001-0.035%; three times the worst
    "step_grad_norm": 0.001,
    # The gradient its optimizer saw (the first moment of a first step
    # over 1 - b1: clipped) against the reference's: the worst
    # parameter outside the expert blocks (attention's k, q or their
    # norm gains) 3.14-3.84%, float8 5.0 x
    "step_grad_dense": 0.077,
    # ... and the expert blocks' parameters pooled: 2.78-2.83%, float8
    # 3.3 x. The step routes by its own pass's chosen sets, which it
    # does not give out; in every reading they were (1)'s, the same
    # computation compiled twice. Only text positions reach the expert
    # blocks from the loss, some 1,000 pairs over the four layers'
    # held experts, so ONE near-tie that another compilation flips
    # adds 4.5%; the limit leaves room for four (9.4%): between the
    # sound reading and float8's, with room on both sides.
    "step_grad_experts": 0.1,
    # each parameter's new value against its old one plus AdamW's
    # first update of that gradient at the schedule's first learning
    # rate (``lfm2_ref.adamw_first_update``), over that update, float32
    # on both sides, differences within float32's step at the
    # parameter not counted; the worst parameter. The reference has no
    # optimizer, so no float8 reading: the limit is the size of the
    # faults it is there for (a learning rate, a bias correction or a
    # clip off by one per cent). Read: 0.0001-0.0003%.
    "update_worst": 0.01,
    # the step's own counters of pairs on the held experts, per expert
    # layer, against the reference's count over the valid positions
    # (near-ties flip across the share's edge): 0.18-0.99%; float8
    # 0.5-1.0 x; the bias left out of the selection 1.4-2.6 x
    "pairs_held": 0.02,
}
# Share of valid (position, expert layer) whose chosen set differs from
# the reference's: bf16 upstream flips near-ties between the fourth and
# fifth score. 5.1-6.8%; float8 3.6 x; the bias left out 2.0 x.
REF_CHOSEN_DIFFER = 0.136


class RoutingWindow(Window):
    """``Window`` that also keeps each step's routing counters (the
    program logs them with the step's loss)."""

    KEYS = ("expert_pairs", "pairs_elsewhere", "valid_positions",
            "padded_positions", "rows_high_water", "rows_capacity",
            "dropped_pairs")

    def __init__(self, ctx, warmup_steps):
        super().__init__(ctx, warmup_steps)
        self.routing = []

    def log(self, event: str, **fields) -> None:
        if event == "train_step":
            self.routing.append({k: fields.get(k) for k in self.KEYS})
        super().log(event, **fields)


def _sample(cfg, ctx: harness.Context) -> tuple:
    """A seeded ragged batch in the cell's bucket."""
    rng = np.random.default_rng([ctx.seed, 2])
    rows = int(ctx.param("ref_rows", REF_ROWS))
    frames = int(ctx.param("bucket_frames"))
    u = cfg.data.max_label_len
    f = cfg.features.num_features
    lens = rng.integers(frames // 2, frames + 1, size=rows).astype(np.int32)
    lens[0] = frames
    feats = rng.standard_normal((rows, frames, f), dtype=np.float32)
    feats *= np.arange(frames)[None, :, None] < lens[:, None, None]
    label_lens = rng.integers(u // 2, u + 1, size=rows).astype(np.int32)
    label_lens[0] = u
    labels = rng.integers(1, cfg.model.vocab_size, size=(rows, u)
                          ).astype(np.int32)
    labels *= np.arange(u)[None, :] < label_lens[:, None]
    return feats, lens, labels, label_lens


def compared(mcfg) -> dict:
    """Which parameter's gradient each named ``grad_*`` reading is."""
    sparse = sparse_layers(mcfg)
    return {"grad_w2": (sparse[-1], "moe", "w2"),
            "grad_router": (sparse[0], "moe", "router"),
            "grad_w_in": ("layer0", "conv", "in_proj", "kernel"),
            "grad_prefix": ("prefix", "kernel")}


def sparse_layers(mcfg) -> list:
    return [f"layer{i}" for i in range(len(mcfg.lfm_layer_types))
            if i >= mcfg.lfm_dense_layers]


def _at(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def plain_outputs(mcfg, params, buffers, batch, s: int, clip: float,
                  pinned=None, faults=()) -> dict:
    """What the comparison reads, by the reference: the forward pass
    under its own routing; the loss and the clipped gradients with the
    chosen sets ``pinned`` (its own where None)."""
    out = lfm2_ref.forward(mcfg, params, buffers, *batch, s, faults)
    loss, grads = lfm2_ref.loss_and_grads(mcfg, params, buffers, *batch,
                                          s, faults, pinned)
    norm, grads = lfm2_ref.clip_by_global_norm(grads, clip)
    return {"hidden": out["hidden"], "valid": out["valid"],
            "logp": out["logp"], "logp_mask": out["logp_mask"],
            "nll": out["nll"], "scores": out["scores"][-1],
            "chosen": out["chosen"], "pairs_held": out["pairs_held"],
            "loss": loss, "grad_norm": norm, "grads": grads}


def plain_program(mcfg, s: int, clip: float, faults=(), pin=True):
    """``plain_outputs`` compiled: params, buffers and the sample are
    arguments (closed over, a seed's values would be constants and
    every seed would compile), and so are the pinned sets."""
    import jax

    if pin:
        return jax.jit(lambda p, b, x, chosen: plain_outputs(
            mcfg, p, b, x, s, clip, chosen, faults))
    return jax.jit(lambda p, b, x: plain_outputs(
        mcfg, p, b, x, s, clip, None, faults))


def system_outputs(model, mcfg, params, buffers, batch, clip: float
                   ) -> dict:
    """Forward pass, loss and clipped gradients by the cell's own code
    (``LFM2ASR.loss``: compiled grouped products forward and backward,
    dispatch, combine) at whatever shape ``batch`` has, with the chosen
    sets of that very forward pass."""
    import jax
    import jax.numpy as jnp

    from deepspeech_tpu.models import lfm2

    labels, label_lens = batch[2:]

    def mean_nll(q):
        (nll, _), state = model.apply(
            {"params": q, "buffers": buffers}, *batch, method="loss",
            mutable=["intermediates"])
        return jnp.mean(nll), (nll, state["intermediates"])

    (_, (nll, mid)), grads = jax.value_and_grad(
        mean_nll, has_aux=True)(params)
    _, grads = lfm2_ref.clip_by_global_norm(grads, clip)
    h, embed, layout, _ = model.apply(
        {"params": params, "buffers": buffers}, *batch, method="hidden")
    logp, _ = lfm2.target_logp(h, embed, layout, labels, label_lens)
    sparse = sparse_layers(mcfg)
    return {"hidden": h, "logp": logp, "nll": nll, "grads": grads,
            "scores": mid[sparse[-1]]["moe"]["scores"][0],
            "chosen": [mid[name]["moe"]["experts"][0] for name in sparse]}


def leaf_sums(got, want):
    """For every parameter the summed squared difference of two trees
    and the summed square of the second; an expert layer's stacked
    matrices ``[G, ., .]`` give one pair of sums a held expert."""
    import jax
    import jax.numpy as jnp

    def one(g, w):
        axes = tuple(range(1, w.ndim)) if w.ndim == 3 else None
        return {"err": jnp.sum(jnp.square(g - w), axis=axes),
                "ref": jnp.sum(jnp.square(w), axis=axes)}

    return jax.tree.map(one, got, want)


def step_sums(old, new, mu, ref_grads, lr):
    """What ONE step from zero moments did to its state, against the
    reference. ``grads``: the clipped gradient its optimizer saw (the
    first moment over 1 - b1) against the reference's. ``update``:
    each new parameter against the old one plus the reference's AdamW
    update of that gradient at ``lr``, over that update."""
    import jax
    import jax.numpy as jnp

    seen = jax.tree.map(lambda m: m / (1 - lfm2_ref.ADAM_B1), mu)

    def update(o, n, g):
        d = lfm2_ref.adamw_first_update(g, lr)[0]
        e = n - (o + d)
        # float32's step at the parameter is not a difference: near 1
        # (a norm gain, a filter tap) it is 6% of an update of 1e-6.
        e = jnp.where(jnp.abs(e) <= jnp.abs(o) * 2.0 ** -23, 0.0, e)
        return {"err": jnp.sum(jnp.square(e)),
                "ref": jnp.sum(jnp.square(d))}

    return {"grads": leaf_sums(seen, ref_grads),
            "update": jax.tree.map(update, old, new, seen)}


def _rel(err, ref) -> float:
    return float(np.sqrt(np.sum(err) / max(np.sum(ref), 1e-60)))


def _leaves(sums) -> list:
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(
        sums, is_leaf=lambda x: isinstance(x, dict) and "err" in x)
    return [(jax.tree_util.keystr(path), x) for path, x in flat]


def worst_leaf(sums, experts=None) -> tuple:
    """The largest reading over the parameters, and whose it is;
    ``experts``: over the expert blocks' parameters only (True) or
    over all others (False)."""
    return max((_rel(x["err"], x["ref"]), name)
               for name, x in _leaves(sums)
               if experts is None or experts == ("['moe']" in name))


def pooled_experts(sums) -> float:
    """One reading over all the expert blocks' parameters together."""
    pool = [x for name, x in _leaves(sums) if "['moe']" in name]
    return _rel(sum(np.sum(x["err"]) for x in pool),
                sum(np.sum(x["ref"]) for x in pool))


def tiled_rms_rel(got, want, mask=None) -> float:
    """``lfm2_ref.rms_rel`` of every tile of ``got`` (the sample
    repeated along the rows) against the one ``want``."""
    want = np.asarray(want)
    tiles = np.asarray(got).reshape((-1,) + want.shape)
    errs = [lfm2_ref.rms_rel(tile, want, mask) for tile in tiles]
    return float(np.sqrt(np.mean(np.square(errs))))


def busiest_held_expert(mcfg, want: dict) -> int:
    """Of the last expert layer's held experts, the one the reference
    routes most valid positions to (an expert with a handful of pairs
    has a gradient of a handful of terms)."""
    chosen = np.asarray(want["chosen"][-1])[np.asarray(want["valid"])]
    return int(np.argmax([(chosen == mcfg.expert_offset + e).sum()
                          for e in range(mcfg.experts_held)]))


def errors(mcfg, got: dict, want: dict, sums: dict) -> dict:
    """Each compared quantity's root-mean-square difference over the
    reference's root mean square: hidden state and router scores over
    the valid positions, log-probabilities over each utterance's own
    targets, ``grad_w2`` over the busiest held expert's matrix; and
    the share of chosen sets that differ. ``sums``: ``leaf_sums`` of
    the system's gradients against the reference's under ``grads``
    and ``step_sums`` of the timed step under ``step`` (a control,
    which has no optimizer, hands the same sums as both and no
    ``update``)."""
    valid = np.asarray(want["valid"])
    masks = {"hidden": valid, "scores": valid, "logp": want["logp_mask"]}
    errs = {k: tiled_rms_rel(got[k], want[k], masks.get(k))
            for k in ("hidden", "logp", "nll", "scores", "pairs_held")}
    expert = busiest_held_expert(mcfg, want)
    for name, path in compared(mcfg).items():
        leaf = _at(sums["grads"], path)
        pick = expert if name == "grad_w2" else ...
        errs[name] = _rel(np.asarray(leaf["err"])[pick],
                          np.asarray(leaf["ref"])[pick])
    errs["grad_worst"] = worst_leaf(sums["grads"])[0]
    step = sums["step"]
    errs["step_loss"] = tiled_rms_rel(got["loss"], want["loss"])
    errs["step_grad_norm"] = tiled_rms_rel(got["grad_norm"],
                                           want["grad_norm"])
    errs["step_grad_dense"] = worst_leaf(step["grads"], experts=False)[0]
    errs["step_grad_experts"] = pooled_experts(step["grads"])
    errs["update_worst"] = (worst_leaf(step["update"])[0]
                            if "update" in step else 0.0)
    k = np.shape(want["chosen"][0])[-1]
    layers = [np.asarray(g).reshape(-1, valid.size, k)
              for g in got["chosen"]]          # each [tiles, B*S, k]
    errs["chosen_differ"] = float(np.mean([
        lfm2_ref.chosen_differ_share([layer[t] for layer in layers],
                                     want["chosen"], valid)
        for t in range(layers[0].shape[0])]))
    return errs


def within(errs: dict, tol: dict, chosen_differ: float) -> bool:
    return bool(all(errs[k] <= tol[k] for k in tol)
                and errs["chosen_differ"] <= chosen_differ)


def adam_moments(opt_state):
    """The ``ScaleByAdamState`` inside the Trainer's optimizer state."""
    import jax
    import optax

    found = [x for x in jax.tree.leaves(
        opt_state, is_leaf=lambda x: isinstance(x, optax.ScaleByAdamState))
        if isinstance(x, optax.ScaleByAdamState)]
    if len(found) != 1:
        raise SystemExit(f"{len(found)} Adam states in the optimizer")
    return found[0]


class ReferenceCheck:
    """The comparison, system against reference, on the timed path.

    The seeded sample, tiled to the cell's batch, goes through (1) a
    program of the step's shapes that runs ``LFM2ASR.loss`` forward
    and backward and keeps that very pass's chosen sets: the final
    normed hidden state, the target log-probabilities, the
    per-utterance loss, the last expert layer's router scores and
    every parameter's gradient, which the reference matches with its
    routing pinned to those sets; and (2) ``trainer.train_step``
    itself, ONCE, the very compiled step the window then times: its
    loss, its gradient norm, its routing counters, the gradient its
    optimizer saw and what it did to every parameter. The step has
    chosen sets of its own, which it does not give out (another
    compiled program flips other near-ties), so its expert blocks'
    gradients hold the route and (1)'s hold the precision. The step
    donates its state, so the parameters wait on the host meanwhile
    and the state is put back as it was (step 0, zero moments; (1)
    runs in the room of the zero moments: compiled for a v5e it needs
    12.0 GB beside them, the step 14.5 GB with them).

    Built once a process: ``tools/lfm2_ref_seeds.py`` reads many seeds
    through the same compiled programs."""

    KEYS = ("features", "feat_lens", "labels", "label_lens")

    def __init__(self, trainer, cfg, ctx: harness.Context):
        import jax

        from deepspeech_tpu.models.lfm2 import seq_positions

        if trainer.guardian_cfg is not None:
            raise SystemExit("the cell times the unguarded step")
        self.trainer, self.cfg, self.ctx = trainer, cfg, ctx
        mcfg, model = cfg.model, trainer.model
        stated = ctx.config["train"]
        clip = stated["grad_clip_norm"]
        self.lr = stated["learning_rate"] / stated["warmup_steps"]
        self.s = seq_positions(mcfg, int(ctx.param("bucket_frames")),
                               cfg.data.max_label_len)
        self.system = jax.jit(lambda p, b, x: system_outputs(
            model, mcfg, p, b, x, clip))
        self.plain = plain_program(mcfg, self.s, clip)
        self.leaf_sums = jax.jit(leaf_sums)
        self.step_sums = jax.jit(lambda old, new, mu, g: step_sums(
            old, new, mu, g, self.lr))

    def fresh(self, params, buffers):
        """The state as the seed made it: step 0, zero moments."""
        import jax
        import jax.numpy as jnp

        from deepspeech_tpu.train import TrainState

        trainer = self.trainer
        return jax.device_put(
            TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       batch_stats=buffers,
                       opt_state=trainer.optimizer.init(params)),
            trainer.state_sh)

    def run(self) -> dict:
        import jax
        import jax.numpy as jnp

        from deepspeech_tpu.ops import moe_pallas
        from deepspeech_tpu.parallel import shard_batch

        trainer, mcfg, ctx = self.trainer, self.cfg.model, self.ctx
        sample = _sample(self.cfg, ctx)
        rows = self.cfg.data.batch_size
        tiles, rest = divmod(rows, sample[0].shape[0])
        if rest:
            raise SystemExit(f"{sample[0].shape[0]} sample rows do not "
                             f"tile a batch of {rows}")
        tiled = shard_batch(trainer.mesh, {
            k: np.tile(x, (tiles,) + (1,) * (x.ndim - 1))
            for k, x in zip(self.KEYS, sample)})
        small = tuple(jnp.asarray(x) for x in sample)
        if int(trainer.state.step):
            raise SystemExit("the comparison starts from the seed's state")
        params, buffers = trainer.state.params, trainer.state.batch_stats
        kept = jax.device_get((params, buffers))
        # The zero moments make room for (1): 3.8 GB of the chip.
        jax.tree.map(lambda x: x.delete(), trainer.state.opt_state)

        # (1) Forward and backward at the step's shapes; the reference
        # routes its gradients by this pass's chosen sets (the first
        # tile's; its forward pass by its own).
        got = self.system(params, buffers,
                          tuple(tiled[k] for k in self.KEYS))
        grads = got.pop("grads")
        got = jax.device_get(got)
        per_tile = sample[0].shape[0] * self.s
        pinned = [np.asarray(c)[:per_tile] for c in got["chosen"]]
        want = self.plain(params, buffers, small, pinned)
        sums = {"grads": jax.device_get(self.leaf_sums(
            grads, want.pop("grads")))}
        want = jax.device_get(want)
        del grads  # room for the step

        # (2) The timed step, once. Its state is donated; afterwards
        # the reference's gradients are computed again (a second or
        # so) rather than kept beside the step's 14.5 GB.
        state, metrics = trainer.train_step(self.fresh(params, buffers),
                                            tiled)
        metrics = jax.device_get(metrics)
        counters = metrics["routing"]
        got.update(loss=metrics["loss"], grad_norm=metrics["grad_norm"],
                   pairs_held=np.sum(counters["expert_pairs"], -1) / tiles)
        old, buffers = jax.device_put(kept, (trainer.state_sh.params,
                                             trainer.state_sh.batch_stats))
        sums["step"] = jax.device_get(self.step_sums(
            old, state.params, adam_moments(state.opt_state).mu,
            self.plain(old, buffers, small, pinned)["grads"]))
        # Put the state back as the seed made it.
        jax.tree.map(lambda x: x.delete(), (state.params, state.opt_state))
        trainer.state = self.fresh(old, buffers)

        errs = errors(mcfg, got, want, sums)
        tol, differ = dict(REF_TOL), REF_CHOSEN_DIFFER
        if ctx.rehearse:  # float32 on the CPU: only the order of sums
            tol, differ = {k: 2e-3 for k in tol}, 0.02
        out = {f"ref_{k}_rms_rel": v for k, v in errs.items()
               if k != "chosen_differ"}
        out["ref_chosen_differ_share"] = errs["chosen_differ"]
        out["ref_worst_leaves"] = {
            "grad": worst_leaf(sums["grads"])[1],
            "step_grad_dense": worst_leaf(sums["step"]["grads"],
                                          experts=False)[1],
            "update": worst_leaf(sums["step"]["update"])[1]}
        out["ref_finite"] = bool(all(np.isfinite(v) for v in errs.values()))
        out["ref_ok"] = within(errs, tol, differ)
        # The step saw every row of the tiled sample, and dropped none.
        out["ref_valid_positions"] = int(counters["valid_positions"])
        out["ref_saw_every_row"] = bool(
            out["ref_valid_positions"] == tiles * int(want["valid"].sum()))
        out["ref_rows_routed"] = int(np.max(counters["rows_high_water"]))
        out["ref_row_capacity"] = int(np.max(counters["rows_capacity"]))
        out["ref_dropped_none"] = bool(np.sum(counters["dropped"]) == 0)
        if not ctx.rehearse:  # the rehearsal's sample is one small tile
            out["ref_spans_tiles"] = bool(
                out["ref_rows_routed"] >= 2 * moe_pallas.TILE_M
                and out["ref_rows_routed"] % moe_pallas.TILE_M > 0)
        return out


def route_checks(cfg) -> dict:
    """'auto' must have resolved to the compiled ``moe_gmm`` /
    ``moe_tgmm`` kernels: a run on ``ragged_dot`` or on interpreted
    kernels looks the same from outside."""
    from deepspeech_tpu.utils.impl import interpret_default, resolve_impl

    return {"moe_impl_pallas":
            resolve_impl(cfg.model.moe_impl, oracle="xla") == "pallas",
            "kernels_compiled": not interpret_default()}


def run(ctx: harness.Context) -> dict:
    import jax

    from deepspeech_tpu import obs
    from deepspeech_tpu.config import apply_overrides
    from deepspeech_tpu.data import CharTokenizer
    from deepspeech_tpu.models.lfm2 import seq_positions
    from deepspeech_tpu.parallel import shard_batch
    from deepspeech_tpu.train import Trainer

    phases = {"imports": time.perf_counter() - ctx.t_process}
    cfg = harness.model_config(ctx)
    if cfg.train.objective != "lm":
        raise SystemExit(f"preset {cfg.name!r} does not train a decoder "
                         f"(train.objective={cfg.train.objective!r})")
    for section in ("data", "train"):
        for key, want in ctx.config.get(section, {}).items():
            got = getattr(getattr(cfg, section), key)
            if got != want:
                raise SystemExit(
                    f"configs/{ctx.cell['config']}.json says {section}."
                    f"{key}={want!r}, the preset has {got!r}")
    frames = int(ctx.param("bucket_frames"))
    # One loss sync per step, as ``drivers/train.py`` has it: every
    # ``train_step`` event is a COMPLETED step. The weights come from
    # ``--seed`` like the batches.
    cfg = apply_overrides(cfg, {
        "data.batch_size": int(ctx.param("per_chip_batch")) * ctx.chips,
        "data.bucket_frames": (frames,),
        "model.lfm_seq_positions": int(ctx.param("seq_positions")),
        "train.checkpoint_dir": "", "train.log_every": 1,
        "train.epochs": 1, "train.seed": ctx.seed})
    v = cfg.model.vocab_size
    # The repo has no word-piece tokenizer; the step sees ids only, so
    # V-1 distinct symbols stand in for the slice's pieces (id 0 starts
    # and ends a transcript).
    tokenizer = CharTokenizer.synthetic_zh(v - 1)
    if tokenizer.vocab_size != v:
        raise SystemExit(f"tokenizer has {tokenizer.vocab_size} classes,"
                         f" the configuration {v}")

    t = time.perf_counter()
    params = {k: ctx.param(k) for k in (
        "per_chip_batch", "bucket_frames", "valid_frames",
        "labels_per_frame", "pool_batches")}
    # ``time_stride`` 1: no alignment bounds the labels of a decoder
    # (the generator's CTC rule would, at stride 8).
    pool = gen_batches.make_batches(
        params, seed=ctx.seed, chips=ctx.chips, vocab_size=v,
        max_label_len=cfg.data.max_label_len,
        num_features=cfg.features.num_features, time_stride=1)
    phases["make_batches"] = time.perf_counter() - t

    warmup = int(ctx.param("warmup_steps", 2))
    pipeline = Pipeline(pool, int(ctx.param("steps_per_epoch", 7500)))
    window = RoutingWindow(ctx, warmup)
    sink = SpanSink()

    # The program's tracer is on from here, not from ``fit``: this
    # cell's step is traced, lowered and compiled in the reference
    # check, and ``lfm2_setup_trace_lower_s`` is to see it.
    if ctx.trace:
        obs.tracer.configure(enabled=True, sink=sink,
                             wall=time.perf_counter)
    try:
        t = time.perf_counter()
        trainer = Trainer(cfg, pipeline, tokenizer, logger=window,
                          preempt=window)
        jax.block_until_ready(trainer.state.params)
        phases["trainer_init"] = time.perf_counter() - t
        window.memory.append(harness.memory_now())

        checks = {} if ctx.rehearse else route_checks(cfg)
        t = time.perf_counter()
        checks.update(ReferenceCheck(trainer, cfg, ctx).run())
        phases["reference_check"] = time.perf_counter() - t

        t_fit = time.perf_counter()
        trainer.fit(1)
    finally:
        obs.tracer.configure(enabled=False)
        trace_path = ctx.stop_trace()
    if window.t_start is None:
        raise SystemExit("the run ended before the warm-up was over")
    phases["warmup_steps"] = window.t_start - t_fit
    window.memory.append(harness.memory_now())
    in_window = ctx.compiles.since(window.compile_snap)

    steps = window.steps[warmup:]
    routing = window.routing[warmup:]
    if not steps:
        raise SystemExit("no step completed inside the window")
    audio = sum(gen_batches.audio_seconds(pool[(warmup + i) % len(pool)])
                for i in range(len(steps)))
    losses = [s[1] for s in window.steps]
    bad = [x for x in losses[warmup:] if not math.isfinite(x)]

    # After the window: the lowered step must hold, for every sparse
    # layer, the grouped products of its two matrices forward (2
    # ``moe_gmm``) and backward (2 ``moe_gmm`` to the rows, 2
    # ``moe_tgmm`` to the weights) and no other Mosaic call. Lowering
    # with the very arrays the loop used compiles nothing again.
    t = time.perf_counter()
    snap = ctx.compiles.snapshot()
    lowered = trainer.train_step.lower(
        trainer.state, shard_batch(trainer.mesh, pool[0]))
    text = lowered.as_text()
    counters = {"tpu_custom_calls": text.count("tpu_custom_call"),
                "kernel_calls": kernel_calls(text)}
    sparse = len(cfg.model.lfm_layer_types) - cfg.model.lfm_dense_layers
    if not ctx.rehearse:
        checks["step_holds_moe_kernels"] = (
            counters["kernel_calls"] == {"moe_gmm": 4 * sparse,
                                         "moe_tgmm": 2 * sparse}
            and counters["tpu_custom_calls"] == 6 * sparse)
    if ctx.trace:
        ma = lowered.compile().memory_analysis()
        counters["step_argument_bytes"] = ma.argument_size_in_bytes
        counters["step_temp_bytes"] = ma.temp_size_in_bytes
    counters["after_window"] = ctx.compiles.since(snap)
    phases["hlo_checks_after_window"] = time.perf_counter() - t

    dropped = sum(r["dropped_pairs"] or 0 for r in window.routing)
    high_water = max(r["rows_high_water"] or 0 for r in window.routing)
    checks["losses_finite"] = not bad and all(
        math.isfinite(x) for x in losses)
    checks["no_guardian"] = trainer.guardian is None
    checks["dropped_pairs"] = dropped
    checks["rows_fit_capacity"] = bool(
        dropped == 0 and high_water <= routing[0]["rows_capacity"])
    checks["compiles_in_window"] = in_window["compiles"]
    checks["mesh_chips"] = int(trainer.mesh.devices.size)
    ok = (checks["compiles_in_window"] == 0
          and checks["mesh_chips"] == ctx.chips
          and all(v for v in checks.values() if isinstance(v, bool)))

    rows = int(pool[0]["feat_lens"].shape[0])
    counters.update({
        "setup": window.setup_compiles, "window": in_window,
        "losses_first_last": [losses[0], losses[-1]],
        "valid_frames": [b["feat_lens"].tolist() for b in pool],
        "label_lens": [b["label_lens"].tolist() for b in pool],
        "rows_per_step": rows, "bucket_frames": frames,
        "num_features": cfg.features.num_features,
        "max_label_len": cfg.data.max_label_len,
        "seq_positions": seq_positions(cfg.model, frames,
                                       cfg.data.max_label_len),
        "routing": routing, "rows_high_water": high_water})
    return {
        "driver": "train_lfm2", "model": cfg.model,
        "correct": ok, "checks": checks,
        "attempted": len(steps), "failed": len(bad),
        "t_window_start": window.t_start, "t_window_end": steps[-1][0],
        "units": len(steps), "audio_s": audio, "latencies_ms": [],
        "step_completed_at": [s[0] for s in steps],
        "warmup_steps": warmup,
        "spans": sink.spans(), "gen_s": pipeline.gen_s,
        "counters": counters, "setup_phases": phases,
        "memory_samples": window.memory, "trace_path": trace_path,
    }
