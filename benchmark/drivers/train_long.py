"""Driver ``train_long``: a decoder-only recogniser trained on
recordings of minutes, whole, through the loop users run.

``drivers/train_lfm2.py``'s loop and helpers (its ``RoutingWindow``,
``Pipeline``, ``SpanSink``; ``Trainer(cfg, pipeline, tokenizer,
logger=..., preempt=...).fit()``; batches from ``gen/batches.py``) for
a preset whose sequences are longer than its attention's window: every
layer's attention then runs past one block of queries, forward and
backward, in the kernels of ``ops/attn_pallas.py`` on a TPU. Nothing of
the program is patched and no ``*_impl`` is set. The record says
``"driver": "train_long"``: the readers of the other drivers skip it,
and the ``smallthinker_*`` readers take it.

Outside the window, every run compares the system with the plain
reference (``reference/smallthinker_ref.py``) at the configuration's
widths on ONE seeded recording longer than the window, which the
system sees tiled to the cell's batch: through a forward-and-backward
program of the step's shapes and ONCE through the compiled step the
window then times (``ReferenceCheck``, after ``train_lfm2``'s).
"""

from __future__ import annotations

import math
import time

import numpy as np

from benchmark import harness
from benchmark.drivers import train_lfm2 as base
from benchmark.drivers.train import Pipeline, SpanSink
from benchmark.drivers.train_rnnt import kernel_calls
from benchmark.gen import batches as gen_batches
from benchmark.reference import smallthinker_ref as ref

# Kernels every layer of the step must hold, and how often at least:
# attention forward once and each backward kernel once, the grouped
# products twice forward, twice to the rows and twice to the weights.
KERNELS_A_LAYER = {"gqa_attn_fwd": 1, "gqa_attn_bwd_dq": 1,
                   "gqa_attn_bwd_dkv": 1, "moe_gmm": 4, "moe_tgmm": 2}

# System (bfloat16 matmul operands and activations, float32
# accumulation, float32 router, scores and softmax) against the plain
# float32 reference on the chip at the published widths, each as
# root-mean-square difference over the reference's root mean square. A
# limit is twice the largest reading over the seeds read, where nothing
# else is said (weights and recording from the seed:
# ``tools/smallthinker_ref_seeds.py``; PERF.md section 6, PR 45, lists
# them); beside each, the range read and what the reference with float8
# (e4m3) weights, the nearest precision below the configuration's,
# reads against the reference on the chip, as a multiple of the limit.
# The controls of ``benchmark/tests/test_smallthinker_ref_control.py``
# put one fault each into the reference and must fail these limits.
REF_TOL = {
    # -- ``LFM2ASR.loss`` forward and backward at the step's shapes,
    #    all 4 tiles; the reference's gradients routed by that pass's
    #    chosen sets. Read on nine seeds (my chip runs, PR 45: seeds
    #    101-108 and the cell's 2147491011); float8 on two --
    # bf16 rounds every operand by up to 2^-9; through four residual
    # layers, after the last RMSNorm, over the valid positions:
    # 0.641-0.681%; float8 4.4 x the limit
    "hidden": 0.0137,
    # the targets' log-probabilities (mostly the constant -log V):
    # 0.072-0.082%; float8 5.3 x
    "logp": 0.00165,
    # the per-utterance loss of ONE recording, 1,189-1,513 terms
    # summed, one number a run: 0.0011-0.0030%; three times the worst
    # (twice would be two standard deviations of one number); float8
    # 0.4-1.9 x: it does not decide
    "nll": 0.0001,
    # the last layer's 64 float32 router logits on inputs that three
    # bf16 layers rounded: 0.544-0.564%; float8 4.0 x
    "logits": 0.0113,
    # clipped gradients. W_q of the global layer (layer 0: back through
    # all four layers, ``gqa_attn_bwd_dq`` without a window)
    # 6.00-6.69%, float8 1.7 x; W_q of the first sliding layer
    # 4.61-5.13%, float8 1.9 x: the backward kernels multiply bf16
    # probabilities and bf16 ``p (dp - delta)``, a difference of two
    # sums over up to 6,763 keys, where the reference is float32. The
    # busiest held expert's down matrix in the last layer
    # (``moe_tgmm``) 1.16-1.32%, float8 4.7 x; layer 0's router (through
    # the six combine weights alone) 2.85-3.50%, float8 2.1 x; the
    # prefix projection (back through everything) 3.36-4.63%, float8
    # 1.7 x; the worst of all parameters (layer 0's gate-and-up
    # matrices or its second norm's gain) 6.27-7.00%, float8 1.6 x
    "grad_wq_global": 0.134,
    "grad_wq_sliding": 0.103,
    "grad_w2": 0.0265,
    "grad_router": 0.07,
    "grad_prefix": 0.093,
    "grad_worst": 0.14,
    # -- the timed step itself, run once on the tiled recording --
    # its loss, one number: 0.0010-0.0030%; three times the worst
    "step_loss": 0.0001,
    # its gradients' global norm before the clip, one number:
    # 0.017-0.070%; three times the worst
    "step_grad_norm": 0.0021,
    # the gradient its optimizer saw (the first moment of a first step
    # over 1 - b1: clipped) against the reference's: the worst
    # parameter outside the expert blocks (layer 0's second norm's
    # gain) 6.13-6.92%, float8 1.6 x; the expert blocks' parameters
    # pooled 5.23-5.75%, float8 1.8 x
    "step_grad_dense": 0.139,
    "step_grad_experts": 0.115,
    # each parameter's new value against its old one plus AdamW's
    # first update of that gradient at the schedule's first learning
    # rate, over that update (``train_lfm2.step_sums``): 0.0001-0.0005%.
    # The reference has no optimizer, so no float8 reading: the limit
    # is the size of the faults it is there for (a learning rate, a
    # bias correction or a clip off by one per cent)
    "update_worst": 0.01,
    # the step's own counters of pairs on each held expert and layer
    # against the reference's own choice over the valid positions
    # (near-ties flip across the share's edge): 0.38-0.49%; float8 3.2 x
    "pairs_held": 0.0098,
}
# Share of valid (position, layer) whose chosen set differs from the
# reference's: bf16 upstream flips near-ties between the sixth and
# seventh logit. 2.70-3.03%; float8 3.9 x.
REF_CHOSEN_DIFFER = 0.0605


class LongWindow(base.RoutingWindow):
    """``RoutingWindow`` that also keeps the pairs in reach of each
    step's valid positions and the held experts hit."""

    KEYS = base.RoutingWindow.KEYS + (
        "reach_pairs_window", "reach_pairs_global",
        "experts_hit_by_layer")


def sample(cfg, ctx: harness.Context) -> tuple:
    """Seeded recordings in the cell's bucket, each as long as the
    traffic's (``valid_frames``: every prefix past the window), labels
    at the traffic's rate."""
    rng = np.random.default_rng([ctx.seed, 2])
    rows = int(ctx.param("ref_rows", 1))
    frames = int(ctx.param("bucket_frames"))
    lo, hi = ctx.param("valid_frames")
    u = cfg.data.max_label_len
    f = cfg.features.num_features
    lens = rng.integers(lo, hi + 1, size=rows).astype(np.int32)
    feats = rng.standard_normal((rows, frames, f), dtype=np.float32)
    feats *= np.arange(frames)[None, :, None] < lens[:, None, None]
    label_lens = np.minimum(np.round(
        ctx.param("labels_per_frame") * lens), u).astype(np.int32)
    labels = rng.integers(1, cfg.model.vocab_size, size=(rows, u)
                          ).astype(np.int32)
    labels *= np.arange(u)[None, :] < label_lens[:, None]
    return feats, lens, labels, label_lens


def compared(mcfg) -> dict:
    """Which parameter's gradient each named ``grad_*`` reading is:
    ``W_q`` of the global layer and of the first sliding one (back
    through the attention's backward kernels of both kinds and the
    layers after them), the last layer's down matrices (the busiest
    held expert's is read), the first layer's router (its gradient
    arrives through the combine weights alone) and the prefix
    projection (back through everything)."""
    kinds = list(mcfg.lfm_layer_types)
    return {
        "grad_wq_global": (f"layer{kinds.index('full_attention')}",
                           "attn", "q", "kernel"),
        "grad_wq_sliding": (f"layer{kinds.index('sliding_attention')}",
                            "attn", "q", "kernel"),
        "grad_w2": (f"layer{len(kinds) - 1}", "moe", "w2"),
        "grad_router": ("layer0", "moe", "router"),
        "grad_prefix": ("prefix", "kernel")}


def plain_outputs(mcfg, params, batch, s: int, clip: float, pinned,
                  faults=(), q_block: int = 512) -> dict:
    """What the comparison reads, by the reference: ONE forward and
    backward pass routed by the chosen sets ``pinned`` (its own where
    None), its gradients clipped; ``chosen`` and ``pairs_held`` are each
    layer's OWN choice on that pass's activations."""
    loss, grads, out = ref.loss_and_grads(
        mcfg, params, *batch, s, faults, pinned, q_block)
    norm, grads = ref.clip_by_global_norm(grads, clip)
    return {"hidden": out["hidden"], "valid": out["valid"],
            "logp": out["logp"], "logp_mask": out["logp_mask"],
            "nll": out["nll"], "logits": out["logits"],
            "chosen": out["chosen"],
            "pairs_held": out["pairs_held"], "loss": loss,
            "grad_norm": norm, "grads": grads}


def errors(mcfg, got: dict, want: dict, sums: dict) -> dict:
    """Each compared quantity's root-mean-square difference over the
    reference's root mean square (``train_lfm2.errors``' readings, with
    this block's gradients): hidden state and router logits over the
    valid positions, log-probabilities over each utterance's own
    targets, ``grad_w2`` over the busiest held expert's matrix; and the
    share of chosen sets that differ."""
    valid = np.asarray(want["valid"])
    masks = {"hidden": valid, "logits": valid, "logp": want["logp_mask"]}
    errs = {k: base.tiled_rms_rel(got[k], want[k], masks.get(k))
            for k in ("hidden", "logp", "nll", "logits", "pairs_held")}
    expert = base.busiest_held_expert(mcfg, want)
    for name, path in compared(mcfg).items():
        leaf = base._at(sums["grads"], path)
        pick = expert if name == "grad_w2" else ...
        errs[name] = base._rel(np.asarray(leaf["err"])[pick],
                               np.asarray(leaf["ref"])[pick])
    errs["grad_worst"] = base.worst_leaf(sums["grads"])[0]
    step = sums["step"]
    errs["step_loss"] = base.tiled_rms_rel(got["loss"], want["loss"])
    errs["step_grad_norm"] = base.tiled_rms_rel(got["grad_norm"],
                                                want["grad_norm"])
    errs["step_grad_dense"] = base.worst_leaf(step["grads"],
                                              experts=False)[0]
    errs["step_grad_experts"] = base.pooled_experts(step["grads"])
    errs["update_worst"] = (base.worst_leaf(step["update"])[0]
                            if "update" in step else 0.0)
    k = np.shape(want["chosen"][0])[-1]
    layers = [np.asarray(g).reshape(-1, valid.size, k)
              for g in got["chosen"]]          # each [tiles, B*S, k]
    errs["chosen_differ"] = float(np.mean([
        ref.chosen_differ_share([layer[t] for layer in layers],
                                want["chosen"], valid)
        for t in range(layers[0].shape[0])]))
    return errs


class ReferenceCheck(base.ReferenceCheck):
    """The comparison, system against reference, on the timed path
    (``train_lfm2.ReferenceCheck``'s two passes and its ``fresh``, with
    this block's reference, at this cell's sizes).

    The seeded recording, tiled to the cell's batch, goes through (1) a
    program of the step's shapes that runs ``LFM2ASR.loss`` forward and
    backward (the attention's three kernels and the grouped products,
    compiled) and keeps that very pass's chosen sets: the final normed
    hidden state, the targets' log-probabilities, the per-utterance
    loss, the last layer's router logits and every parameter's
    gradient, which the reference matches with its routing pinned to
    those sets; and (2) ``trainer.train_step`` itself, ONCE, the very
    compiled step the window then times: its loss, its gradient norm,
    its routing counters, the gradient its optimizer saw and what it
    did to every parameter. The step donates its state, so the
    parameters (and the reference's gradients) wait on the host
    meanwhile and the state is put back as it was (step 0, zero
    moments; (1) and the reference run in the room of the zero moments:
    compiled for a v5e, 10.2 and 10.3 GB beside them).

    Built once a process: ``tools/smallthinker_ref_seeds.py`` reads
    many seeds through the same compiled programs."""

    def __init__(self, trainer, cfg, ctx: harness.Context, faults=()):
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
        self.system = jax.jit(lambda p, b, x: base.system_outputs(
            model, mcfg, p, b, x, clip))
        self.plain = jax.jit(lambda p, x, chosen: plain_outputs(
            mcfg, p, x, self.s, clip, chosen, faults))
        self.leaf_sums = jax.jit(base.leaf_sums)
        self.step_sums = jax.jit(lambda old, new, mu, g: base.step_sums(
            old, new, mu, g, self.lr))

    def run(self) -> dict:
        import jax
        import jax.numpy as jnp

        from deepspeech_tpu.ops import moe_pallas
        from deepspeech_tpu.parallel import shard_batch

        trainer, mcfg, ctx = self.trainer, self.cfg.model, self.ctx
        drawn = sample(self.cfg, ctx)
        rows = self.cfg.data.batch_size
        tiles, rest = divmod(rows, drawn[0].shape[0])
        if rest:
            raise SystemExit(f"{drawn[0].shape[0]} sample rows do not "
                             f"tile a batch of {rows}")
        tiled = shard_batch(trainer.mesh, {
            k: np.tile(x, (tiles,) + (1,) * (x.ndim - 1))
            for k, x in zip(self.KEYS, drawn)})
        small = tuple(jnp.asarray(x) for x in drawn)
        if int(trainer.state.step):
            raise SystemExit("the comparison starts from the seed's state")
        params, buffers = trainer.state.params, trainer.state.batch_stats
        kept = jax.device_get((params, buffers))
        # The zero moments make room for (1) and the reference.
        jax.tree.map(lambda x: x.delete(), trainer.state.opt_state)

        # (1) Forward and backward at the step's shapes; the reference
        # routes its gradients by this pass's chosen sets (the first
        # tile's; its ``chosen`` by its own).
        got = self.system(params, buffers,
                          tuple(tiled[k] for k in self.KEYS))
        grads = got.pop("grads")
        got = jax.device_get(got)
        got["logits"] = got.pop("scores")
        per_tile = drawn[0].shape[0] * self.s
        pinned = [np.asarray(c)[:per_tile] for c in got["chosen"]]
        want = self.plain(params, small, pinned)
        ref_grads = want.pop("grads")
        sums = {"grads": jax.device_get(self.leaf_sums(grads, ref_grads))}
        # The reference's gradients wait on the host while the step has
        # the chip: beside its temporaries they do not fit, and after
        # it neither does the reference's program beside two states.
        ref_grads = jax.device_get(ref_grads)
        want = jax.device_get(want)
        del grads  # room for the step

        # (2) The timed step, once. Its state is donated.
        state, metrics = trainer.train_step(
            self.fresh(params, buffers), tiled)
        metrics = jax.device_get(metrics)
        counters = metrics["routing"]
        got.update(loss=metrics["loss"], grad_norm=metrics["grad_norm"],
                   pairs_held=np.asarray(counters["expert_pairs"]) / tiles)
        old, buffers = jax.device_put(kept, (trainer.state_sh.params,
                                             trainer.state_sh.batch_stats))
        sums["step"] = jax.device_get(self.step_sums(
            old, state.params, base.adam_moments(state.opt_state).mu,
            jax.device_put(ref_grads, trainer.state_sh.params)))
        del ref_grads
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
            "grad": base.worst_leaf(sums["grads"])[1],
            "step_grad_dense": base.worst_leaf(sums["step"]["grads"],
                                               experts=False)[1],
            "update": base.worst_leaf(sums["step"]["update"])[1]}
        out["ref_finite"] = bool(all(np.isfinite(v) for v in errs.values()))
        out["ref_ok"] = base.within(errs, tol, differ)
        # The step saw every row of the tiled recording, past the
        # window, and dropped none.
        valid = int(np.asarray(want["valid"]).sum())
        out["ref_valid_positions"] = int(counters["valid_positions"])
        out["ref_saw_every_row"] = bool(
            out["ref_valid_positions"] == tiles * valid)
        out["ref_past_the_window"] = bool(
            not mcfg.lfm_window or valid > mcfg.lfm_window)
        out["ref_rows_routed"] = int(np.max(counters["rows_high_water"]))
        out["ref_row_capacity"] = int(np.max(counters["rows_capacity"]))
        out["ref_dropped_none"] = bool(np.sum(counters["dropped"]) == 0)
        if not ctx.rehearse:  # the rehearsal's sample is one small tile
            out["ref_spans_tiles"] = bool(
                out["ref_rows_routed"] >= 2 * moe_pallas.TILE_M)
        return out


def holds_named_kernels(calls: dict, custom_calls: int, layers: int
                        ) -> bool:
    """Every Mosaic call of the lowered step is a named kernel, and
    each of the five names is there at least as often as the layers
    need it."""
    return ("" not in calls and sum(calls.values()) == custom_calls
            and all(calls.get(k, 0) >= n * layers
                    for k, n in KERNELS_A_LAYER.items()))


def run(ctx: harness.Context) -> dict:
    import jax

    from deepspeech_tpu import obs
    from deepspeech_tpu.config import apply_overrides
    from deepspeech_tpu.data import CharTokenizer
    from deepspeech_tpu.models.lfm2 import attends_in_kernels, seq_positions
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
    # One loss sync per step, as every training cell has it: every
    # ``train_step`` event is a COMPLETED step. The weights come from
    # ``--seed`` like the batches.
    cfg = apply_overrides(cfg, {
        "data.batch_size": int(ctx.param("per_chip_batch")) * ctx.chips,
        "data.bucket_frames": (frames,),
        "model.lfm_seq_positions": int(ctx.param("seq_positions")),
        "train.checkpoint_dir": "", "train.log_every": 1,
        "train.epochs": 1, "train.seed": ctx.seed})
    v = cfg.model.vocab_size
    # V-1 distinct symbols stand in for the slice's word-pieces (id 0
    # starts and ends a transcript), as ``train_lfm2`` has it.
    tokenizer = CharTokenizer.synthetic_zh(v - 1)
    if tokenizer.vocab_size != v:
        raise SystemExit(f"tokenizer has {tokenizer.vocab_size} classes,"
                         f" the configuration {v}")

    t = time.perf_counter()
    params = {k: ctx.param(k) for k in (
        "per_chip_batch", "bucket_frames", "valid_frames",
        "labels_per_frame", "pool_batches")}
    pool = gen_batches.make_batches(
        params, seed=ctx.seed, chips=ctx.chips, vocab_size=v,
        max_label_len=cfg.data.max_label_len,
        num_features=cfg.features.num_features, time_stride=1)
    phases["make_batches"] = time.perf_counter() - t

    warmup = int(ctx.param("warmup_steps", 2))
    pipeline = Pipeline(pool, int(ctx.param("steps_per_epoch", 7500)))
    window = LongWindow(ctx, warmup)
    sink = SpanSink()

    # The program's tracer is on from here, not from ``fit``: the step
    # is traced, lowered and compiled in the reference check, and
    # ``smallthinker_setup_trace_lower_s`` is to see it.
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

        checks = {} if ctx.rehearse else base.route_checks(cfg)
        if not ctx.rehearse:
            checks["attends_in_kernels"] = attends_in_kernels(cfg.model)
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

    # After the window: every Mosaic call of the lowered step is a
    # named kernel, the attention's three and the grouped products' two
    # among them once a layer that needs them. Lowering with the very
    # arrays the loop used compiles nothing again.
    t = time.perf_counter()
    snap = ctx.compiles.snapshot()
    lowered = trainer.train_step.lower(
        trainer.state, shard_batch(trainer.mesh, pool[0]))
    text = lowered.as_text()
    counters = {"tpu_custom_calls": text.count("tpu_custom_call"),
                "kernel_calls": kernel_calls(text)}
    if not ctx.rehearse:
        checks["step_holds_named_kernels"] = holds_named_kernels(
            counters["kernel_calls"], counters["tpu_custom_calls"],
            len(cfg.model.lfm_layer_types))
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
    # every recording of every batch is longer than the window
    prefix = [-(-int(n) // cfg.model.frame_stack)
              for b in pool for n in b["feat_lens"]]
    checks["every_prefix_past_window"] = bool(
        ctx.rehearse or min(prefix) > cfg.model.lfm_window)
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
        "driver": "train_long", "model": cfg.model,
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
