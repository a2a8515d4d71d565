"""Driver ``train``: the training loop users run, fed by the
benchmark's own pipeline.

Calls ``deepspeech_tpu.train.Trainer(cfg, pipeline, tokenizer,
logger=..., preempt=...).fit()`` — what ``train.main --synthetic``
does with its ``_SyntheticPipeline`` — with three duck-typed objects of
the benchmark's own:

  pipeline  cycles a seeded pool of batches (``gen/batches.py``); every
            batch goes through the Trainer's ``device_prefetch`` and
            ``shard_batch``
  logger    receives the Trainer's ``train_step`` events. With
            ``train.log_every=1`` each is logged after the Trainer has
            blocked on that step's loss, so an event is a COMPLETED
            step; the sync costs one host round trip per step
  preempt   ``requested()`` turns true when the window's clock runs
            out; the Trainer then leaves its loop at the step boundary

No checkpoint directory, no eval pipeline, guardian off, default mesh
(every chip on the ``data`` axis; ``data.batch_size`` is the global
batch). Nothing of the program is patched and no ``*_impl`` is set.

The window opens when the last warm-up step has completed and closes
with the last step completed before the clock ran out.
"""

from __future__ import annotations

import dataclasses
import json
import math
import time

import numpy as np

from benchmark import harness
from benchmark.gen import batches as gen_batches
from benchmark.reference import ds2_ref

# System against the plain float32 reference, eval-mode logits, on the
# chip at the published widths: the system multiplies in bfloat16
# (8 significant bits, rounding error 2^-9 per operand) through up to
# nine matmul layers and a 200-step recurrence; the root-mean-square
# difference measured 0.4-0.6% of the logits' own rms (PERF.md,
# findings of PR 22). Weight-only int8 puts about 1/(2*127*0.3) = 1.3%
# on every weight and fp8 (3 mantissa bits) 3%, several times bf16's
# 0.2%, so either would land well past this bound.
REF_RMS_TOL = 0.015
REF_FRAMES = 400
REF_ROWS_PER_CHIP = 8  # a sublane-aligned shard for the kernels


class Pipeline:
    """Duck-typed ``DataPipeline``: what ``Trainer`` calls."""

    provides_global_batches = True

    def __init__(self, pool: list, steps_per_epoch: int):
        self.pool = pool
        self.steps_per_epoch = steps_per_epoch
        self.served = 0
        self.gen_s = 0.0

    def peek(self):
        return self.pool[0]

    def epoch(self, epoch_idx: int):
        while True:  # the preempt hook ends the loop
            t = time.perf_counter()
            batch = self.pool[self.served % len(self.pool)]
            self.served += 1
            self.gen_s += time.perf_counter() - t
            yield batch

    def batches_per_epoch(self, epoch_idx: int) -> int:
        return self.steps_per_epoch


class Window:
    """Logger and preempt hook in one: opens the window after the
    warm-up steps and closes it when the clock runs out."""

    def __init__(self, ctx: harness.Context, warmup_steps: int):
        self.ctx = ctx
        self.warmup = warmup_steps
        self.steps = []      # (t_completed, loss, grad_norm)
        self.events = []     # every other event the Trainer logged
        self.t_start = None
        self.compile_snap = None
        self.setup_compiles = None
        self.memory = []

    def log(self, event: str, **fields) -> None:
        t = time.perf_counter()
        if event != "train_step":
            self.events.append((event, fields))
            return
        self.steps.append((t, fields["loss"], fields["grad_norm"]))
        if len(self.steps) == self.warmup:
            self.memory.append(harness.memory_now())
            self.setup_compiles = self.ctx.compiles.since((0, 0.0, 0))
            self.ctx.start_trace()
            self.compile_snap = self.ctx.compiles.snapshot()
            self.t_start = time.perf_counter()

    def requested(self) -> bool:
        return (self.t_start is not None and time.perf_counter()
                >= self.t_start + self.ctx.window_seconds())


class SpanSink:
    """Sink for the program's tracer (``obs.tracer.configure(sink=)``):
    keeps the span records; they are parsed after the window."""

    def __init__(self):
        self.lines = []

    def write(self, line: str) -> None:
        self.lines.append(line)

    def spans(self) -> list:
        out = []
        for line in self.lines:
            rec = json.loads(line)
            if rec.get("event") == "span":
                out.append((rec["name"], rec["ts"],
                            rec["ts"] + rec["dur_ms"] / 1e3))
        return out


def reference_check(trainer, cfg, ctx: harness.Context) -> dict:
    """The model's eval-mode logits against the plain reference on a
    few seeded utterances, outside the window."""
    import jax

    from deepspeech_tpu.parallel import batch_sharding

    rng = np.random.default_rng([ctx.seed, 2])
    rows = REF_ROWS_PER_CHIP * ctx.chips
    frames = int(ctx.param("ref_frames", REF_FRAMES))
    f = cfg.features.num_features
    lens = rng.integers(frames // 2, frames + 1, size=rows
                        ).astype(np.int32)
    feats = rng.standard_normal((rows, frames, f), dtype=np.float32)
    feats *= np.arange(frames)[None, :, None] < lens[:, None, None]
    sh = batch_sharding(trainer.mesh)
    feats_d, lens_d = jax.device_put(feats, sh), jax.device_put(lens, sh)
    params, stats = trainer.state.params, trainer.state.batch_stats

    system = jax.jit(lambda p, s, x, n: trainer.model.apply(
        {"params": p, "batch_stats": s}, x, n, train=False))
    got, got_lens = system(params, stats, feats_d, lens_d)
    plain = jax.jit(lambda p, s, x, n: ds2_ref.forward(
        cfg.model, p, s, x, n))
    want, want_lens = plain(params, stats, feats_d, lens_d)
    err = ds2_ref.relative_error(got, want, np.asarray(want_lens))
    return {"ref_rms_rel": err["rms_rel"], "ref_max_rel": err["max_rel"],
            "ref_lens_equal": bool(np.array_equal(np.asarray(got_lens),
                                                  np.asarray(want_lens))),
            "ref_batch_devices": len(feats_d.sharding.device_set),
            "ref_ok": bool(err["rms_rel"] <= REF_RMS_TOL)}


def run(ctx: harness.Context) -> dict:
    import jax

    from deepspeech_tpu import obs
    from deepspeech_tpu.config import apply_overrides
    from deepspeech_tpu.data import CharTokenizer
    from deepspeech_tpu.parallel import shard_batch
    from deepspeech_tpu.train import Trainer

    phases = {"imports": time.perf_counter() - ctx.t_process}
    cfg = harness.model_config(ctx)
    frames = int(ctx.param("bucket_frames"))
    cfg = apply_overrides(cfg, {
        "data.batch_size": int(ctx.param("per_chip_batch")) * ctx.chips,
        "data.bucket_frames": (frames,),
        "train.checkpoint_dir": "", "train.log_every": 1,
        "train.epochs": 1})
    v = cfg.model.vocab_size
    tokenizer = (CharTokenizer.english() if cfg.data.language == "en"
                 else CharTokenizer.synthetic_zh(v - 1))
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
        num_features=cfg.features.num_features,
        time_stride=cfg.model.time_stride)
    phases["make_batches"] = time.perf_counter() - t

    warmup = int(ctx.param("warmup_steps", 2))
    pipeline = Pipeline(pool, int(ctx.param("steps_per_epoch", 7500)))
    window = Window(ctx, warmup)
    sink = SpanSink()

    t = time.perf_counter()
    trainer = Trainer(cfg, pipeline, tokenizer, logger=window,
                      preempt=window)
    jax.block_until_ready(trainer.state.params)
    phases["trainer_init"] = time.perf_counter() - t
    window.memory.append(harness.memory_now())

    checks = {} if ctx.rehearse else harness.kernel_route_checks(cfg)
    t = time.perf_counter()
    checks.update(reference_check(trainer, cfg, ctx))
    phases["reference_check"] = time.perf_counter() - t

    if ctx.trace:
        # The program's own spans, on this process's clock; on, the
        # train loop blocks inside train.step.
        obs.tracer.configure(enabled=True, sink=sink,
                             wall=time.perf_counter)
    t_fit = time.perf_counter()
    try:
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
    if not steps:
        raise SystemExit("no step completed inside the window")
    audio = sum(gen_batches.audio_seconds(pool[(warmup + i) % len(pool)])
                for i in range(len(steps)))
    losses = [s[1] for s in window.steps]
    bad = [x for x in losses[warmup:] if not math.isfinite(x)]

    # After the window: the lowered step must hold the Mosaic kernels,
    # and across chips the compiled step must hold the gradient
    # all-reduce. Lowering with the very arrays the loop used finds the
    # step in jax's in-process cache: nothing is compiled again.
    t = time.perf_counter()
    snap = ctx.compiles.snapshot()
    lowered = trainer.train_step.lower(
        trainer.state, shard_batch(trainer.mesh, pool[0]))
    counters = {"tpu_custom_calls":
                lowered.as_text().count("tpu_custom_call")}
    if ctx.trace or ctx.chips > 1:
        compiled = lowered.compile()
        counters["collectives"] = harness.count_collectives(
            compiled.as_text())
        ma = compiled.memory_analysis()
        counters["step_argument_bytes"] = ma.argument_size_in_bytes
        counters["step_temp_bytes"] = ma.temp_size_in_bytes
    counters["after_window"] = ctx.compiles.since(snap)
    phases["hlo_checks_after_window"] = time.perf_counter() - t

    if not ctx.rehearse:
        checks["step_holds_kernels"] = counters["tpu_custom_calls"] > 0
    checks["losses_finite"] = not bad and all(
        math.isfinite(x) for x in losses)
    checks["no_guardian"] = trainer.guardian is None
    checks["compiles_in_window"] = in_window["compiles"]
    checks["mesh_chips"] = int(trainer.mesh.devices.size)
    if ctx.chips > 1:
        held = [c["in_use"] for c in window.memory[1]]
        checks["every_chip_holds_state"] = (
            ctx.rehearse or all(b > 100e6 for b in held))
        checks["allreduce_in_step"] = (
            counters["collectives"]["all-reduce"] > 0)
        checks["batch_spans_chips"] = (
            checks["ref_batch_devices"] == ctx.chips)
    ok = (checks["compiles_in_window"] == 0
          and checks["mesh_chips"] == ctx.chips
          and all(v for v in checks.values() if isinstance(v, bool)))

    counters.update({
        "setup": window.setup_compiles, "window": in_window,
        "losses_first_last": [losses[0], losses[-1]],
        "valid_frames": [b["feat_lens"].tolist() for b in pool],
        "rows_per_step": int(pool[0]["feat_lens"].shape[0]),
        "bucket_frames": frames,
        "num_features": cfg.features.num_features})
    return {
        "driver": "train", "model": cfg.model,
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
