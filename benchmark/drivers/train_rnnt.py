"""Driver ``train_rnnt``: transducer training through the loop users
run, fed by the benchmark's own pipeline.

The same loop as ``drivers/train.py`` (its ``Pipeline``, ``Window`` and
``SpanSink``; ``Trainer(cfg, pipeline, tokenizer, logger=...,
preempt=...).fit()``; batches from ``gen/batches.py``), for a preset
whose ``train.objective`` is ``rnnt``: the Trainer then builds
``models/transducer.RNNTModel`` and differentiates the tiled joint +
loss of ``ops/transducer.py``. Nothing of the program is patched and no
``*_impl`` is set. The record says ``"driver": "train_rnnt"``: the
readers that compute DS2 costs from ``record["model"]`` skip it, and
the ``rnnt_*`` readers take it.

Outside the window, every run compares the system with the plain
reference (``reference/rnnt_ref.py``) at the configuration's widths on
a seeded sample the reference can hold.
"""

from __future__ import annotations

import json
import math
import re
import time

import numpy as np

from benchmark import harness
from benchmark.costs import rnnt as costs
from benchmark.drivers.train import Pipeline, SpanSink, Window
from benchmark.gen import batches as gen_batches
from benchmark.reference import rnnt_ref

# The sample the reference holds at the published widths: 8 utterances
# of at most 400 frames and 32 labels (T' = 67, materialised logits
# [8,67,33,4096] = 290 MB, its autodiff a few times that). 32 labels
# and not fewer, so that the tiled joint takes the sample in two tiles
# of T' with a remainder (8 x 33 = 264 nodes a frame, 62 frames a tile,
# 67 = 62 + 5) as it takes the cell's batch in 95 (3 frames a tile,
# 285 for 284): `ref_joint_tiles` and `ref_tile_remainder` say so.
REF_ROWS = 8
REF_FRAMES = 400
REF_LABELS = 32

_KERNEL_METADATA = re.compile(r'kernel_metadata = "((?:[^"\\]|\\.)*)"')

# System (bfloat16 matmul operands and activations between layers,
# float32 accumulation and recurrent state) against the plain float32
# reference on the chip, each as root-mean-square difference over the
# reference's root mean square. bfloat16 rounds every operand by up to
# 2^-9 = 0.2%; through eight layer-normalised recurrent layers of up to
# 134 steps that is 4.0-4.4% of the encoder output's rms, and 3.5-4.1%
# of the gradient that went all the way back (the last layer's W_p);
# the log-probabilities differ by far less of THEIR rms because most of
# it is the constant -log V. Each limit is twice the largest reading
# over twelve seeds (weights and sample from the seed; PERF.md section
# 6, PR 26, lists them). The same sample through the reference with one
# fault put in, against the reference (on the chip, two seeds; the
# faults of ``benchmark/tests/test_rnnt_ref_control.py`` and int8), as
# multiples of the limits in the order below: float8 (e4m3) weights,
# the nearest precision below the configuration's, 5.6 / 4.1 / 5.4 /
# 3.7 / 5.8 / 5.4 at the least; weight-only int8 (a scale a column)
# 1.14 / 0.96 / 1.10 / 0.76 / 1.22 / 1.09, over four of six, narrowly; a
# joint without its tanh 2.8 / 3.2 / 3.1 / 5.4 / 3.0 on the five it
# moves; padded lattice nodes counted 221 / 13 / 2.4 on loss and
# gradients.
REF_TOL = {"enc": 0.09, "blank": 0.004, "emit": 0.0035, "nll": 0.0016,
           "grad_w_o": 0.045, "grad_w_p": 0.082}


def _sample(cfg, ctx: harness.Context) -> tuple:
    """A seeded ragged batch: rows padded in T' and in U among them."""
    rng = np.random.default_rng([ctx.seed, 2])
    rows = int(ctx.param("ref_rows", REF_ROWS))
    frames = int(ctx.param("ref_frames", REF_FRAMES))
    u = min(REF_LABELS, cfg.data.max_label_len)
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


def errors(got: dict, want: dict, labels, label_lens) -> dict:
    """Each compared quantity's root-mean-square difference over the
    reference's root mean square: the encoder output over its valid
    frames, the blank and label log-probabilities over each utterance's
    own lattice, the rest whole."""
    nodes = rnnt_ref.lattice_mask(want["lens"], label_lens,
                                  want["blank"].shape[1],
                                  want["blank"].shape[2])
    emits = nodes[:, :, :-1] & (
        np.arange(np.shape(labels)[1])[None, None, :]
        < np.asarray(label_lens)[:, None, None])
    frames = (np.arange(want["enc"].shape[1])[None, :, None]
              < np.asarray(want["lens"])[:, None, None])
    masks = {"enc": frames, "blank": nodes, "emit": emits}
    return {k: rnnt_ref.rms_rel(got[k], want[k], masks.get(k))
            for k in REF_TOL}


def plain_outputs(mcfg, params, batch) -> dict:
    """What the comparison reads, by the reference."""
    last = f"lstmp{mcfg.rnn_layers - 1}"
    out = rnnt_ref.forward(mcfg, params, *batch)
    _, grads = rnnt_ref.loss_and_grads(mcfg, params, *batch)
    return {**out, "grad_w_o": grads["joint"]["out"]["kernel"],
            "grad_w_p": grads["enc"][last]["wp"]}


def reference_check(trainer, cfg, ctx: harness.Context) -> dict:
    """Encoder output, the lattice's blank and label log-probabilities,
    per-utterance loss, and the gradients of the joint's output matrix
    and of the last encoder layer's projection, under the cell's own
    code path (``RNNTModel.loss``: compiled recurrences, tiled joint +
    loss and its custom gradient), against the reference."""
    import jax
    import jax.numpy as jnp

    from deepspeech_tpu.models.transducer import RNNTModel
    from deepspeech_tpu.ops.transducer import (joint_tile_frames,
                                               rnnt_joint_scores)

    model, mcfg = trainer.model, cfg.model
    params = trainer.state.params
    batch = tuple(jnp.asarray(x) for x in _sample(cfg, ctx))
    labels, label_lens = batch[2], batch[3]
    last = f"lstmp{mcfg.rnn_layers - 1}"

    # The sample is an ARGUMENT of both programs: closed over, its
    # seeded values would be constants of the HLO, and every seed would
    # compile both anew (37 s a run) instead of reading the cache.
    @jax.jit
    def system(p, batch):
        feats, lens, labels, label_lens = batch

        def mean_nll(q):
            (nll, _), state = model.apply(
                {"params": q}, *batch, True, method=RNNTModel.loss,
                capture_intermediates=True)
            return jnp.mean(nll), (nll, state["intermediates"])

        (_, (nll, mid)), grads = jax.value_and_grad(
            mean_nll, has_aux=True)(p)
        # What the loss's recursions ran on, from the very operands the
        # model handed to rnnt_joint_loss.
        w_o, b_o = mid["joint"]["out"]["__call__"][0]
        blank, emit = rnnt_joint_scores(
            mid["joint"]["enc_proj"]["__call__"][0],
            mid["joint"]["pred_proj"]["__call__"][0],
            w_o.astype(jnp.dtype(mcfg.dtype)), b_o, labels, label_lens)
        enc, enc_lens = model.apply({"params": p}, feats, lens,
                                    method=RNNTModel.encode)
        return {"enc": enc, "lens": enc_lens, "blank": blank,
                "emit": emit, "nll": nll,
                "grad_w_o": grads["joint"]["out"]["kernel"],
                "grad_w_p": grads["enc"][last]["wp"]}

    plain = jax.jit(lambda p, batch: plain_outputs(mcfg, p, batch))
    got = jax.device_get(system(params, batch))
    want = jax.device_get(plain(params, batch))
    errs = errors(got, want, labels, label_lens)
    tol = dict(REF_TOL)
    if ctx.rehearse:  # float32 on the CPU: only the order of sums
        tol = {k: 1e-3 for k in tol}
    out = {f"ref_{k}_rms_rel": v for k, v in errs.items()}
    out["ref_lens_equal"] = bool(np.array_equal(got["lens"], want["lens"]))
    out["ref_finite"] = bool(all(np.isfinite(v) for v in errs.values()))
    out["ref_ok"] = bool(all(errs[k] <= tol[k] for k in tol))
    # How the tiled joint cut the sample: more than one tile of T', the
    # last one padded, as at the cell's own shapes.
    t_enc, u1 = want["blank"].shape[1:]
    tile = joint_tile_frames(labels.shape[0], u1, t_enc)
    out["ref_joint_tiles"] = -(-t_enc // tile)
    out["ref_tile_remainder"] = t_enc % tile
    if not ctx.rehearse:  # the rehearsal's sample is one small tile
        out["ref_spans_tiles"] = bool(out["ref_joint_tiles"] >= 2
                                      and out["ref_tile_remainder"] > 0)
    return out


def route_checks(cfg) -> dict:
    """'auto' must have resolved to the compiled ``lstmp_scan_*``
    kernels: ``models/rnn._run_lstmp`` falls back to the XLA scan
    without a word (rows not a multiple of 8, weights past the VMEM
    limit), and a run on it looks the same from outside at less than
    half the rate. The CTC loss's route is none of this cell's."""
    checks = harness.kernel_route_checks(cfg)
    del checks["loss_impl_pallas"]
    return checks


def kernel_calls(lowered_text: str) -> dict:
    """Mosaic calls of the lowered step, counted by kernel name. MLIR
    prints ``kernel_metadata`` as a string with ``\\0A`` for a newline
    and ``\\22`` for a quote."""
    names = [json.loads(m.replace("\\0A", "\n").replace("\\22", '"')
                        ).get("kernel", "")
             for m in _KERNEL_METADATA.findall(lowered_text)]
    return {k: names.count(k) for k in sorted(set(names))}


def run(ctx: harness.Context) -> dict:
    import jax

    from deepspeech_tpu import obs
    from deepspeech_tpu.config import apply_overrides
    from deepspeech_tpu.data import CharTokenizer
    from deepspeech_tpu.ops.transducer import joint_tile_frames
    from deepspeech_tpu.parallel import shard_batch
    from deepspeech_tpu.train import Trainer

    phases = {"imports": time.perf_counter() - ctx.t_process}
    cfg = harness.model_config(ctx)
    if cfg.train.objective != "rnnt":
        raise SystemExit(f"preset {cfg.name!r} does not train a "
                         f"transducer (train.objective="
                         f"{cfg.train.objective!r})")
    want_labels = ctx.config.get("data", {}).get("max_label_len")
    if want_labels is not None and cfg.data.max_label_len != want_labels:
        raise SystemExit(
            f"configs/{ctx.cell['config']}.json says data.max_label_len="
            f"{want_labels}, the preset has {cfg.data.max_label_len}")
    frames = int(ctx.param("bucket_frames"))
    # One loss sync per step, as ``drivers/train.py`` has it: every
    # ``train_step`` event is a COMPLETED step. The weights come from
    # ``--seed`` like the batches.
    cfg = apply_overrides(cfg, {
        "data.batch_size": int(ctx.param("per_chip_batch")) * ctx.chips,
        "data.bucket_frames": (frames,),
        "train.checkpoint_dir": "", "train.log_every": 1,
        "train.epochs": 1, "train.seed": ctx.seed})
    v = cfg.model.vocab_size
    # The repo has no word-piece tokenizer; the step sees ids only, so
    # V-1 distinct symbols stand in for the 4095 pieces.
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

    checks = {} if ctx.rehearse else route_checks(cfg)
    t = time.perf_counter()
    checks.update(reference_check(trainer, cfg, ctx))
    phases["reference_check"] = time.perf_counter() - t

    if ctx.trace:
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

    # After the window: the lowered step must hold one forward and one
    # backward ``lstmp_scan_*`` kernel for every recurrent layer, the
    # compiled step's own memory report, and no buffer of it may have
    # the lattice's [B,T',U+1,V] elements. Lowering
    # with the very arrays the loop used finds the step in jax's
    # in-process cache: nothing is compiled again.
    t = time.perf_counter()
    snap = ctx.compiles.snapshot()
    rows = int(pool[0]["feat_lens"].shape[0])
    t_enc = costs.enc_frames(cfg.model, frames)[1]
    u1 = cfg.data.max_label_len + 1
    lattice = rows * t_enc * u1 * v
    lowered = trainer.train_step.lower(
        trainer.state, shard_batch(trainer.mesh, pool[0]))
    text = lowered.as_text()
    counters = {"tpu_custom_calls": text.count("tpu_custom_call"),
                "kernel_calls": kernel_calls(text)}
    if not ctx.rehearse:
        layers = cfg.model.rnn_layers + cfg.model.rnnt_pred_layers
        checks["step_holds_lstmp_kernels"] = (
            counters["kernel_calls"] == {"lstmp_scan_bwd": layers,
                                         "lstmp_scan_fwd": layers}
            and counters["tpu_custom_calls"] == 2 * layers)
    if ctx.trace:
        compiled = lowered.compile()
        ma = compiled.memory_analysis()
        counters["step_argument_bytes"] = ma.argument_size_in_bytes
        counters["step_temp_bytes"] = ma.temp_size_in_bytes
        if not ctx.rehearse:  # at toy sizes the lattice is no burden
            checks["step_holds_no_lattice"] = (
                ma.temp_size_in_bytes < 4 * lattice)
    counters["after_window"] = ctx.compiles.since(snap)
    phases["hlo_checks_after_window"] = time.perf_counter() - t

    checks["losses_finite"] = not bad and all(
        math.isfinite(x) for x in losses)
    checks["no_guardian"] = trainer.guardian is None
    checks["compiles_in_window"] = in_window["compiles"]
    checks["mesh_chips"] = int(trainer.mesh.devices.size)
    ok = (checks["compiles_in_window"] == 0
          and checks["mesh_chips"] == ctx.chips
          and all(v for v in checks.values() if isinstance(v, bool)))

    counters.update({
        "setup": window.setup_compiles, "window": in_window,
        "losses_first_last": [losses[0], losses[-1]],
        "valid_frames": [b["feat_lens"].tolist() for b in pool],
        "label_lens": [b["label_lens"].tolist() for b in pool],
        "rows_per_step": rows, "bucket_frames": frames,
        "num_features": cfg.features.num_features,
        "max_label_len": cfg.data.max_label_len,
        "enc_frames": t_enc, "lattice_elements": lattice,
        "joint_tile_frames": joint_tile_frames(rows, u1, t_enc)})
    return {
        "driver": "train_rnnt", "model": cfg.model,
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
