"""Driver ``transcribe_lm``: a decoder-only recogniser SERVED through
the entry point users call, fed by the benchmark's own pipeline.

``Inferencer(cfg, tokenizer, params, buffers).decode_batch(batch)``
with ``decode.mode="lm_greedy"`` (``deepspeech_tpu/decode/
lm_greedy.py``): prefill in sub-batches into a cache, then one
on-device greedy loop. Batches come from ``gen/batches.py`` through the
program's ``device_prefetch``; each carries ``max_tokens`` (the drawn
label count + 1: the end id means nothing on seeded weights, so
``decode.lm_ignore_end`` is set). Closed loop: the next call starts
when the ids of the last are back on the host. Nothing of the program
is patched and no ``*_impl`` is set. The record says ``"driver":
"transcribe_lm"``: the readers of the other drivers skip it, and the
``axk1_*`` readers take it.

The window opens when the warm-up call has completed and closes with
the last call completed after the clock ran out; ``audio_s`` is the
valid audio of the calls completed in it.

Outside the window, every run compares the system with the plain
reference (``reference/axk1_ref.py``) at the configuration's widths
through the very executables the window times (``ReferenceCheck``).
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from benchmark import harness
from benchmark.drivers.train import SpanSink
from benchmark.drivers.train_rnnt import kernel_calls
from benchmark.gen import batches as gen_batches
from benchmark.reference import axk1_ref

# The sample the reference holds at the published widths: 8 utterances
# drawn as the traffic draws them (valid 12-16.5 s in the 1696-frame
# bucket, 3.6 labels a second), about 2,050 valid positions of 8 x 288.
# The SYSTEM sees it tiled to the cell's batch (32 times, 256 streams),
# with the drawn labels as forced tokens: prefill in the cell's
# sub-batches, then the cell's decode loop, 44-60 steps.
REF_ROWS = 8

# System (bfloat16 weights, matmul operands, activations and cache,
# float32 accumulation, float32 router) against the plain float32
# reference's full forward pass with expanded keys and values, on the
# chip at the published widths, each as root-mean-square difference
# over the reference's root mean square. A limit is twice the largest
# reading over twelve seeds (``tools/axk1_ref_seeds.py``, weights and
# sample from the seed; PERF.md section 6, PR 32, lists them); beside
# each, the range read and what the reference with float8 (e4m3)
# weights, the nearest precision below the configuration's, reads
# against the reference on the chip (two seeds) as a multiple of the
# limit. The controls of ``benchmark/tests/test_axk1_ref_control.py``
# put one fault each into the reference and must fail these limits.
REF_TOL = {
    # what decode step j of a stream emits, all steps of the 8 watched
    # streams of the LAST tile (prefilled by the last sub-batch), after
    # prefill + j steps through the cache, against the reference's
    # logits at that position; bf16 rounds every operand by up to 2^-9
    # through eight residual layers and the head: 4.17-5.18%; float8
    # 1.65 x
    "logits": 0.104,
    # the last layer's cache rows (c_kv normed | k_rope rotated) of the
    # watched streams, prefix rows as prefill wrote them and text rows
    # as the decode steps did, over the valid positions (in bfloat16,
    # behind seven layers): 3.22-3.70%; float8 1.57 x
    "rows": 0.074,
    # the last expert layer's 192 float32 router scores on inputs that
    # seven bf16 layers rounded, prefill and decode positions:
    # 1.70-1.96%; float8 1.58 x
    "scores": 0.039,
    # its combine weights as a map over the 192 experts, over the valid
    # positions whose chosen set is the reference's (normalisation and
    # the factor 2.5; near-ties are ``chosen_differ``'s): 0.12-0.15%;
    # float8 1.87 x; the factor left at 1 reads 200 x
    "weights": 0.0030,
    # the call's own counters of pairs on the held experts, per expert
    # layer (prefill + decode, over the tiles), against the reference's
    # count over the valid positions (near-ties flip across the share's
    # edge): 0.31-0.89%; float8 0.5-0.6 x (it does not tell them apart)
    "pairs_held": 0.018,
    # one layer's attention in its two forms on the same seeded input at
    # the same positions: the decode form (absorbed, against the cache)
    # against the sequence form (expanded), system against system, both
    # bfloat16: 0.453-0.456%. The reference has no second form, so no
    # float8 reading: a form that lost its rotary part, a norm or a
    # mask reads tenths to ones.
    "forms": 0.0091,
}
# Share of valid (position, expert layer) whose chosen set differs from
# the reference's: bf16 upstream flips near-ties between the eighth and
# ninth score, or between the fourth and fifth group, of 192 scores
# that lie close together on seeded weights: 11.4-12.3%; float8 2.25 x;
# plain top-8 without groups 3.5 x.
REF_CHOSEN_DIFFER = 0.247


def _sample(cfg, ctx: harness.Context) -> dict:
    """A seeded ragged batch drawn as the traffic draws its own."""
    params = {k: ctx.param(k) for k in (
        "bucket_frames", "valid_frames", "labels_per_frame")}
    params.update(per_chip_batch=int(ctx.param("ref_rows", REF_ROWS)),
                  pool_batches=1)
    seed = int(np.random.default_rng([ctx.seed, 2]).integers(2 ** 31))
    return gen_batches.make_batches(
        params, seed=seed, chips=1, vocab_size=cfg.model.vocab_size,
        max_label_len=cfg.data.max_label_len,
        num_features=cfg.features.num_features, time_stride=1)[0]


def forced_tokens(labels, label_lens) -> np.ndarray:
    """What each decode step is fed: id 0 starts, then the labels; -1
    (the stream's own argmax) past them."""
    b, u = labels.shape
    out = np.full((b, u + 1), -1, np.int32)
    out[:, 0] = 0
    keep = np.arange(u)[None, :] < np.asarray(label_lens)[:, None]
    out[:, 1:] = np.where(keep, labels, -1)
    return out


def system_outputs(engine, sample: dict, rows_watched, tiles: int) -> dict:
    """What the comparison reads, from the call the engine just made on
    the tiled sample, in the reference's layout (``[rows, S, ...]``
    over the packed positions): the prefix positions from the prefill
    program's watched rows (the first tile), the text positions from
    the decode loop's (``rows_watched``, the last tile)."""
    import jax

    m = engine.cfg.model
    last = engine.last_call
    pre, dec, cache = jax.device_get(
        (last["prefill_watch"], last["decode_watch"],
         [c[rows_watched[0]:rows_watched[-1] + 1] for c in last["cache"]]))
    a_lens = -(-sample["feat_lens"] // m.frame_stack)
    n, s = len(rows_watched), m.lfm_seq_positions
    a = pre["scores"].shape[1]
    steps = sample["label_lens"] + 1

    def packed(before, after):
        """``[n, S, ...]``: the prefix positions from the prefill
        program, each stream's steps from the decode loop."""
        out = np.zeros((n, s) + before.shape[2:], before.dtype)
        out[:, :a] = before
        for r in range(n):
            out[r, a_lens[r]:a_lens[r] + steps[r]] = after[r, :steps[r]]
        return out

    chosen = [packed(p, d) for p, d in zip(pre["chosen"], dec["chosen"])]
    weights = np.zeros((n, s, m.lfm_experts), np.float32)
    np.put_along_axis(weights, chosen[-1],
                      packed(pre["weights"], dec["weights"]), axis=-1)
    stats = last["stats"]
    pairs = [sum(p) + sum(d) for p, d in zip(
        stats["prefill"]["expert_pairs"], stats["decode"]["expert_pairs"])]
    return {"logits": dec["logits"], "rows": cache[-1],
            "scores": packed(pre["scores"], dec["scores"]),
            "weights": weights,
            "chosen": chosen, "pairs_held": np.asarray(pairs) / tiles}


def errors(got: dict, want: dict) -> dict:
    """Each compared quantity's root-mean-square difference over the
    reference's root mean square, and the share of chosen sets that
    differ. ``want``: ``axk1_ref.forward``'s output; ``got``: the same
    keys (``rows``: the last layer's alone, or a list a layer)."""
    valid, steps = np.asarray(want["valid"]), np.asarray(want["steps"])
    rows = got["rows"][-1] if isinstance(got["rows"], list) \
        else got["rows"]
    same = valid & np.all(
        np.sort(np.asarray(got["chosen"][-1]), -1)
        == np.sort(np.asarray(want["chosen"][-1]), -1), axis=-1)
    return {
        "logits": axk1_ref.rms_rel(got["logits"], want["logits"], steps),
        "rows": axk1_ref.rms_rel(rows, want["rows"][-1], valid),
        "scores": axk1_ref.rms_rel(got["scores"], want["scores"], valid),
        # no position with the reference's set: ``chosen_differ`` says so
        "weights": axk1_ref.rms_rel(got["weights"], want["weights"], same)
        if same.any() else 0.0,
        "pairs_held": axk1_ref.rms_rel(got["pairs_held"],
                                       want["pairs_held"]),
        "chosen_differ": axk1_ref.chosen_differ_share(
            got["chosen"], want["chosen"], valid),
    }


def within(errs: dict, tol: dict, chosen_differ: float) -> bool:
    return bool(all(errs[k] <= tol[k] for k in tol if k in errs)
                and errs["chosen_differ"] <= chosen_differ)


class ReferenceCheck:
    """The comparison, system against reference, on the timed path.

    The seeded sample, tiled to the cell's batch with its labels as
    forced tokens, goes through ``engine.transcribe``: the compiled
    prefill program in the cell's sub-batches and the compiled decode
    loop, the very executables the window then times (this call
    compiles them). From that one call: the logits every decode step
    of the watched streams emitted, the last layer's cache rows, the
    last expert layer's router scores and combine weights, every expert
    layer's chosen sets, and the call's counters of pairs on the held
    experts, against the reference's full forward pass (expanded keys
    and values, no cache) over the same 8 packed sequences. Then one
    layer's attention in its two forms on a seeded input.

    Built once a process: ``tools/axk1_ref_seeds.py`` reads many seeds
    through the same compiled programs."""

    def __init__(self, inferencer, cfg, ctx: harness.Context):
        import jax

        from deepspeech_tpu.models.axk1 import both_forms

        self.engine, self.cfg, self.ctx = inferencer.lm_greedy, cfg, ctx
        s = cfg.model.lfm_seq_positions
        # One layer's attention in both forms, the decode form at the
        # last quarter of the positions.
        self.forms = jax.jit(lambda p, x: both_forms(
            cfg.model, p, x, np.arange(s - s // 4, s)))

    def run(self, params=None) -> dict:
        import jax
        import jax.numpy as jnp

        cfg, ctx, engine = self.cfg, self.ctx, self.engine
        params = engine.params if params is None else params
        sample = _sample(cfg, ctx)
        n = sample["feat_lens"].shape[0]
        rows = cfg.data.batch_size
        tiles, rest = divmod(rows, n)
        if rest or n > cfg.decode.lm_prefill_rows:
            raise SystemExit(f"{n} sample rows do not tile {rows} streams"
                             f" in sub-batches of "
                             f"{cfg.decode.lm_prefill_rows}")
        tiled = {k: np.tile(x, (tiles,) + (1,) * (x.ndim - 1))
                 for k, x in sample.items()}
        watched = np.arange(rows - n, rows, dtype=np.int32)
        out = engine.transcribe(
            tiled["features"], tiled["feat_lens"],
            max_tokens=tiled["label_lens"] + 1,
            forced=forced_tokens(tiled["labels"], tiled["label_lens"]),
            watch=watched)
        got = system_outputs(engine, sample, watched, tiles)
        want = jax.device_get(axk1_ref.forward(
            cfg.model, params, sample["features"], sample["feat_lens"],
            sample["labels"], sample["label_lens"],
            cfg.model.lfm_seq_positions))
        errs = errors(got, want)

        x = jax.random.normal(
            jax.random.PRNGKey(ctx.seed % (2 ** 31)),
            (n, cfg.model.lfm_seq_positions, cfg.model.lfm_hidden),
            jnp.dtype(cfg.model.dtype))
        dec, seq = jax.device_get(self.forms(params["layer1"]["attn"], x))
        errs["forms"] = axk1_ref.rms_rel(dec, seq)

        tol, differ = dict(REF_TOL), REF_CHOSEN_DIFFER
        if ctx.rehearse:  # float32 on the CPU: only the order of sums
            tol, differ = {k: 2e-3 for k in tol}, 0.02
        checks = {f"ref_{k}_rms_rel": v for k, v in errs.items()
                  if k != "chosen_differ"}
        checks["ref_chosen_differ_share"] = errs["chosen_differ"]
        checks["ref_finite"] = bool(
            all(np.isfinite(v) for v in errs.values()))
        checks["ref_ok"] = within(errs, tol, differ)
        # The forced call decoded every stream's steps and dropped none.
        stats = out["stats"]
        checks["ref_steps"] = stats["decode_steps"]
        checks["ref_saw_every_step"] = bool(
            np.array_equal(out["tokens"], tiled["label_lens"] + 1)
            and stats["decode_steps"] == int(sample["label_lens"].max()) + 1)
        checks["ref_dropped_none"] = stats["dropped_pairs"] == 0
        return checks


def cell_config(ctx: harness.Context):
    """The preset as the cell runs it: checked against the
    configuration file, then the mix's batch, bucket, cache rows and
    prefill sub-batch, and the end id ignored."""
    from deepspeech_tpu.config import apply_overrides

    cfg = harness.model_config(ctx)
    # A rehearsal's sizes come from JSON: lists where the preset has
    # tuples, which the reference's compiled blocks hash.
    cfg = dataclasses.replace(cfg, model=dataclasses.replace(
        cfg.model, **{k: tuple(v) for k, v in vars(cfg.model).items()
                      if isinstance(v, list)}))
    if cfg.decode.mode != "lm_greedy":
        raise SystemExit(f"preset {cfg.name!r} is not served by "
                         f"lm_greedy (decode.mode={cfg.decode.mode!r})")
    for section in ("data", "decode"):
        for key, want in ctx.config.get(section, {}).items():
            got = getattr(getattr(cfg, section), key)
            if got != want:
                raise SystemExit(
                    f"configs/{ctx.cell['config']}.json says {section}."
                    f"{key}={want!r}, the preset has {got!r}")
    return apply_overrides(cfg, {
        "data.batch_size": int(ctx.param("per_chip_batch")) * ctx.chips,
        "data.bucket_frames": (int(ctx.param("bucket_frames")),),
        "model.lfm_seq_positions": int(ctx.param("cache_rows")),
        "decode.lm_prefill_rows": int(ctx.param(
            "prefill_rows", cfg.decode.lm_prefill_rows)),
        "decode.lm_ignore_end": True, "train.checkpoint_dir": ""})


def route_checks(cfg) -> dict:
    """'auto' must have resolved to the compiled ``moe_gmm`` kernel: a
    run on ``ragged_dot`` or on interpreted kernels looks the same from
    outside."""
    from deepspeech_tpu.utils.impl import interpret_default, resolve_impl

    return {"moe_impl_pallas":
            resolve_impl(cfg.model.moe_impl, oracle="xla") == "pallas",
            "kernels_compiled": not interpret_default()}


def run(ctx: harness.Context) -> dict:
    import jax

    from deepspeech_tpu import obs
    from deepspeech_tpu.data import CharTokenizer
    from deepspeech_tpu.data.pipeline import device_prefetch
    from deepspeech_tpu.infer import Inferencer
    from deepspeech_tpu.models.lfm2 import seeded_variables

    phases = {"imports": time.perf_counter() - ctx.t_process}
    cfg = cell_config(ctx)
    frames, rows = cfg.data.bucket_frames[0], cfg.data.batch_size
    v = cfg.model.vocab_size
    # The repo has no word-piece tokenizer; the engine sees ids only, so
    # V-1 distinct symbols stand in for the slice's pieces.
    tokenizer = CharTokenizer.synthetic_zh(v - 1)

    t = time.perf_counter()
    params = {k: ctx.param(k) for k in (
        "per_chip_batch", "bucket_frames", "valid_frames",
        "labels_per_frame", "pool_batches")}
    pool = gen_batches.make_batches(
        params, seed=ctx.seed, chips=ctx.chips, vocab_size=v,
        max_label_len=cfg.data.max_label_len,
        num_features=cfg.features.num_features, time_stride=1)
    for batch in pool:
        batch["max_tokens"] = batch["label_lens"] + 1
    phases["make_batches"] = time.perf_counter() - t

    sink = SpanSink()
    gen = {"s": 0.0, "served": 0}

    def cycle():
        while True:
            t0 = time.perf_counter()
            batch = pool[gen["served"] % len(pool)]
            gen["served"] += 1
            gen["s"] += time.perf_counter() - t0
            yield batch

    def put(batch):
        return {"features": jax.device_put(batch["features"]),
                "feat_lens": jax.device_put(batch["feat_lens"]),
                "max_tokens": batch["max_tokens"], "host": batch}

    memory, calls = [], []
    # The program's tracer is on from here: ``axk1_setup_trace_lower_s``
    # is to see the weights' initialisation and the reference check,
    # where this cell's two programs are traced, lowered and compiled.
    if ctx.trace:
        obs.tracer.configure(enabled=True, sink=sink,
                             wall=time.perf_counter)
    try:
        t = time.perf_counter()
        weights, buffers = seeded_variables(cfg, ctx.seed)
        inferencer = Inferencer(cfg, tokenizer, weights, buffers)
        del weights
        engine = inferencer.lm_greedy
        jax.block_until_ready(engine.params)
        phases["weights"] = time.perf_counter() - t
        memory.append(harness.memory_now())

        checks = {} if ctx.rehearse else route_checks(cfg)
        t = time.perf_counter()
        checks.update(ReferenceCheck(inferencer, cfg, ctx).run())
        phases["reference_check"] = time.perf_counter() - t

        t = time.perf_counter()
        batches = device_prefetch(cycle(), put_fn=put)
        warmup = int(ctx.param("warmup_calls", 1))
        for _ in range(warmup):
            inferencer.decode_batch(next(batches))
        phases["warmup_calls"] = time.perf_counter() - t
        memory.append(harness.memory_now())
        setup_compiles = ctx.compiles.since((0, 0.0, 0))
        ctx.start_trace()
        snap = ctx.compiles.snapshot()
        t_start = time.perf_counter()
        while True:
            t0 = time.perf_counter()
            batch = next(batches)
            t1 = time.perf_counter()
            texts = inferencer.decode_batch(batch)
            calls.append({
                "t": time.perf_counter(), "texts": len(texts),
                "input_s": t1 - t0,
                "audio_s": gen_batches.audio_seconds(batch["host"]),
                "valid_frames": batch["host"]["feat_lens"],
                "max_tokens": batch["max_tokens"],
                "stats": engine.last_call["stats"]})
            if calls[-1]["t"] >= t_start + ctx.window_seconds():
                break
    finally:
        obs.tracer.configure(enabled=False)
        trace_path = ctx.stop_trace()
    memory.append(harness.memory_now())
    in_window = ctx.compiles.since(snap)

    # After the window: each of the two lowered programs must hold the
    # expert layers' grouped products as ``moe_gmm`` and no other
    # Mosaic call (the decode loop's body holds 2 a layer; the text of
    # the prefill program shares some layers' calls, so its count is
    # reported, not fixed). Lowering with the very arrays the loop used
    # compiles nothing again.
    t = time.perf_counter()
    snap = ctx.compiles.snapshot()
    cache = engine.cache_for(rows, frames)
    feats = jax.device_put(pool[0]["features"])
    lens = jax.device_put(pool[0]["feat_lens"])
    lowered = {
        "prefill": engine.prefill.lower(
            engine.params, engine.buffers, cache, feats, lens, 0),
        "decode": engine.decode.lower(
            engine.params, engine.buffers, cache, lens, lens,
            forced_tokens(pool[0]["labels"], pool[0]["label_lens"]),
            np.arange(REF_ROWS, dtype=np.int32), np.bool_(True))}
    counters = {"kernel_calls": {}, "tpu_custom_calls": {}}
    for name, low in lowered.items():
        text = low.as_text()
        counters["kernel_calls"][name] = kernel_calls(text)
        counters["tpu_custom_calls"][name] = text.count("tpu_custom_call")
        if ctx.trace:
            ma = low.compile().memory_analysis()
            counters[f"{name}_argument_bytes"] = ma.argument_size_in_bytes
            counters[f"{name}_temp_bytes"] = ma.temp_size_in_bytes
    sparse = len(engine.sparse)
    if not ctx.rehearse:
        checks["programs_hold_moe_kernels"] = (
            counters["kernel_calls"]["decode"] == {"moe_gmm": 2 * sparse}
            and all(2 <= counters["tpu_custom_calls"][name]
                    == counters["kernel_calls"][name].get("moe_gmm")
                    for name in lowered))
    engine._cache = cache
    counters["after_window"] = ctx.compiles.since(snap)
    phases["hlo_checks_after_window"] = time.perf_counter() - t

    dropped = sum(c["stats"]["dropped_pairs"] for c in calls)
    checks["dropped_pairs"] = dropped
    checks["rows_fit_capacity"] = bool(dropped == 0 and all(
        c["stats"][part]["rows_high_water"]
        <= c["stats"][part]["rows_capacity"]
        for c in calls for part in ("prefill", "decode")))
    checks["every_stream_decoded"] = all(
        c["texts"] == rows and c["stats"]["decode"]["valid_positions"]
        == int(np.sum(c["max_tokens"])) for c in calls)
    checks["compiles_in_window"] = in_window["compiles"]
    ok = (checks["compiles_in_window"] == 0
          and all(v for v in checks.values() if isinstance(v, bool)))

    counters.update({
        "setup": setup_compiles, "window": in_window,
        "rows_per_call": rows, "bucket_frames": frames,
        "num_features": cfg.features.num_features,
        "cache_rows": cfg.model.lfm_seq_positions,
        "prefill_rows": cfg.decode.lm_prefill_rows,
        "cache_bytes": int(sum(c.nbytes for c in cache)),
        "calls": [{"completed_s": c["t"] - t_start,
                   "input_s": c["input_s"],
                   "valid_frames": c["valid_frames"].tolist(),
                   "max_tokens": c["max_tokens"].tolist(),
                   **c["stats"]} for c in calls]})
    return {
        "driver": "transcribe_lm", "model": cfg.model,
        "correct": ok, "checks": checks,
        "attempted": len(calls), "failed": 0,
        "t_window_start": t_start, "t_window_end": calls[-1]["t"],
        "units": len(calls), "audio_s": sum(c["audio_s"] for c in calls),
        "latencies_ms": [],
        "call_completed_at": [c["t"] for c in calls],
        "spans": sink.spans(), "gen_s": gen["s"],
        "counters": counters, "setup_phases": phases,
        "memory_samples": memory, "trace_path": trace_path,
    }
