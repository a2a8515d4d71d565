"""Driver ``transcribe_mtp``: a decoder-only recogniser that DRAFTS FOR
ITSELF, served through the entry point users call, fed by the
benchmark's own pipeline.

``Inferencer(cfg, tokenizer, params, buffers).decode_batch(batch)``
with ``decode.mode="lm_greedy"`` and a preset whose
``model.lm_draft_layers`` is 1 (``deepspeech_tpu/decode/lm_greedy.py``):
prefill in sub-batches into a cache (the model's layers and the draft
module's), then one on-device loop whose step verifies two positions a
stream and drafts the next. Everything around the call is
``transcribe_lm``'s, whose helpers this driver imports: the batches of
``gen/batches.py`` through the program's ``device_prefetch``, each with
``max_tokens`` (the end id is ignored), closed loop, nothing patched,
no ``*_impl`` set. The record says ``"driver": "transcribe_mtp"``: the
readers of the other drivers skip it, and the ``xing4_*`` readers take
it.

Outside the window, every run compares the system with the plain
reference (``reference/xing4_ref.py``: model AND module over whole
sequences) at the configuration's widths through the very executables
the window times (``ReferenceCheck``). The forced tokens of that call
are accepted drafts by definition, so there every step advances two
positions; in the window, on seeded weights, nearly every draft is
rejected and a step advances one. Both run the same executable.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from benchmark import harness
from benchmark.drivers.train import SpanSink
from benchmark.drivers.train_rnnt import kernel_calls
from benchmark.drivers.transcribe_lm import (_sample, cell_config,
                                             forced_tokens, route_checks,
                                             within)
from benchmark.gen import batches as gen_batches
from benchmark.reference import xing4_ref

# The sample the reference holds at the published widths: 8 utterances
# drawn as the traffic draws them, about 2,050 valid positions of 8 x
# 288. The SYSTEM sees it tiled to the cell's batch (32 times, 256
# streams), with the drawn labels as forced tokens: prefill in the
# cell's sub-batches, then the cell's loop, 22-30 steps of two tokens.
REF_ROWS = 8

# System (bfloat16 weights, matmul operands, streams and cache, float32
# accumulation, float32 router and coefficient path) against the plain
# float32 reference's full forward pass, on the chip at the published
# widths, each as root-mean-square difference over the reference's root
# mean square. Two readings a quantity (``tools/xing4_ref_seeds.py``, my
# chip run, PR 39; PERF.md section 6 lists them): the range over twelve
# seeds (weights and sample from the seed), and what the reference with
# float8 (e4m3) weights, the nearest precision below the
# configuration's, reads against the reference (two seeds). A LIMIT IS
# THE GEOMETRIC MEAN of the largest of the twelve and the smaller
# float8 reading: 1.4-1.65 times the former (5 to 13 standard deviations
# of the twelve above their largest) and as far under the latter, so
# float8 fails EVERY limit that has a float8 reading. (ISSUE 39 asked
# for twice the largest; that would put ``draft_logits`` and ``pairs``
# above float8's reading and ``logits`` 2% under it.) The readings are
# four times ``ax_k1``'s at the same depth: with the seeded b of std 1
# and phi of std 0.02 the coefficients' logits have std 2.6, and an
# error of the streams comes back through sigmoid and exp of them, so a
# sub-layer multiplies a rounding error by about 1.4 where the plain
# residual adds to it. The controls of
# ``benchmark/tests/test_xing4_ref_control.py`` put one fault each into
# the reference and must fail these limits.
REF_TOL = {
    # what the step of token j of a stream emits, all tokens of the 8
    # watched streams of the LAST tile, after prefill + steps of two
    # positions through the cache, against the reference's logits
    # there: 15.4-20.4%; float8 41.9-45.2%
    "logits": 0.293,
    # the draft module's logits at the same positions (tokens that have
    # a next input), through ITS cache: 14.7-21.0%; float8 38.6-40.7%
    "draft_logits": 0.285,
    # the last layer's and the module's cache rows of the watched
    # streams (the larger of the two readings), prefix rows as prefill
    # wrote them and text rows as the steps did: 11.2-14.5%; float8
    # 32.6-33.2%
    "rows": 0.218,
    # the last expert layer's 64 float32 router scores: 4.86-6.25%;
    # float8 14.2-14.3%
    "scores": 0.094,
    # its combine weights as a map over the 64 experts, over the valid
    # positions whose chosen set is the reference's (normalisation and
    # the factor 2): 0.44-0.60%; float8 1.64-1.65%; the factor left at
    # 1 reads 50%
    "weights": 0.0099,
    # the last layer's feed-forward hyper-connection: read mix 6.2-9.5%
    # (float8 20.0-20.3%), write mix 5.6-9.2% (20.9-21.5%), the doubly
    # stochastic residual mix 9.5-12.2% (27.5-28.6%)
    "h_pre": 0.138,
    "h_post": 0.139,
    "h_res": 0.183,
    # the call's own counters of pairs on each of the 64 experts, per
    # expert layer (the module's last; prefill + decode, over the
    # tiles): 2.05-2.60%; float8 4.82-5.15%
    "pairs": 0.0354,
    # one layer's attention in its two forms on the same seeded input:
    # the decode form over TWO new positions against the cache and the
    # sequence form, system against system, both bfloat16:
    # 0.439-0.443%, the limit twice that (no float8 reading: the
    # reference has one form; a form that lost its second position's
    # view of the first, a mask or a rotation reads tenths to ones)
    "forms": 0.0089,
}
# Share of valid (position, expert layer) whose chosen set differs from
# the reference's (bf16 upstream flips near-ties between the fourth and
# fifth of 64 scores): 8.3-10.7%; float8 45.2-45.5%.
REF_CHOSEN_DIFFER = 0.2195
# The residual mix's sums, their distance from one. Columns, the
# largest: 1.17e-6 to 1.2e-6 (the last of the 20 rounds' divisions
# leaves ``hc_eps``). Rows, root mean square: 0.29-0.40% (as near as 20
# rounds bring logits of std 2.6), the limit twice that; ONE round reads
# 41% on the CPU control.
H_RES_COLUMNS, H_RES_ROWS = 1e-5, 0.008


def system_outputs(engine, sample: dict, rows_watched, tiles: int) -> dict:
    """What the comparison reads, from the call the engine just made on
    the tiled sample, in the reference's layout (``[rows, S, ...]``
    over the packed positions): the prefix positions from the prefill
    program's watched rows (the first tile), the text positions from
    the loop's (``rows_watched``, the last tile)."""
    import jax

    m = engine.cfg.model
    last = engine.last_call
    lo, hi = rows_watched[0], rows_watched[-1] + 1
    pre, dec, cache = jax.device_get(
        (last["prefill_watch"], last["decode_watch"],
         [c[lo:hi] for c in last["cache"][-2:]]))
    a_lens = -(-sample["feat_lens"] // m.frame_stack)
    n, s = len(rows_watched), m.lfm_seq_positions
    a = pre["scores"].shape[1]
    steps = sample["label_lens"] + 1

    def packed(before, after):
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
    pairs = (np.asarray(stats["prefill"]["expert_pairs"], np.float64)
             + np.asarray(stats["decode"]["expert_pairs"])) / tiles
    out = {"logits": dec["logits"], "draft_logits": dec["draft_logits"],
           "rows": cache[0], "draft_rows": cache[1],
           "scores": packed(pre["scores"], dec["scores"]),
           "weights": weights, "chosen": chosen, "pairs": pairs}
    for key in ("h_pre", "h_post", "h_res"):
        out[key] = packed(pre[key], dec[key])
    return out


def errors(got: dict, want: dict) -> dict:
    """Each compared quantity's root-mean-square difference over the
    reference's root mean square, the share of chosen sets that differ
    and how far the residual mix's sums are from one. ``want``:
    ``xing4_ref.forward``'s output; ``got``: the same keys (``rows``:
    the last LAYER's and ``draft_rows`` the module's, or the reference's
    list an array)."""
    rel = xing4_ref.rms_rel
    valid, steps = np.asarray(want["valid"]), np.asarray(want["steps"])
    if isinstance(got["rows"], list):
        got = dict(got, rows=got["rows"][-2], draft_rows=got["rows"][-1])
    same = valid & np.all(
        np.sort(np.asarray(got["chosen"][-1]), -1)
        == np.sort(np.asarray(want["chosen"][-1]), -1), axis=-1)
    h_res = np.asarray(got["h_res"], np.float64)[valid]
    out = {
        "logits": rel(got["logits"], want["logits"], steps),
        "draft_logits": rel(got["draft_logits"], want["draft_logits"],
                            want["draft_steps"]),
        "rows": max(rel(got["rows"], want["rows"][-2], valid),
                    rel(got["draft_rows"], want["rows"][-1],
                        want["follows"])),
        "scores": rel(got["scores"], want["scores"], valid),
        # no position with the reference's set: ``chosen_differ`` says so
        "weights": rel(got["weights"], want["weights"], same)
        if same.any() else 0.0,
        "pairs": rel(got["pairs"], want["pairs"]),
        "chosen_differ": xing4_ref.chosen_differ_share(
            got["chosen"], want["chosen"], valid),
        "h_res_columns": float(np.max(np.abs(h_res.sum(-2) - 1.0))),
        "h_res_rows": float(np.sqrt(np.mean((h_res.sum(-1) - 1.0) ** 2))),
    }
    for key in ("h_pre", "h_post", "h_res"):
        out[key] = rel(got[key], want[key], valid)
    return out


def sound(errs: dict, tol: dict, chosen_differ: float) -> bool:
    """``transcribe_lm.within`` and the residual mix's sums."""
    return bool(within(errs, tol, chosen_differ)
                and errs["h_res_columns"] <= H_RES_COLUMNS
                and errs["h_res_rows"] <= H_RES_ROWS)


class ReferenceCheck:
    """The comparison, system against reference, on the timed path.

    The seeded sample, tiled to the cell's batch with its labels as
    forced tokens, goes through ``engine.transcribe``: the compiled
    prefill program in the cell's sub-batches and the compiled loop,
    the very executables the window then times (this call compiles
    them); every forced token is an accepted draft, so a step advances
    two positions. From that one call: the model's and the module's
    logits at every token of the watched streams, the last layer's and
    the module's cache rows, the last expert layer's router scores and
    combine weights, every expert layer's chosen sets, the last
    layer's hyper-connection coefficients, and the call's counters of
    pairs on each expert, against the reference's full forward pass of
    model and module over the same 8 packed sequences. Then one layer's
    attention in its two forms, the decode form over two positions.

    Built once a process: ``tools/xing4_ref_seeds.py`` reads many seeds
    through the same compiled programs."""

    def __init__(self, inferencer, cfg, ctx: harness.Context):
        import jax

        from deepspeech_tpu.models.axk1 import both_forms

        self.engine, self.cfg, self.ctx = inferencer.lm_greedy, cfg, ctx
        s = cfg.model.lfm_seq_positions
        # Pairs of positions over the last quarter of the cache.
        self.forms = jax.jit(lambda p, x: both_forms(
            cfg.model, p, x, np.arange(s - s // 4, s - 1, 2), q=2))

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
        want = jax.device_get(xing4_ref.forward(
            cfg.model, params, engine.buffers, sample["features"],
            sample["feat_lens"], sample["labels"], sample["label_lens"],
            cfg.model.lfm_seq_positions))
        errs = errors(got, want)

        x = jax.random.normal(
            jax.random.PRNGKey(ctx.seed % (2 ** 31)),
            (n, cfg.model.lfm_seq_positions, cfg.model.lfm_hidden),
            jnp.dtype(cfg.model.dtype))
        dec, seq = jax.device_get(self.forms(params["layer1"]["attn"], x))
        errs["forms"] = xing4_ref.rms_rel(dec, seq)

        tol, differ = dict(REF_TOL), REF_CHOSEN_DIFFER
        if ctx.rehearse:  # float32 on the CPU: only the order of sums
            tol, differ = {k: 2e-3 for k in tol}, 0.02
        checks = {f"ref_{k}_rms_rel": v for k, v in errs.items()
                  if k in tol}
        checks["ref_chosen_differ_share"] = errs["chosen_differ"]
        checks["ref_h_res_columns_from_one"] = errs["h_res_columns"]
        checks["ref_h_res_rows_from_one"] = errs["h_res_rows"]
        checks["ref_finite"] = bool(
            all(np.isfinite(v) for v in errs.values()))
        checks["ref_ok"] = sound(errs, tol, differ)
        # The forced call decoded every stream's tokens two a step,
        # every forced input an accepted draft, and dropped none.
        stats = out["stats"]
        tokens = tiled["label_lens"] + 1
        checks["ref_steps"] = stats["decode_steps"]
        checks["ref_saw_every_step"] = bool(
            np.array_equal(out["tokens"], tokens)
            and stats["decode_steps"] == -(-int(tokens.max()) // 2))
        checks["ref_forced_drafts_accepted"] = bool(
            stats["draft_accepted"] == stats["draft_positions"]
            == int(np.sum(tokens // 2)))
        checks["ref_dropped_none"] = stats["dropped_pairs"] == 0
        return checks


def drafted_every_step(stats: dict, top_k: int) -> bool:
    """The module drafted for every active stream of every step (the
    first drafts are prefill's, one more for each stream that went on
    after a step), and its expert layer (the last row of pairs) routed
    every position it drafted at: every emitted token but a stream's
    last."""
    active = stats["decode_steps"] * stats["rows"] \
        - stats["idle_slot_steps"]
    followed = stats["decode"]["valid_positions"] - stats["rows"]
    return bool(stats["drafts"] == active and sum(
        stats["decode"]["expert_pairs"][-1]) == top_k * followed)


def ids_equal_plain_greedy(cfg, tokenizer, engine, batch) -> bool:
    """The rehearsal's check that drafting changes no id: the same
    weights through the loop without the module, on one batch."""
    from deepspeech_tpu.decode.lm_greedy import LMGreedy

    plain = LMGreedy(dataclasses.replace(cfg, model=dataclasses.replace(
        cfg.model, lm_draft_layers=0)), engine.params, engine.buffers)
    args = (batch["features"], batch["feat_lens"])
    limits = {"max_tokens": batch["max_tokens"]}
    a, b = engine.transcribe(*args, **limits), plain.transcribe(
        *args, **limits)
    return bool(np.array_equal(a["ids"], b["ids"])
                and np.array_equal(a["tokens"], b["tokens"]))


def run(ctx: harness.Context) -> dict:
    import jax

    from deepspeech_tpu import obs
    from deepspeech_tpu.data import CharTokenizer
    from deepspeech_tpu.data.pipeline import device_prefetch
    from deepspeech_tpu.infer import Inferencer
    from deepspeech_tpu.models.lfm2 import seeded_variables

    phases = {"imports": time.perf_counter() - ctx.t_process}
    cfg = cell_config(ctx)
    if cfg.model.lm_draft_layers != 1:
        raise SystemExit(f"preset {cfg.name!r} has no draft module "
                         f"(model.lm_draft_layers="
                         f"{cfg.model.lm_draft_layers})")
    frames, rows = cfg.data.bucket_frames[0], cfg.data.batch_size
    v = cfg.model.vocab_size
    # The repo has no word-piece tokenizer; the engine sees ids only, so
    # V-1 distinct symbols stand in for the pieces.
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
    # The program's tracer is on from here: ``xing4_setup_trace_lower_s``
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
        if ctx.rehearse:
            checks["ids_equal_plain_greedy"] = ids_equal_plain_greedy(
                cfg, tokenizer, engine, pool[0])
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

    # After the window: every Mosaic call of the two lowered programs
    # must be a NAMED kernel, and the expert layers' grouped products
    # among them as ``moe_gmm``, two a layer at least (not an exact
    # count: a later PR may add a kernel, and a lowered text may share
    # one call between layers). Lowering with the very arrays the loop
    # used compiles nothing again.
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
            np.arange(REF_ROWS, dtype=np.int32), np.bool_(True), lens)}
    counters = {"kernel_calls": {}, "tpu_custom_calls": {}}
    for name, low in lowered.items():
        text = low.as_text()
        counters["kernel_calls"][name] = kernel_calls(text)
        counters["tpu_custom_calls"][name] = text.count("tpu_custom_call")
        if ctx.trace:
            ma = low.compile().memory_analysis()
            counters[f"{name}_argument_bytes"] = ma.argument_size_in_bytes
            counters[f"{name}_temp_bytes"] = ma.temp_size_in_bytes
    if not ctx.rehearse:
        checks["programs_hold_named_kernels"] = all(
            "" not in counters["kernel_calls"][name]
            and sum(counters["kernel_calls"][name].values())
            == counters["tpu_custom_calls"][name]
            and counters["kernel_calls"][name].get("moe_gmm", 0)
            >= 2 * len(engine.sparse) for name in lowered)
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
    checks["drafted_every_step"] = all(
        drafted_every_step(c["stats"], cfg.model.lfm_top_k)
        for c in calls)
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
        "driver": "transcribe_mtp", "model": cfg.model,
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
