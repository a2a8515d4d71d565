"""Driver ``transcribe_long``: a decoder-only recogniser with
grouped-query attention, windowed and global layers and a cache per
layer kind, SERVED on recordings of minutes through the entry point
users call, fed by the benchmark's own pipeline.

``Inferencer(cfg, tokenizer, params, buffers).decode_batch(batch)``
with ``decode.mode="lm_greedy"`` (``deepspeech_tpu/decode/
lm_greedy.py``): prefill in sub-batches, in query blocks that never
hold an ``[S, S]`` array, into a RING of ``lfm_window`` rows for each
sliding layer and a full cache for each global one; then one on-device
greedy loop of over a thousand steps. Everything around the call is
``transcribe_lm``'s, whose helpers this driver imports: the batches of
``gen/batches.py`` through the program's ``device_prefetch``, each with
``max_tokens`` (the end id is ignored), closed loop, nothing patched,
no ``*_impl`` set. The record says ``"driver": "transcribe_long"``: the
readers of the other drivers skip it, and the ``trinity_*`` readers
take it.

Before anything is compared or timed, ``BALANCE_STEPS`` x the pool's
batches served calls let the held experts' selection bias follow the
family's balancing rule
(``balance_router``): a seeded router loads its experts unevenly, and
how many held experts a decode step touches (each 56.6 MB of the step's
reads) then moves the rate by seed; training leaves a bias that evens
the load, and that is the state the cell runs in.

Outside the window, every run compares the system with the plain
reference (``reference/trinity_ref.py``: the full forward pass over
whole packed sequences, band and causal masks by index arithmetic) at
the configuration's widths through the very executables the window
times (``ReferenceCheck``), and holds the call's counters to what the
lengths imply, so that no later change serves the window from a full
cache, or the global layer from a ring, unseen.
"""

from __future__ import annotations

import re
import time

import numpy as np

from benchmark import harness
from benchmark.drivers.train import SpanSink
from benchmark.drivers.train_rnnt import kernel_calls
from benchmark.drivers.transcribe_lm import (_sample, cell_config,
                                             forced_tokens, route_checks,
                                             within)
from benchmark.gen import batches as gen_batches
from benchmark.reference import trinity_ref

# The sample the reference holds at the published widths: 2 recordings
# drawn as the traffic draws them (35,251 and 39,751 valid frames of the
# 42,000-frame bucket: prefixes of 4,407 and 4,969 positions, 1,269 and
# 1,431 labels), 11,240 valid positions of 2 x 6,784. The SYSTEM sees it
# tiled to the cell's batch (8 times, 16 streams), with the drawn labels
# as forced tokens: prefill in the cell's sub-batches of 2, then the
# cell's decode loop, 1,432 steps.
REF_ROWS = 2
# Queries a block of the reference's attention: [1, 48, 256, 6784]
# float32 scores are 0.33 GB (the chip holds the program's 11 GB beside).
REF_Q_BLOCK = 256
# Positions at which one layer's two forms are compared: before, at and
# (most) past the window, up to the longest prefix.
FORMS_AT = (0, 1000, 4095, 4096, 4097, 4300, 4500, 4700, 4900, 5000,
            5100, 5249)

# System (bfloat16 weights, matmul operands, activations and cache,
# float32 accumulation, float32 scores, softmax, gate and router)
# against the plain float32 reference's full forward pass, on the chip
# at the published widths, each as root-mean-square difference over the
# reference's root mean square. Each limit is TWICE the worst reading
# over nineteen seeds on the chip (``tools/trinity_ref_seeds.py``, seeds
# 101-103 and 201-212, weights and sample from the seed, and the cell's
# own first four runs; my chip runs, PR 41, PERF.md section 6). Beside each: the range read, and what the
# reference with float8 (e4m3) weights, the nearest precision below the
# configuration's, reads against the reference on the chip (seeds 101,
# 201, 202): it is over EVERY limit it has a reading for. The controls
# of ``benchmark/tests/test_trinity_ref_control.py`` put one fault each
# into the reference and must fail these limits.
REF_TOL = {
    # what decode step j of a stream emits, all steps of the 2 watched
    # streams of the LAST tile (prefilled by the last sub-batch), after
    # prefill + j steps through rings and full cache, against the
    # reference's logits at that position
    "logits": 0.10,         # 0.0377-0.0506; float8 0.145-0.158
    # the last sliding layer's RING of the watched streams after the
    # call: slot s against the reference's keys and values at the newest
    # position of s's class (p - W + 1 .. p, slot j mod W)
    "ring": 0.041,          # 0.0148-0.0203; float8 0.068-0.072
    # the global layer's cache rows 0 .. p against the reference's
    "rows": 0.042,          # 0.0159-0.0209; float8 0.066-0.071
    # the gated heads' output before Wo of the last sliding layer and of
    # the global layer, prefix positions as prefill gave them and text
    # positions as the steps did
    "gated_window": 0.028,  # 0.0111-0.0141; float8 0.067-0.068
    "gated_global": 0.034,  # 0.0140-0.0169; float8 0.072-0.074
    # the last expert layer's 256 float32 router scores
    "scores": 0.017,        # 0.0064-0.0086; float8 0.027-0.030
    # its combine weights as a map over the 256 experts, over the valid
    # positions whose chosen set is the reference's (normalisation and
    # the factor 2.448)
    "weights": 0.0015,      # 0.00056-0.00073; float8 0.0030-0.0032
    # the call's own counters of pairs on each of the 32 held experts,
    # per expert layer (prefill + decode, over the tiles)
    "pairs": 0.021,         # 0.0078-0.0104; float8 0.029-0.032
    # one sliding and one global layer's attention in its two forms on
    # the same seeded input at positions before, at and past the window:
    # the decode form (against a ring of 4096 / a full cache) against
    # the sequence form (query blocks), system against system, both
    # bfloat16 (the larger of the two layers' readings)
    "forms": 0.005,         # 0.0022-0.0025 (no reference in it)
}
# Share of valid (position, expert layer) whose chosen set differs from
# the reference's (bf16 upstream flips near-ties between the fourth and
# fifth of 256 scores): 0.0314-0.0344 read; float8 0.218-0.223.
REF_CHOSEN_DIFFER = 0.069


def system_outputs(engine, sample: dict, rows_watched, tiles: int) -> dict:
    """What the comparison reads, from the call the engine just made on
    the tiled sample, in the reference's layout (``[rows, S, ...]``
    over the packed positions): the prefix positions from the prefill
    program's watched rows (the first tile), the text positions from
    the decode loop's (``rows_watched``, the last tile); the last
    sliding layer's ring and the global layer's cache of the watched
    streams as the call left them."""
    import jax

    m = engine.cfg.model
    last = engine.last_call
    lo, hi = rows_watched[0], rows_watched[-1] + 1
    kinds = engine.kinds
    pre, dec, ring, rows = jax.device_get(
        (last["prefill_watch"], last["decode_watch"],
         [c[lo:hi] for c in last["cache"][kinds["sliding_attention"][-1]]],
         [c[lo:hi] for c in last["cache"][kinds["full_attention"][-1]]]))
    # (keys, values) [n, R, kv, hd] each -> [n, R, 2 kv, hd]
    ring, rows = np.concatenate(ring, 2), np.concatenate(rows, 2)
    a_lens = -(-sample["feat_lens"] // m.frame_stack)
    n, s = len(rows_watched), m.lfm_seq_positions
    a = pre["scores"].shape[1]
    steps = sample["label_lens"] + 1

    def packed(before, after):
        out = np.zeros((n, s) + before.shape[2:], np.float32)
        out[:, :a] = before
        for r in range(n):
            out[r, a_lens[r]:a_lens[r] + steps[r]] = after[r, :steps[r]]
        return out

    chosen = [packed(p, d).astype(np.int32)
              for p, d in zip(pre["chosen"], dec["chosen"])]
    weights = np.zeros((n, s, m.lfm_experts), np.float32)
    np.put_along_axis(weights, chosen[-1],
                      packed(pre["weights"], dec["weights"]), axis=-1)
    stats = last["stats"]
    pairs = (np.asarray(stats["prefill"]["expert_pairs"], np.float64)
             + np.asarray(stats["decode"]["expert_pairs"])) / tiles
    return {"logits": dec["logits"], "ring": ring, "rows": rows,
            "gated_window": packed(pre["gated0"], dec["gated0"]),
            "gated_global": packed(pre["gated1"], dec["gated1"]),
            "scores": packed(pre["scores"], dec["scores"]),
            "weights": weights, "chosen": chosen, "pairs": pairs}


def reference_as_system(out: dict, last, ring_rows: int, faults=()
                        ) -> dict:
    """A reference's output under :func:`system_outputs`'s keys (what
    the controls and the float8 reading hand to :func:`errors` in the
    system's place): its last sliding layer's keys and values laid out
    as a ring of ``ring_rows`` slots (``faults``: at the wrong modulus),
    its global layer's as a cache that never wraps."""
    ring, _ = trinity_ref.cache_view(out["k"][-2], out["v"][-2], last,
                                     ring_rows, faults)
    rows, _ = trinity_ref.cache_view(out["k"][-1], out["v"][-1], last,
                                     np.shape(out["valid"])[1])
    return {"logits": out["logits"], "ring": ring, "rows": rows,
            "gated_window": out["gated"][-2],
            "gated_global": out["gated"][-1], "scores": out["scores"],
            "weights": out["weights"], "chosen": out["chosen"],
            "pairs": out["pairs"]}


def reference(m, params, buffers, sample: dict, faults=()) -> dict:
    """``trinity_ref.forward`` over the sample ONE RECORDING AT A TIME
    (at the published widths a recording's float32 activations are
    1.5 GB beside the program's 11 GB), on the host, merged: every
    array over the recordings, the pairs summed."""
    import jax

    rows = [jax.device_get(trinity_ref.forward(
        m, params, buffers, *(sample[k][i:i + 1] for k in (
            "features", "feat_lens", "labels", "label_lens")),
        m.lfm_seq_positions, faults, REF_Q_BLOCK))
        for i in range(sample["feat_lens"].shape[0])]

    def merge(*xs):
        return np.concatenate(xs, axis=0)

    out = {k: jax.tree.map(merge, *(r[k] for r in rows))
           for k in rows[0] if k != "pairs"}
    out["pairs"] = sum(r["pairs"] for r in rows)
    return out


def errors(got: dict, want: dict, last) -> dict:
    """Each compared quantity's root-mean-square difference over the
    reference's root mean square, and the share of chosen sets that
    differ. ``want``: ``trinity_ref.forward``'s output; ``got``:
    :func:`system_outputs`'s keys; ``last [rows]``: the last position
    each stream wrote."""
    rel = trinity_ref.rms_rel
    valid, steps = np.asarray(want["valid"]), np.asarray(want["steps"])
    same = valid & np.all(
        np.sort(np.asarray(got["chosen"][-1]), -1)
        == np.sort(np.asarray(want["chosen"][-1]), -1), axis=-1)
    out = {
        "logits": rel(got["logits"], want["logits"], steps),
        "scores": rel(got["scores"], want["scores"], valid),
        # no position with the reference's set: ``chosen_differ`` says so
        "weights": rel(got["weights"], want["weights"], same)
        if same.any() else 0.0,
        "pairs": rel(got["pairs"], want["pairs"]),
        "chosen_differ": trinity_ref.chosen_differ_share(
            got["chosen"], want["chosen"], valid),
    }
    # The last layer of each kind: its cache (a ring, or every row) and
    # its gated output. ``want["gated"]``: sliding first.
    layers = {"ring": ("window", -2), "rows": ("global", -1)}
    for key, (name, layer) in layers.items():
        view, held = trinity_ref.cache_view(
            want["k"][layer], want["v"][layer], last,
            got[key].shape[1])
        out[key] = rel(got[key], view, held)
        out["gated_" + name] = rel(got["gated_" + name],
                                   want["gated"][layer], valid)
    return out


# Steps at set-up in which the held experts' selection bias follows the
# family's balancing rule towards its target, an equal load, each step
# from one served call of EVERY batch of the pool (the window replays
# them in turn). A seeded router loads experts unevenly (a held expert's
# pairs lie 72-958 about a mean of 340 a call), and a stream's own
# prefix colours its choices, so the held 32 receive 10.8-14.8% of a
# layer's pairs by seed and batch, a decode step touches 24.6-28.6 held
# experts (2.07 for each per cent of the pairs, to 0.4%), each 56.6 MB
# of the step's reads, and the rate moved by 2.6% between seeds (my chip
# runs, PR 41, PERF.md section 6). The bias that training leaves evens
# that out, and it is what the window then runs with, system and
# reference alike.
BALANCE_STEPS = 4
# First step: d ln(load) / d bias is about 33 at these widths (a bias b
# moves a score near the top-4 threshold as b / 0.078 moves its logit,
# whose spread is 1.1); later steps take the slope the last one showed.
BALANCE_GAIN = 0.02


def balance_step(pairs, elsewhere, experts: int, last=None,
                 gain: float = BALANCE_GAIN):
    """One step of the balancing rule from a call's decode counters:
    ``pairs [layers, held]`` on each held expert and ``elsewhere
    [layers]``. Returns what to add to each held expert's bias, the
    state for the next step, and what the call read: the held experts'
    share of the pairs and the spread of their loads (standard
    deviation over mean, a layer, averaged)."""
    pairs = np.asarray(pairs, np.float64)
    total = pairs.sum(1) + np.asarray(elsewhere, np.float64)
    over = np.log(np.maximum(pairs, 1.0)
                  / (total[:, None] / experts))
    if last is not None:
        # What the last step did: d ln(load) / d bias, over every held
        # expert; the next aims at four fifths of what is left.
        slope = np.sum((over - last["over"]) * last["step"]) \
            / max(np.sum(last["step"] ** 2), 1e-30)
        gain = last["gain"] if slope <= 0 else float(
            np.clip(0.8 / slope, gain / 8, gain * 8))
    step = -gain * over
    read = {"held_share": float(pairs.sum() / total.sum()),
            "load_spread": float(np.mean(
                pairs.std(1) / np.maximum(pairs.mean(1), 1e-30))),
            "gain": gain}
    return step, {"over": over, "step": step, "gain": gain}, read


def balance_router(inferencer, batches, steps: int = BALANCE_STEPS) -> list:
    """``steps`` times: one served call of each of ``batches``, then the
    held experts' selection bias of every expert layer takes one
    :func:`balance_step` from the calls' summed counters. The bias is an
    argument of the two programs: nothing compiles again. Returns each
    step's reading."""
    from flax.core import unfreeze

    engine = inferencer.lm_greedy
    m = engine.cfg.model
    held = slice(m.expert_offset, m.expert_offset + m.experts_held)
    state, log = None, []
    for _ in range(steps):
        pairs = elsewhere = 0.0
        for batch in batches:
            inferencer.decode_batch(batch)
            part = engine.last_call["stats"]["decode"]
            pairs = pairs + np.asarray(part["expert_pairs"], np.float64)
            elsewhere = elsewhere + np.asarray(part["pairs_elsewhere"],
                                               np.float64)
        step, state, read = balance_step(pairs, elsewhere, m.lfm_experts,
                                         state)
        log.append(read)
        buffers = unfreeze(engine.buffers)
        for name, row in zip(engine.sparse, step):
            moe = buffers[name]["moe"]
            moe["expert_bias"] = moe["expert_bias"].at[held].add(
                row.astype(np.float32))
        engine.buffers = buffers
    return log


def rows_implied(a_lens, steps, window: int, layers: dict) -> dict:
    """Cache rows a call's decode steps attend to, per layer kind, from
    the lengths alone: step j of a stream with ``a`` prefix positions
    sees ``a + j + 1`` rows in a layer that sees all and ``min(a + j +
    1, window)`` in a sliding one."""
    full = sliding = 0
    for a, n in zip(np.asarray(a_lens, np.int64),
                    np.asarray(steps, np.int64)):
        reach = a + 1 + np.arange(n)
        full += int(reach.sum())
        sliding += int(np.minimum(reach, window).sum())
    return {"rows_attended_window": sliding * layers["sliding_attention"],
            "rows_attended_global": full * layers["full_attention"]}


def call_attends_what_lengths_imply(engine, stats: dict, valid_frames,
                                    max_tokens) -> bool:
    m = engine.cfg.model
    a_lens = -(-np.asarray(valid_frames) // m.frame_stack)
    want = rows_implied(a_lens, max_tokens, m.lfm_window,
                        {k: len(v) for k, v in engine.kinds.items()})
    passed = int(np.sum(a_lens + np.asarray(max_tokens) > m.lfm_window))
    return bool(all(stats[k] == v for k, v in want.items())
                and stats["ring_wraps"] == passed
                and stats["cache_rows_read"] == sum(want.values()))


class ReferenceCheck:
    """The comparison, system against reference, on the timed path.

    The seeded sample, tiled to the cell's batch with its labels as
    forced tokens, goes through ``engine.transcribe``: the compiled
    prefill program in the cell's sub-batches and the compiled decode
    loop, the very executables the window then times (a process's first call
    compiles them). From that one call: the logits every decode step of
    the watched streams emitted, the last sliding layer's ring and the
    global layer's cache, both layers' gated attention output, the last
    expert layer's router scores and combine weights, every expert
    layer's chosen sets, and the call's counters of pairs on each held
    expert, against the reference's full forward pass over the same 2
    packed sequences. Then one sliding and one global layer's attention
    in its two forms on a seeded input, at positions past the window.

    Built once a process: ``tools/trinity_ref_seeds.py`` reads many
    seeds through the same compiled programs."""

    def __init__(self, inferencer, cfg, ctx: harness.Context):
        import jax

        from deepspeech_tpu.models.lfm2 import both_forms

        self.engine, self.cfg, self.ctx = inferencer.lm_greedy, cfg, ctx
        m = cfg.model
        prefix = -(-cfg.data.bucket_frames[0] // m.frame_stack)
        at = np.asarray([p for p in FORMS_AT if p < prefix])
        if len(at) < 4:                  # a rehearsal's few positions
            at = np.arange(prefix)
        self.forms = {
            kind: jax.jit(lambda p, x, kind=kind, rows=rows: both_forms(
                m, kind, p, x, at, rows))
            for kind, rows in (("sliding_attention",
                                min(m.lfm_window, prefix)),
                               ("full_attention", prefix))}
        self.prefix = prefix

    def run(self, params=None) -> dict:
        import jax
        import jax.numpy as jnp

        cfg, ctx, engine = self.cfg, self.ctx, self.engine
        m = cfg.model
        params = engine.params if params is None else params
        sample = _sample(cfg, ctx)
        n = sample["feat_lens"].shape[0]
        rows = cfg.data.batch_size
        tiles, rest = divmod(rows, n)
        if rest or n != cfg.decode.lm_prefill_rows \
                or n != cfg.decode.lm_watch_rows:
            raise SystemExit(
                f"{n} sample rows must tile {rows} streams and be one "
                f"prefill sub-batch ({cfg.decode.lm_prefill_rows}) and "
                f"the watched rows ({cfg.decode.lm_watch_rows})")
        tiled = {k: np.tile(x, (tiles,) + (1,) * (x.ndim - 1))
                 for k, x in sample.items()}
        watched = np.arange(rows - n, rows, dtype=np.int32)
        out = engine.transcribe(
            tiled["features"], tiled["feat_lens"],
            max_tokens=tiled["label_lens"] + 1,
            forced=forced_tokens(tiled["labels"], tiled["label_lens"]),
            watch=watched)
        got = system_outputs(engine, sample, watched, tiles)
        # What the call gave out (0.7 GB of watched arrays) is on the
        # host now: not held through the reference's pass.
        engine.last_call = None
        want = reference(m, params, engine.buffers, sample)
        a_lens = -(-sample["feat_lens"] // m.frame_stack)
        errs = errors(got, want, a_lens + sample["label_lens"])
        del got, want

        x = jax.random.normal(
            jax.random.PRNGKey(ctx.seed % (2 ** 31)),
            (1, self.prefix, m.lfm_hidden), jnp.dtype(m.dtype))
        forms = []
        for kind, layers in engine.kinds.items():
            dec, seq = jax.device_get(self.forms[kind](
                params[f"layer{layers[-1]}"]["attn"], x))
            forms.append(trinity_ref.rms_rel(dec, seq))
        errs["forms"] = max(forms)

        tol, differ = dict(REF_TOL), REF_CHOSEN_DIFFER
        if ctx.rehearse:  # float32 on the CPU: only the order of sums
            tol, differ = {k: 2e-3 for k in tol}, 0.02
        checks = {f"ref_{k}_rms_rel": v for k, v in errs.items()
                  if k != "chosen_differ"}
        checks["ref_chosen_differ_share"] = errs["chosen_differ"]
        checks["ref_finite"] = bool(
            all(np.isfinite(v) for v in errs.values()))
        checks["ref_ok"] = within(errs, tol, differ)
        # The forced call decoded every stream's steps, dropped none and
        # attended to what its lengths imply in each kind of layer.
        stats = out["stats"]
        checks["ref_steps"] = stats["decode_steps"]
        checks["ref_saw_every_step"] = bool(
            np.array_equal(out["tokens"], tiled["label_lens"] + 1)
            and stats["decode_steps"] == int(sample["label_lens"].max()) + 1)
        checks["ref_dropped_none"] = stats["dropped_pairs"] == 0
        checks["ref_rows_attended"] = call_attends_what_lengths_imply(
            engine, stats, tiled["feat_lens"], tiled["label_lens"] + 1)
        return checks


def run(ctx: harness.Context) -> dict:
    import jax

    from deepspeech_tpu import obs
    from deepspeech_tpu.data import CharTokenizer
    from deepspeech_tpu.data.pipeline import device_prefetch
    from deepspeech_tpu.decode import lm_greedy
    from deepspeech_tpu.infer import Inferencer
    from deepspeech_tpu.models.lfm2 import seeded_variables

    phases = {"imports": time.perf_counter() - ctx.t_process}
    cfg = cell_config(ctx)
    m = cfg.model
    if not m.lfm_window or "sliding_attention" not in m.lfm_layer_types \
            or "full_attention" not in m.lfm_layer_types:
        raise SystemExit(f"preset {cfg.name!r} has no windowed layer "
                         f"beside a global one")
    frames, rows = cfg.data.bucket_frames[0], cfg.data.batch_size
    v = m.vocab_size
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
    # The program's tracer is on from here: ``trinity_setup_trace_lower_s``
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

        # The first of these calls compiles the two programs.
        t = time.perf_counter()
        balance = balance_router(inferencer, [put(b) for b in pool])
        phases["balance_router"] = time.perf_counter() - t

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

    # After the window: every Mosaic call of the two lowered programs
    # must be a NAMED kernel, and the expert layers' grouped products
    # among them as ``moe_gmm``: two a layer at least in the decode
    # loop's body; the prefill program's lowered text shares one call
    # between layers of equal shapes, so there the count is reported
    # and held to "some, all named" (PERF.md section 7). Lowering with
    # the very arrays the loop used compiles nothing again.
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
            np.arange(cfg.decode.lm_watch_rows, dtype=np.int32),
            np.bool_(True))}
    del feats
    counters = {"kernel_calls": {}, "tpu_custom_calls": {}}
    prefix = -(-frames // m.frame_stack)
    square = re.compile(rf"[<x]{prefix}x{prefix}x")
    for name, low in lowered.items():
        text = low.as_text()
        counters["kernel_calls"][name] = kernel_calls(text)
        counters["tpu_custom_calls"][name] = text.count("tpu_custom_call")
        if name == "prefill":
            # No array of the lowered prefill program has two dimensions
            # of all the prefix's positions: no [S, S] scores.
            counters["prefill_square_arrays"] = len(square.findall(text))
        if ctx.trace:
            ma = low.compile().memory_analysis()
            counters[f"{name}_argument_bytes"] = ma.argument_size_in_bytes
            counters[f"{name}_temp_bytes"] = ma.temp_size_in_bytes
    least = {"prefill": 2, "decode": 2 * len(engine.sparse)}
    if not ctx.rehearse:
        checks["programs_hold_named_kernels"] = all(
            "" not in counters["kernel_calls"][name]
            and sum(counters["kernel_calls"][name].values())
            == counters["tpu_custom_calls"][name]
            and counters["kernel_calls"][name].get("moe_gmm", 0)
            >= least[name] for name in lowered)
    if prefix > 512:                    # more than one block of queries
        checks["prefill_holds_no_square_scores"] = \
            counters["prefill_square_arrays"] == 0
    shapes = [jax.tree.leaves(layer)[0].shape for layer in cache]
    cache_bytes = lm_greedy.cache_bytes(cache)
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
    # The window and its ring did their work in every call: every
    # stream's prefix is longer than the window, each sliding layer's
    # cache is a ring of exactly the window and the global layer's
    # holds every row, and the steps attended to what the lengths imply.
    checks["every_prefix_past_window"] = bool(all(
        int(np.min(-(-c["valid_frames"] // m.frame_stack)))
        > m.lfm_window for c in calls))
    checks["ring_rows_are_the_window"] = bool(
        all(shapes[i][1] == m.lfm_window
            for i in engine.kinds["sliding_attention"])
        and all(shapes[i][1] == m.lfm_seq_positions
                for i in engine.kinds["full_attention"]))
    checks["rows_attended_as_lengths_imply"] = all(
        call_attends_what_lengths_imply(
            engine, c["stats"], c["valid_frames"], c["max_tokens"])
        for c in calls)
    checks["compiles_in_window"] = in_window["compiles"]
    ok = (checks["compiles_in_window"] == 0
          and all(v for v in checks.values() if isinstance(v, bool)))

    gauges = obs.registry().snapshot()["gauges"]
    counters.update({
        "setup": setup_compiles, "window": in_window,
        "rows_per_call": rows, "bucket_frames": frames,
        "num_features": cfg.features.num_features,
        "cache_rows": m.lfm_seq_positions,
        "ring_rows": min(m.lfm_window, m.lfm_seq_positions),
        "prefill_rows": cfg.decode.lm_prefill_rows,
        "cache_bytes": int(cache_bytes),
        "cache_bytes_window": int(gauges.get("lm_cache_bytes_window", 0)),
        "cache_bytes_global": int(gauges.get("lm_cache_bytes_global", 0)),
        "router_balance": balance,
        "calls": [{"completed_s": c["t"] - t_start,
                   "input_s": c["input_s"],
                   "valid_frames": c["valid_frames"].tolist(),
                   "max_tokens": c["max_tokens"].tolist(),
                   **c["stats"]} for c in calls]})
    return {
        "driver": "transcribe_long", "model": m,
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
