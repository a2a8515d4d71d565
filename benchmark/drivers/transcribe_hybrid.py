"""Driver ``transcribe_hybrid``: a decoder-only recogniser whose every
layer is a state-space mixer BESIDE grouped-query attention, SERVED
through the entry point users call, fed by the benchmark's own
pipeline.

``Inferencer(cfg, tokenizer, params, buffers).decode_batch(batch)``
with ``decode.mode="lm_greedy"`` (``deepspeech_tpu/decode/
lm_greedy.py``): prefill in sub-batches, the mixer's sequence form in
chunks (``ssd_chunk_scan``), into a cache of keys, values, a float32
recurrent state and the convolution's last inputs a layer; then one
on-device greedy loop whose every step updates every live stream's
state where it lies (``ssd_state_step``) and attends through
``gqa_attn_decode``. Everything around the call is ``transcribe_lm``'s,
whose helpers this driver imports: the batches of ``gen/batches.py``
through the program's ``device_prefetch``, each with ``max_tokens`` (the
end id is ignored), closed loop, nothing patched, no ``*_impl`` set.
The record says ``"driver": "transcribe_hybrid"``: the readers of the
other drivers skip it, and the ``falcon_*`` readers take it.

Outside the window, every run compares the system with the plain
reference (``reference/falcon_h1_ref.py``: the full forward pass over
whole packed sequences, the recurrence position by position) at the
configuration's widths through the very executables the window times
(``ReferenceCheck``), and holds the call's counters to what the lengths
imply.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import harness
from benchmark.drivers.train import SpanSink
from benchmark.drivers.train_rnnt import kernel_calls
from benchmark.drivers.transcribe_lm import (_sample, cell_config,
                                             forced_tokens)
from benchmark.gen import batches as gen_batches
from benchmark.reference import falcon_h1_ref

# The sample (the mix's ``ref_rows``): 8 utterances drawn as the traffic
# draws them (valid 12-16.5 s in the 1696-frame bucket, 3.6 labels a
# second). The SYSTEM
# sees it tiled to the cell's batch (16 times, 128 streams), with the
# drawn labels as forced tokens: prefill in the cell's sub-batches, then
# the cell's decode loop, 44-60 steps. The REFERENCE holds the streams
# the call gives out (``decode.lm_watch_rows``: the first 2 of the
# sample; every step's logits of a stream are 68 MB).
# Where one mixer's two forms part: the sequence form over this many
# positions, the decode form over the rest of the cache rows.
FORMS_SPLIT = 212

# System (bfloat16 weights, matmul operands, activations, cache rows and
# convolution inputs, float32 accumulation, float32 scores, softmax,
# norms, decays and recurrent STATE) against the plain float32
# reference's full forward pass, on the chip at the published widths,
# each as root-mean-square difference over the reference's root mean
# square. Each limit is TWICE the worst reading over eight seeds on the
# chip, weights and sample from the seed (``tools/falcon_ref_seeds.py``
# on seeds 101-103 and 201-203 and the cell's own first two runs; my
# chip runs, PR 49, PERF.md section 6), unless said otherwise. Beside
# each: the range read, and what the reference with float8 (e4m3)
# weights, the nearest precision below the configuration's, reads
# against the reference on the chip (seeds 101, 102): it is over EVERY
# limit it has a reading for, by 2.3-3.4 x. The readings barely move
# with the seed (3% at most): six layers of bfloat16 rounding at these
# widths are a property of the arithmetic, not of the draw. The controls
# of ``benchmark/tests/test_falcon_ref_control.py`` put one fault each
# into the reference and must fail these limits.
REF_TOL = {
    # what decode step j of a stream emits, all steps of the 2 watched
    # streams of the LAST tile, after prefill + j steps through the
    # cache, against the reference's logits at that position
    "logits": 0.019,          # 0.00914-0.00931; float8 0.0551-0.0553
    # the last layer's float32 state of the watched streams after
    # prefill (position a - 1, a ragged end) and after the last step
    "state_prefill": 0.016,   # 0.00759-0.00801; float8 0.0439-0.0440
    "state_last": 0.024,      # 0.01120-0.01191; float8 0.0676-0.0704
    # its convolution inputs after prefill (positions a - 3 .. a - 1)
    # and after the last step
    "conv_prefill": 0.0114,   # 0.00548-0.00568; float8 0.0329
    "conv_last": 0.0173,      # 0.00855-0.00864; float8 0.0526-0.0538
    # its keys (rotated, times the key multiplier 0.011) and its values,
    # rows 0 .. a + u, apart: together the keys would vanish beside the
    # values
    "keys": 0.0128,           # 0.00635-0.00637; float8 0.0382
    "values": 0.0128,         # 0.00632-0.00636; float8 0.0382-0.0384
    # the last layer's branch outputs apart (attention's is 0.0375 x and
    # its keys 0.011 x: in the sum one could hide in the other), prefix
    # positions as prefill gave them, text positions as the steps did
    "mixer": 0.0192,          # 0.00952-0.00958; float8 0.0534-0.0536
    "attn": 0.0131,           # 0.00628-0.00652; float8 0.0334-0.0338
    # ... and its MLP's output (0.011 x) at the text positions (in
    # prefill the last layer's MLP feeds nothing and is not computed)
    "mlp": 0.027,             # 0.01339-0.01347; float8 0.0781-0.0788
    # one mixer in its two forms on the same seeded input: the decode
    # form (76 steps from the state the sequence form left after 212
    # positions) against the sequence form over all 288, system against
    # system, both bfloat16 with a float32 state: the outputs at the 76
    # positions (the decode form reads its state in float32, the
    # sequence form's carry through a bfloat16 product) ...
    "forms_out": 0.0056,      # 0.00279-0.00280 (no reference in it)
    # ... and the final float32 state: 0.000013-0.000040 read. NOT twice
    # the worst: the reading is the order of float32 sums and moves 3 x
    # with the seed; the limit is ten times the worst and a tenth of what
    # a state rounded to bfloat16 EVERY STEP reads on the chip (0.0039,
    # 0.0052: seeds 101, 102, the same two forms with the decode form's
    # state carried in bfloat16), which must fail it and does
    "forms_state": 0.0004,
}


def system_outputs(engine, sample: dict, watched, w: int) -> dict:
    """What the comparison reads, from the call the engine just made on
    the tiled sample, in the reference's layout: the prefix positions
    and the state after prefill from the prefill program's watched rows
    (the first tile's first ``w``), the text positions from the decode
    loop's (``watched``: the same utterances in the last tile); the last
    layer's cache of the watched streams as the call left it."""
    import jax

    m = engine.cfg.model
    last = engine.last_call
    lo, hi = int(watched[0]), int(watched[-1]) + 1
    pre, dec, cache = jax.device_get(
        (last["prefill_watch"], last["decode_watch"],
         [c[lo:hi] for c in last["cache"][engine.stateful[-1]]]))
    a_lens = -(-sample["feat_lens"][:w] // m.frame_stack)
    s = m.lfm_seq_positions
    steps = sample["label_lens"][:w] + 1

    def packed(before, after):
        out = np.zeros((w, s) + before.shape[2:], np.float32)
        out[:, :before.shape[1]] = before[:w]
        for r in range(w):
            out[r, a_lens[r]:a_lens[r] + steps[r]] = after[r, :steps[r]]
        return out

    keys, values, state, conv = cache
    return {"logits": dec["logits"],
            # kept [state, head]: the transpose of the equations'
            "state_prefill": np.swapaxes(pre["state"][:w], -1, -2),
            "state_last": np.swapaxes(state, -1, -2),
            "conv_prefill": pre["conv"][:w], "conv_last": conv,
            "keys": keys, "values": values,
            "mixer": packed(pre["branch_mixer"], dec["branch_mixer"]),
            "attn": packed(pre["branch_attn"], dec["branch_attn"]),
            "mlp": dec["branch_mlp"]}


def reference_as_system(out: dict) -> dict:
    """A reference's output under :func:`system_outputs`'s keys (what
    the controls and the float8 reading hand to :func:`errors` in the
    system's place)."""
    return {"logits": out["logits"], "keys": out["k"], "values": out["v"],
            "mlp": np.take_along_axis(np.asarray(out["mlp"]),
                                  out["at"][..., None], 1),
        **{k: out[k] for k in (
            "state_prefill", "state_last", "conv_prefill", "conv_last",
            "mixer", "attn")}}


def errors(got: dict, want: dict, last) -> dict:
    """Each compared quantity's root-mean-square difference over the
    reference's root mean square. ``want``: ``falcon_h1_ref.forward``'s
    output; ``got``: :func:`system_outputs`'s keys; ``last [rows]``: the
    last position each stream wrote."""
    rel = falcon_h1_ref.rms_rel
    valid, steps = np.asarray(want["valid"]), np.asarray(want["steps"])
    held = np.arange(valid.shape[1])[None, :] <= np.asarray(last)[:, None]
    ref = reference_as_system(want)
    out = {k: rel(got[k], ref[k]) for k in (
        "state_prefill", "state_last", "conv_prefill", "conv_last")}
    out.update(logits=rel(got["logits"], ref["logits"], steps),
               keys=rel(got["keys"], ref["keys"], held),
               values=rel(got["values"], ref["values"], held),
               mixer=rel(got["mixer"], ref["mixer"], valid),
               attn=rel(got["attn"], ref["attn"], valid),
               mlp=rel(got["mlp"], ref["mlp"], steps))
    return out


def within(errs: dict, tol: dict) -> bool:
    return bool(all(errs[k] <= tol[k] for k in tol if k in errs))


def rows_implied(a_lens, steps) -> int:
    """Cache rows a call's decode steps attend to in ONE layer, from the
    lengths alone: step j of a stream with ``a`` prefix positions sees
    ``a + j + 1`` rows."""
    return int(sum(int(n) * int(a) + int(n) * (int(n) + 1) // 2
                   for a, n in zip(a_lens, steps)))


def call_counts_what_lengths_imply(engine, stats: dict, valid_frames,
                                   max_tokens) -> bool:
    """Every live stream's step updated one state a layer and attended
    to the rows before it and its own, in every layer."""
    m = engine.cfg.model
    layers = len(engine.stateful)
    a_lens = -(-np.asarray(valid_frames) // m.frame_stack)
    rows = layers * rows_implied(a_lens, max_tokens)
    return bool(stats["state_updates"] == layers * int(np.sum(max_tokens))
                and stats["rows_attended_global"] == rows
                and stats["cache_rows_read"] == rows)


class ReferenceCheck:
    """The comparison, system against reference, on the timed path.

    The seeded sample, tiled to the cell's batch with its labels as
    forced tokens, goes through ``engine.transcribe``: the compiled
    prefill program in the cell's sub-batches and the compiled decode
    loop, the very executables the window then times (a process's first
    call compiles them). From that one call: the logits every decode
    step of the watched streams emitted; the last layer's float32 state
    and convolution inputs after prefill and after the last step; its
    key/value rows; its two branch outputs apart. The cache is then
    RELEASED (3.7 GB) and the reference's full forward pass over the
    watched sequences runs in its place. Then one mixer's two forms on a
    seeded input.

    Built once a process: ``tools/falcon_ref_seeds.py`` reads many seeds
    through the same compiled programs."""

    def __init__(self, inferencer, cfg, ctx: harness.Context):
        import jax

        from deepspeech_tpu.models.lfm2 import mixer_both_forms

        self.engine, self.cfg, self.ctx = inferencer.lm_greedy, cfg, ctx
        m = cfg.model
        self.split = min(FORMS_SPLIT, m.lfm_seq_positions // 2)
        self.forms = jax.jit(
            lambda p, x, dtype: mixer_both_forms(m, p, x, self.split,
                                                 dtype),
            static_argnums=2)

    def forms_errors(self, params, dtype="float32") -> dict:
        """One mixer's two forms on a seeded input, the decode form's
        state carried in ``dtype``."""
        import jax
        import jax.numpy as jnp

        m = self.cfg.model
        x = jax.random.normal(
            jax.random.PRNGKey(self.ctx.seed % (2 ** 31)),
            (2, m.lfm_seq_positions, m.lfm_hidden), jnp.dtype(m.dtype))
        layer = f"layer{self.engine.stateful[-1]}"
        dec, seq, state, whole = jax.device_get(self.forms(
            params[layer]["mixer"], x, jnp.dtype(dtype)))
        return {"forms_out": falcon_h1_ref.rms_rel(dec, seq),
                "forms_state": falcon_h1_ref.rms_rel(state, whole)}

    def run(self, params=None) -> dict:
        cfg, ctx, engine = self.cfg, self.ctx, self.engine
        m = cfg.model
        params = engine.params if params is None else params
        sample = _sample(cfg, ctx)
        n = sample["feat_lens"].shape[0]
        rows, w = cfg.data.batch_size, cfg.decode.lm_watch_rows
        tiles, rest = divmod(rows, n)
        if rest or cfg.decode.lm_prefill_rows % n or w > n:
            raise SystemExit(
                f"{n} sample rows must tile {rows} streams and a prefill "
                f"sub-batch ({cfg.decode.lm_prefill_rows}) and hold the "
                f"watched rows ({w})")
        tiled = {k: np.tile(x, (tiles,) + (1,) * (x.ndim - 1))
                 for k, x in sample.items()}
        watched = np.arange(rows - n, rows - n + w, dtype=np.int32)
        out = engine.transcribe(
            tiled["features"], tiled["feat_lens"],
            max_tokens=tiled["label_lens"] + 1,
            forced=forced_tokens(tiled["labels"], tiled["label_lens"]),
            watch=watched)
        got = system_outputs(engine, sample, watched, w)
        # What the call gave out is on the host now; the cache is not
        # held through the reference's pass (the next call makes it
        # again).
        engine.last_call = engine._cache = None
        want = reference(m, params, sample, w)
        a_lens = -(-sample["feat_lens"][:w] // m.frame_stack)
        errs = errors(got, want, a_lens + sample["label_lens"][:w])
        del got, want
        errs.update(self.forms_errors(params))

        tol = dict(REF_TOL)
        if ctx.rehearse:  # float32 on the CPU: only the order of sums
            tol = {k: 2e-3 for k in tol}
        checks = {f"ref_{k}_rms_rel": v for k, v in errs.items()}
        checks["ref_finite"] = bool(
            all(np.isfinite(v) for v in errs.values()))
        checks["ref_ok"] = within(errs, tol)
        # The forced call decoded every stream's steps, updated a state
        # a live stream, step and layer, and attended to what its
        # lengths imply.
        stats = out["stats"]
        checks["ref_steps"] = stats["decode_steps"]
        checks["ref_saw_every_step"] = bool(
            np.array_equal(out["tokens"], tiled["label_lens"] + 1)
            and stats["decode_steps"] == int(sample["label_lens"].max()) + 1)
        checks["ref_counts"] = call_counts_what_lengths_imply(
            engine, stats, tiled["feat_lens"], tiled["label_lens"] + 1)
        return checks


def reference(m, params, sample: dict, w: int, faults=()) -> dict:
    """``falcon_h1_ref.forward`` over the first ``w`` sequences of the
    sample, on the host."""
    import jax

    return jax.device_get(falcon_h1_ref.forward(
        m, params, *(sample[k][:w] for k in (
            "features", "feat_lens", "labels", "label_lens")),
        m.lfm_seq_positions, faults))


def route_checks(cfg) -> dict:
    """The recurrence and the decode attention must have resolved to
    the compiled kernels: a run on the oracles or on interpreted
    kernels looks the same from outside."""
    from deepspeech_tpu.models.lfm2 import attends_in_kernels
    from deepspeech_tpu.ops import ssd_pallas
    from deepspeech_tpu.utils.impl import interpret_default

    m = cfg.model
    return {"ssd_in_kernels": ssd_pallas.in_kernels(
        m.ssm_d_ssm // m.ssm_heads, m.ssm_state),
        "attention_in_kernels": attends_in_kernels(m),
        "kernels_compiled": not interpret_default()}


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
    if "ssm_attention" not in m.lfm_layer_types:
        raise SystemExit(f"preset {cfg.name!r} has no layer with a "
                         f"recurrent state")
    frames, rows = cfg.data.bucket_frames[0], cfg.data.batch_size
    v = m.vocab_size
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
    # The program's tracer is on from here: ``falcon_setup_trace_lower_s``
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

        # This call compiles the two programs.
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
    # must be a NAMED kernel, ``ssd_chunk_scan`` in the prefill program
    # and ``ssd_state_step`` and ``gqa_attn_decode`` in the decode
    # program, each at least once a layer (never an exact count: the
    # lowered text may share a call between layers of equal shapes).
    # Lowering with the very arrays the loop used compiles nothing again.
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
    for name, low in lowered.items():
        text = low.as_text()
        counters["kernel_calls"][name] = kernel_calls(text)
        counters["tpu_custom_calls"][name] = text.count("tpu_custom_call")
        if ctx.trace:
            ma = low.compile().memory_analysis()
            counters[f"{name}_argument_bytes"] = ma.argument_size_in_bytes
            counters[f"{name}_temp_bytes"] = ma.temp_size_in_bytes
            counters[f"{name}_alias_bytes"] = ma.alias_size_in_bytes
    layers = len(engine.stateful)
    least = {"prefill": {"ssd_chunk_scan": 1},
             "decode": {"ssd_state_step": layers,
                        "gqa_attn_decode": layers}}
    if not ctx.rehearse:
        checks["programs_hold_named_kernels"] = all(
            "" not in counters["kernel_calls"][name]
            and sum(counters["kernel_calls"][name].values())
            == counters["tpu_custom_calls"][name]
            and all(counters["kernel_calls"][name].get(k, 0) >= n
                    for k, n in least[name].items())
            for name in lowered)
    parts = {"keys_values": [c[:2] for c in cache],
             "state": [c[2] for c in cache], "conv": [c[3] for c in cache]}
    cache_bytes = {k: int(lm_greedy.cache_bytes(x))
                   for k, x in parts.items()}
    checks["state_is_float32"] = all(
        str(c[2].dtype) == "float32" for c in cache)
    engine._cache = cache
    counters["after_window"] = ctx.compiles.since(snap)
    phases["hlo_checks_after_window"] = time.perf_counter() - t

    checks["every_stream_decoded"] = all(
        c["texts"] == rows and c["stats"]["decode"]["valid_positions"]
        == int(np.sum(c["max_tokens"])) for c in calls)
    # Every prefix ends inside the bucket (the state is taken at a
    # ragged end) and every live stream's step updated its states and
    # attended to what the lengths imply.
    prefix = -(-frames // m.frame_stack)
    checks["every_prefix_ragged"] = bool(all(
        int(np.max(-(-c["valid_frames"] // m.frame_stack))) < prefix
        for c in calls))
    checks["counts_as_lengths_imply"] = all(
        call_counts_what_lengths_imply(
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
        "prefill_rows": cfg.decode.lm_prefill_rows,
        "cache_bytes": sum(cache_bytes.values()),
        "cache_bytes_by_part": cache_bytes,
        "cache_bytes_state": int(gauges.get("lm_cache_bytes_state", 0)),
        "cache_bytes_conv": int(gauges.get("lm_cache_bytes_conv", 0)),
        "cache_bytes_global": int(gauges.get("lm_cache_bytes_global", 0)),
        "calls": [{"completed_s": c["t"] - t_start,
                   "input_s": c["input_s"],
                   "valid_frames": c["valid_frames"].tolist(),
                   "max_tokens": c["max_tokens"].tolist(),
                   **c["stats"]} for c in calls]})
    return {
        "driver": "transcribe_hybrid", "model": m,
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
