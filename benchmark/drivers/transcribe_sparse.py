"""Driver ``transcribe_sparse``: a decoder-only recogniser whose one
attention layer reads a SELECTION of its cache's blocks, beside
linear-attention layers that carry a float32 state, SERVED on
recordings of 15-20 minutes through the entry point users call, fed by
the benchmark's own pipeline.

``Inferencer(cfg, tokenizer, params, buffers).decode_batch(batch)``
with ``decode.mode="lm_greedy"`` (``deepspeech_tpu/decode/
lm_greedy.py``): prefill in sub-batches (the sparse layer's sequence
form under every query's own selection, ``gqa_attn_select_fwd``; the
linear layers' in chunks, ``ssd_chunk_scan``) into a cache of keys,
values and pooled keys for the sparse layer and a state alone for a
linear one; then one on-device greedy loop of over four thousand steps,
each of which ranks the cache's blocks through the pooled keys, fetches
the selected blocks ONLY (``gqa_attn_select_decode``) and updates every
live stream's states where they lie (``ssd_state_step``). Everything
around the call is ``transcribe_lm``'s, whose helpers this driver
imports as ``transcribe_long`` does: the batches of ``gen/batches.py``
through the program's ``device_prefetch``, each with ``max_tokens`` (the
end id is ignored), closed loop, nothing patched, no ``*_impl`` set, no
router to balance (the model has no experts). The record says
``"driver": "transcribe_sparse"``: the readers of the other drivers
skip it, and the ``sala_*`` readers take it.

Outside the window, every run compares the system with the plain
reference (``reference/minicpm_sala_ref.py``: the full forward pass
over whole packed sequences, selection by the equations with exact
scores, the recurrence position by position) at the configuration's
widths through the very executables the window times
(``ReferenceCheck``), and holds the call's counters to what the lengths
and the selection's rule imply, so that no later change reads the whole
cache, or lets the selection lapse, unseen. That call is the programs'
first (it compiles them) and stands for the warm-up call.
"""

from __future__ import annotations

import re
import time

import numpy as np

from benchmark import harness
from benchmark.costs import minicpm_sala as costs
from benchmark.drivers.train import SpanSink
from benchmark.drivers.train_rnnt import kernel_calls
from benchmark.drivers.transcribe_lm import (_sample, cell_config,
                                             forced_tokens)
from benchmark.gen import batches as gen_batches
from benchmark.reference import minicpm_sala_ref

# The sample (the mix's ``ref_rows``): 2 recordings drawn as the traffic
# draws them (valid 15-20 min in the 120,000-frame bucket, 3.6 labels a
# second). The SYSTEM sees it tiled to the cell's batch (16 times, 32
# streams), with the drawn labels as forced tokens: prefill in the cell's
# sub-batches of 2, then the cell's decode loop, up to 4,321 steps. The
# REFERENCE holds the ONE stream the call gives out
# (``decode.lm_watch_rows``: every step's logits of a stream are
# 4,321 x 73,448 x 4 B = 1.27 GB), the first of the sample, whole: up to
# 19,321 packed positions.
# Queries a block of the reference's sparse layer: [32 heads, 128, S]
# float32 scores are 0.32 GB beside the program.
REF_Q_BLOCK = 128

# System (bfloat16 weights, matmul operands, activations, cache rows and
# pooled keys, float32 accumulation, float32 scores, softmax, norms,
# decays and recurrent STATE) against the plain float32 reference's full
# forward pass, on the chip at the published widths, each as
# root-mean-square difference over the reference's root mean square.
# Each limit is TWICE the worst reading over five seeds on the chip,
# weights and sample from the seed (``tools/sala_ref_seeds.py`` on seeds
# 101, 3000000202 and 303 and the cell's own first two runs, seeds
# 2000000011 and 2000000013; my chip runs, PR 54, PERF.md section 6).
# Beside each: the range read, what the reference with float8 (e4m3)
# weights, the nearest precision below the configuration's, reads against
# the reference on the chip (seed 101: over EVERY limit, by 2.4-7.5 x),
# and what the reference with its linear layers' state carried in
# bfloat16 reads (seed 101: over ``state_prefill`` by 1.10 x and under
# the others: after 15,000 positions a bfloat16 state has lost 1.5% where
# the system's float32 state, fed by bfloat16 keys and values, has lost
# 0.7%). The readings barely move with the seed (2% at most but for
# ``gated_sparse_all`` and the two shares, which follow the seed's
# near-ties: 13-20%). The controls of
# ``benchmark/tests/test_minicpm_sala_ref_control.py`` put one fault each
# into the reference and must fail these limits.
REF_TOL = {
    # what decode step j of the watched stream emits, all its steps
    # (4,052 of seed 101's stream), after prefill + j steps through
    # rows, pooled keys and states, against the reference's logits at
    # that position
    "logits": 0.0222,         # 0.01102-0.01111; float8 0.1468; bf16 0.0072
    # the sparse layer's keys and values of the watched stream, rows
    # 0 .. a + u, and its pooled keys of the windows whole inside them
    # (prefill's and those the steps wrote)
    "keys": 0.0063,           # 0.003136-0.003231; float8 0.0470
    "values": 0.0063,         # 0.003129-0.003131; float8 0.0468
    "pooled": 0.0073,         # 0.003521-0.003626; float8 0.0472
    # the last linear layer's float32 state of the watched stream after
    # prefill (position a - 1: 11-15 k positions of bfloat16 keys and
    # values summed in float32) and after the last step
    "state_prefill": 0.0137,  # 0.006788-0.006829; float8 0.0667; bf16 0.01497
    "state_last": 0.0264,     # 0.01304-0.01322; float8 0.1813; bf16 0.0168
    # each mixer's gated output before W_o (the sparse layer's where
    # both of a query's chosen sets are the reference's, and
    # (``gated_sparse_all``) at every valid query: a near-tie that moves
    # one block of 97 moves little of the softmax; the last linear
    # layer's), prefix positions as prefill gave them, text positions
    # as the steps did
    "gated_sparse": 0.0111,       # 0.005508-0.005547; float8 0.0773
    "gated_sparse_all": 0.0447,   # 0.01966-0.02235; float8 0.1065
    "gated_linear": 0.0228,       # 0.01138-0.01142; float8 0.1312; bf16 0.0156
    # share of the reference's chosen BLOCKS that the system did not
    # choose, over the valid (query, key/value head): a tie at the
    # selection's edge moves one block of 97
    "blocks_differ": 0.0031,      # 0.00131-0.00156; float8 0.0173
}
# Share of valid (query, key/value head) of the watched stream whose
# chosen blocks differ from the reference's in ANY block (bf16 upstream
# flips near-ties at the 64th of some two hundred block scores that lie
# close together on seeded weights): 0.0962-0.1176 read; float8 0.595.
REF_CHOSEN_DIFFER = 0.235


def system_outputs(engine, sample: dict, watched, w: int) -> dict:
    """What the comparison reads, from the call the engine just made on
    the tiled sample, in the reference's layout: the prefix positions
    and the state after prefill from the prefill program's watched rows
    (the first tile's first ``w``), the text positions from the decode
    loop's (``watched``: the same recordings in the last tile); the
    sparse layer's cache and the last linear layer's state of the
    watched streams as the call left them."""
    import jax

    m = engine.cfg.model
    last = engine.last_call
    lo, hi = int(watched[0]), int(watched[-1]) + 1
    pre, dec, rows, state = jax.device_get(
        (last["prefill_watch"], last["decode_watch"],
         [c[lo:hi] for c in last["cache"][engine.selecting[-1]]],
         last["cache"][engine.linear[-1]][0][lo:hi]))
    a_lens = -(-sample["feat_lens"][:w] // m.frame_stack)
    s = m.lfm_seq_positions
    steps = sample["label_lens"][:w] + 1
    nkv = m.lfm_kv_heads

    def packed(before, after):
        """``[w, S, ...]``: the prefix positions from the prefill
        program, each stream's steps from the decode loop."""
        out = np.zeros((w, s) + before.shape[2:], np.float32)
        out[:, :before.shape[1]] = before[:w]
        for r in range(w):
            out[r, a_lens[r]:a_lens[r] + steps[r]] = after[r, :steps[r]]
        return out

    def blocks(x):                 # [w, Q, kv x NB] -> [w, kv, Q, NB]
        x = np.asarray(x)
        return np.moveaxis(x.reshape(x.shape[:2] + (nkv, -1)), 2, 1)

    # a prefill query sees the prefix's blocks, a step's the cache's:
    # laid side by side at the cache's count, kv-major as both came
    pre_sel, dec_sel = blocks(pre["selected"]), blocks(dec["selected"])
    nb = dec_sel.shape[-1]
    chosen = np.zeros((w, nkv, s, nb), bool)
    chosen[:, :, :pre_sel.shape[2], :pre_sel.shape[-1]] = pre_sel[:w]
    for r in range(w):
        chosen[r, :, a_lens[r]:a_lens[r] + steps[r]] = \
            dec_sel[r, :, :steps[r]]
    keys, values, pooled = rows
    # the cache is head-major [w, kv, R, hd]; the reference's rows are
    # [w, R, kv, hd]
    keys, values = np.swapaxes(keys, 1, 2), np.swapaxes(values, 1, 2)
    return {"logits": dec["logits"], "keys": keys, "values": values,
            "pooled": pooled, "state_prefill": pre["state"][:w],
            "state_last": state, "chosen": chosen,
            "gated_sparse": packed(pre["gated0"], dec["gated0"]),
            "gated_linear": packed(pre["gated1"], dec["gated1"])}


def reference_as_system(out: dict) -> dict:
    """A reference's output under :func:`system_outputs`'s keys (what
    the controls and the float8 reading hand to :func:`errors` in the
    system's place)."""
    return {"logits": out["logits"], "keys": out["k"], "values": out["v"],
            **{k: out[k] for k in (
                "pooled", "state_prefill", "state_last", "chosen",
                "gated_sparse", "gated_linear")}}


def errors(got: dict, want: dict, last, m) -> dict:
    """Each compared quantity's root-mean-square difference over the
    reference's root mean square, and the share of chosen sets that
    differ. ``want``: ``minicpm_sala_ref.forward``'s output; ``got``:
    :func:`system_outputs`'s keys; ``last [rows]``: the last position
    each stream wrote."""
    rel = minicpm_sala_ref.rms_rel
    valid, steps = np.asarray(want["valid"]), np.asarray(want["steps"])
    last = np.asarray(last)
    ref = reference_as_system(want)
    s = valid.shape[1]
    held = np.arange(s)[None, :] <= last[:, None]
    n = min(np.shape(ref["pooled"])[1], np.shape(got["pooled"])[1])
    whole = (np.arange(n) * m.sparse_stride + m.sparse_kernel - 1
             )[None, :] <= last[:, None]
    nb = min(np.shape(got["chosen"])[-1], np.shape(ref["chosen"])[-1])
    same = valid & np.all(
        np.asarray(got["chosen"])[..., :nb]
        == np.asarray(ref["chosen"])[..., :nb], axis=(1, 3))
    out = {k: rel(got[k], ref[k]) for k in ("state_prefill", "state_last")}
    out.update(
        logits=rel(got["logits"], ref["logits"], steps),
        keys=rel(np.asarray(got["keys"])[:, :s], ref["keys"], held),
        values=rel(np.asarray(got["values"])[:, :s], ref["values"], held),
        pooled=rel(np.asarray(got["pooled"])[:, :n],
                   np.asarray(ref["pooled"])[:, :n], whole),
        # outputs compared where the sets agree (the routers' pattern)
        gated_sparse=rel(got["gated_sparse"], ref["gated_sparse"], same)
        if same.any() else 0.0,
        gated_sparse_all=rel(got["gated_sparse"], ref["gated_sparse"],
                             valid),
        gated_linear=rel(got["gated_linear"], ref["gated_linear"], valid),
        blocks_differ=minicpm_sala_ref.blocks_differ_share(
            got["chosen"], ref["chosen"], valid),
        chosen_differ=minicpm_sala_ref.chosen_differ_share(
            got["chosen"], ref["chosen"], valid))
    return out


def within(errs: dict, tol: dict, chosen_differ: float) -> bool:
    return bool(all(errs[k] <= tol[k] for k in tol if k in errs)
                and errs["chosen_differ"] <= chosen_differ)


def counts_implied(m, valid_frames, max_tokens) -> dict:
    """What a call's decode counters must read, from the lengths and the
    selection's rule alone (``costs/minicpm_sala.py``): rows read and
    held and pooled keys ranked in the sparse layers, pooled keys
    written (a row that ends a window), states updated."""
    a_lens = -(-np.asarray(valid_frames) // m.frame_stack)
    sparse = costs.layers_of(m, costs.SPARSE)
    read = held = ranked = writes = 0
    for a, n in zip(a_lens, np.asarray(max_tokens)):
        pos = int(a) + np.arange(int(n))
        read += int(costs.rows_selected(m, pos).sum())
        held += int((pos + 1).sum())
        past = pos + 1 > m.sparse_dense_len
        ranked += int(costs.windows_ranked(m, pos)[past].sum())
        ends = (pos >= m.sparse_kernel - 1) \
            & ((pos + 1 - m.sparse_kernel) % m.sparse_stride == 0)
        writes += int(ends.sum())
    return {"select_rows_read": sparse * read,
            "select_rows_held": sparse * held,
            "select_windows_read": sparse * ranked,
            "pooled_key_writes": sparse * writes,
            "cache_rows_read": sparse * read,
            "state_updates": costs.layers_of(m, costs.LINEAR)
            * int(np.sum(max_tokens))}


def call_counts_what_lengths_imply(engine, stats: dict, valid_frames,
                                   max_tokens) -> bool:
    want = counts_implied(engine.cfg.model, valid_frames, max_tokens)
    return bool(all(stats[k] == v for k, v in want.items())
                and stats["select_rows_read"] < stats["select_rows_held"])


def reference(m, params, sample: dict, w: int, faults=()) -> dict:
    """``minicpm_sala_ref.forward`` over the first ``w`` sequences of
    the sample, on the host."""
    import jax

    return jax.device_get(minicpm_sala_ref.forward(
        m, params, *(sample[k][:w] for k in (
            "features", "feat_lens", "labels", "label_lens")),
        m.lfm_seq_positions, faults, REF_Q_BLOCK))


class ReferenceCheck:
    """The comparison, system against reference, on the timed path.

    The seeded sample, tiled to the cell's batch with its labels as
    forced tokens, goes through ``engine.transcribe``: the compiled
    prefill program in the cell's sub-batches and the compiled decode
    loop, the very executables the window then times (a process's first
    call compiles them). From that one call: the logits every decode
    step of the watched stream emitted; the sparse layer's rows and
    pooled keys; the last linear layer's float32 state after prefill
    and after the last step; each mixer's gated output; every query's
    chosen blocks. The cache is then RELEASED and the reference's full
    forward pass over the watched sequence runs in its place.

    Built once a process: ``tools/sala_ref_seeds.py`` reads many seeds
    through the same compiled programs."""

    def __init__(self, inferencer, cfg, ctx: harness.Context):
        self.engine, self.cfg, self.ctx = inferencer.lm_greedy, cfg, ctx

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
        t0 = time.perf_counter()
        out = engine.transcribe(
            tiled["features"], tiled["feat_lens"],
            max_tokens=tiled["label_lens"] + 1,
            forced=forced_tokens(tiled["labels"], tiled["label_lens"]),
            watch=watched)
        call_s = time.perf_counter() - t0
        stats = out["stats"]
        tokens = out["tokens"]
        del tiled["features"]
        got = system_outputs(engine, sample, watched, w)
        # What the call gave out is on the host now; the cache is not
        # held through the reference's pass (the next call makes it
        # again).
        engine.last_call = engine._cache = None
        del out
        want = reference(m, params, sample, w)
        a_lens = -(-sample["feat_lens"][:w] // m.frame_stack)
        errs = errors(got, want, a_lens + sample["label_lens"][:w], m)
        del got, want

        tol, differ = dict(REF_TOL), REF_CHOSEN_DIFFER
        if ctx.rehearse:  # float32 on the CPU: only the order of sums
            tol, differ = {k: 2e-3 for k in tol}, 0.02
        checks = {f"ref_{k}_rms_rel": v for k, v in errs.items()
                  if k != "chosen_differ"}
        checks["ref_chosen_differ_share"] = errs["chosen_differ"]
        checks["ref_finite"] = bool(
            all(np.isfinite(v) for v in errs.values()))
        checks["ref_ok"] = within(errs, tol, differ)
        # The forced call decoded every stream's steps and read, ranked,
        # wrote and updated what its lengths imply.
        checks["ref_steps"] = stats["decode_steps"]
        checks["ref_call_s"] = call_s
        checks["ref_saw_every_step"] = bool(
            np.array_equal(tokens, tiled["label_lens"] + 1)
            and stats["decode_steps"] == int(sample["label_lens"].max()) + 1)
        checks["ref_counts"] = call_counts_what_lengths_imply(
            engine, stats, tiled["feat_lens"], tiled["label_lens"] + 1)
        return checks


def route_checks(cfg) -> dict:
    """The recurrence and both forms of the attention must have
    resolved to the compiled kernels: a run on the oracles or on
    interpreted kernels looks the same from outside."""
    from deepspeech_tpu.models.lfm2 import attends_in_kernels
    from deepspeech_tpu.ops import ssd_pallas
    from deepspeech_tpu.utils.impl import interpret_default

    m = cfg.model
    return {"ssd_in_kernels": ssd_pallas.in_kernels(
        m.lin_head_dim, m.lin_head_dim),
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
    if costs.SPARSE not in m.lfm_layer_types \
            or costs.LINEAR not in m.lfm_layer_types:
        raise SystemExit(f"preset {cfg.name!r} has no layer under a block "
                         f"selection beside a linear-attention layer")
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
    # The program's tracer is on from here: ``setup_trace_lower_s`` is
    # to see the weights' initialisation and the reference check, where
    # this cell's two programs are traced, lowered and compiled.
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

        # This call compiles the two programs, and is the warm-up call.
        checks = {} if ctx.rehearse else route_checks(cfg)
        t = time.perf_counter()
        checks.update(ReferenceCheck(inferencer, cfg, ctx).run())
        phases["reference_check"] = time.perf_counter() - t

        t = time.perf_counter()
        batches = device_prefetch(cycle(), put_fn=put)
        for _ in range(int(ctx.param("warmup_calls", 0))):
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
    # must be a NAMED kernel, ``ssd_chunk_scan`` and the selection's
    # sequence form in the prefill program, ``ssd_state_step`` and the
    # selection's decode kernel in the decode program, each at least
    # once a layer (never an exact count: the lowered text may share a
    # call between layers of equal shapes), and no array of the lowered
    # prefill program has two dimensions of all the prefix's positions.
    # Lowering with the very arrays the loop used compiles nothing again.
    t = time.perf_counter()
    snap = ctx.compiles.snapshot()
    engine.last_call = None            # 1.3 GB of watched logits
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
            counters["prefill_square_arrays"] = len(square.findall(text))
        if ctx.trace:
            ma = low.compile().memory_analysis()
            counters[f"{name}_argument_bytes"] = ma.argument_size_in_bytes
            counters[f"{name}_temp_bytes"] = ma.temp_size_in_bytes
            counters[f"{name}_alias_bytes"] = ma.alias_size_in_bytes
    sparse, linear = len(engine.selecting), len(engine.linear)
    least = {"prefill": {"ssd_chunk_scan": 1, "gqa_attn_select_fwd": 1},
             "decode": {"ssd_state_step": linear,
                        "gqa_attn_select_decode": sparse}}
    if not ctx.rehearse:
        checks["programs_hold_named_kernels"] = all(
            harness.holds_named_kernels(
                counters["kernel_calls"][name],
                counters["tpu_custom_calls"][name], least[name])
            for name in lowered)
    if prefix > 512:                    # more than one block of queries
        checks["prefill_holds_no_square_scores"] = \
            counters["prefill_square_arrays"] == 0
    parts = {"rows": [cache[i][:2] for i in engine.selecting],
             "pooled": [cache[i][2] for i in engine.selecting],
             "state": [cache[i][0] for i in engine.linear]}
    cache_bytes = {k: int(lm_greedy.cache_bytes(x))
                   for k, x in parts.items()}
    checks["state_is_float32"] = all(
        str(cache[i][0].dtype) == "float32" for i in engine.linear)
    engine._cache = cache
    counters["after_window"] = ctx.compiles.since(snap)
    phases["hlo_checks_after_window"] = time.perf_counter() - t

    checks["every_stream_decoded"] = all(
        c["texts"] == rows and c["stats"]["decode"]["valid_positions"]
        == int(np.sum(c["max_tokens"])) for c in calls)
    # The selection did its work in every call: every stream's prefix is
    # past ``dense_len``, so prefill and every step select, and the
    # steps read, ranked, wrote and updated what the lengths imply
    # (never the whole cache).
    checks["every_prefix_past_dense_len"] = bool(all(
        int(np.min(-(-c["valid_frames"] // m.frame_stack)))
        > m.sparse_dense_len for c in calls))
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
        "cache_bytes_pooled": int(gauges.get("lm_cache_bytes_pooled", 0)),
        "cache_bytes_select": int(gauges.get("lm_cache_bytes_select", 0)),
        "calls": [{"completed_s": c["t"] - t_start,
                   "input_s": c["input_s"],
                   "valid_frames": c["valid_frames"].tolist(),
                   "max_tokens": c["max_tokens"].tolist(),
                   **c["stats"]} for c in calls]})
    return {
        "driver": "transcribe_sparse", "model": m,
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
