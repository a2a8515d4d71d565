"""Driver ``stream``: live voice sessions through one session manager.

Calls ``deepspeech_tpu.serving.session.StreamingSessionManager(cfg,
params, batch_stats, tokenizer, chunk_frames=C, decode="greedy",
capacity=N)`` with ``join``/``step``/``leave`` — what ``serve.py`` is
built on. Weights come from ``create_model(cfg.model).init`` in one
jitted call from ``--seed``: no checkpoint, no orbax.

Closed loop, one client: the pump hands a tick (its joins, leaves and
chunks, from ``gen/sessions.py``) to the manager as soon as the
previous tick's partial texts are back on the host. A tick's latency
runs from handing over its first join until ``step()`` has returned
every attached session's partial text. The engine is lockstep and a
stream's chunks are periodic, so below saturation a real-time tick
sees the service time measured here.

The window opens after ``warmup_ticks`` ticks (every program the churn
needs has run by then: a compile inside the window makes the run
incorrect) and closes with the last tick completed before the clock
ran out.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import harness
from benchmark.gen import sessions as gen_sessions
from benchmark.reference import ds2_ref

# Streamed logits against the plain float32 reference's full forward:
# same argument as in drivers/train.py (bfloat16 matmuls through eight
# layers), measured 0.3-0.5% (PERF.md, findings of PR 22).
REF_RMS_TOL = 0.015
REF_STREAM_FRAMES = 300   # four full chunks and a 44-frame tail
# A session streamed alone against its transcript from the busy
# window: rows are independent, so the texts should be equal; an
# argmax near a tie may flip with bf16, more than this is a wrong
# stream (the bound chip_smoke.py uses).
RESTREAM_CER_MAX = 0.1
RESTREAM_SESSIONS = 8


def edit_distance(a: str, b: str) -> int:
    prev = list(range(len(b) + 1))
    for i, ca in enumerate(a, 1):
        cur = [i]
        for j, cb in enumerate(b, 1):
            cur.append(min(prev[j] + 1, cur[j - 1] + 1,
                           prev[j - 1] + (ca != cb)))
        prev = cur
    return prev[-1]


def reference_check(mgr, cfg, ctx: harness.Context) -> dict:
    """One seeded stream, chunk by chunk through the manager's own
    transcriber (same compiled program: row 0 of a batch of
    ``capacity``), against the reference's forward of the whole
    utterance."""
    import jax
    import jax.numpy as jnp

    st, cap, c = mgr.st, mgr.capacity, mgr.chunk_frames
    f = cfg.features.num_features
    n = int(ctx.param("ref_stream_frames", REF_STREAM_FRAMES))
    rng = np.random.default_rng([ctx.seed, 2])
    feats = rng.standard_normal((n, f), dtype=np.float32)
    lens = np.zeros((cap,), np.int32)
    lens[0] = n
    state = st.init_state(cap)
    # Length 0 masks the other rows from the first frame; row 0 learns
    # its length at finish(), as a live stream does.
    state = state.replace(raw_len=jnp.asarray(
        np.where(np.arange(cap) == 0, 2 ** 30, 0), jnp.int32))
    outs, valids = [], []
    batch = np.zeros((cap, c, f), np.float32)
    for i in range(n // c):
        batch[0] = feats[i * c:(i + 1) * c]
        state, lo, va = st.process_chunk(state, batch)
        outs.append(np.asarray(lo[0]))
        valids.append(np.asarray(va[0]))
    tail = np.zeros((cap, n % c, f), np.float32)
    tail[0] = feats[(n // c) * c:]
    state, lo, va = st.finish(state, lens, tail=tail if n % c else None)
    outs.append(np.asarray(lo[0]))
    valids.append(np.asarray(va[0]))
    got = np.concatenate(outs)[np.concatenate(valids)]
    want, want_lens = jax.jit(lambda p, s, x, m: ds2_ref.forward(
        cfg.model, p, s, x, m))(st.params, st.batch_stats, feats[None],
                                np.asarray([n], np.int32))
    t_out = int(np.asarray(want_lens)[0])
    if got.shape[0] != t_out:
        return {"ref_rows": [int(got.shape[0]), t_out], "ref_ok": False}
    err = ds2_ref.relative_error(got[None], np.asarray(want)[:, :t_out],
                                 [t_out])
    return {"ref_rms_rel": err["rms_rel"], "ref_max_rel": err["max_rel"],
            "ref_ok": bool(err["rms_rel"] <= REF_RMS_TOL)}


class Pump:
    """Feeds the generator's ticks to the manager and keeps the
    spans, the samples and the finals."""

    def __init__(self, mgr, traffic, pool):
        self.mgr, self.traffic, self.pool = mgr, traffic, pool
        self.spans = []
        self.latencies_ms = []
        self.frames = []
        self.tick_end = []
        self.finals = {}
        self.missing_finals = 0
        self.chunks_fed = 0

    def tick(self) -> None:
        mgr, pool, c = self.mgr, self.pool, self.traffic.chunk
        t_gen = time.perf_counter()
        tk = self.traffic.next_tick()
        chunks = {sid: gen_sessions.chunk_of(pool, sid, k)
                  for sid, k in tk.feeds}
        tails = []
        for sid, n in tk.leaves:
            full = self.traffic.plans[sid][1] // c
            tails.append((sid, gen_sessions.chunk_of(pool, sid, full)[:n]
                          if n else None))
        t0 = time.perf_counter()
        for sid in tk.joins:
            mgr.join(sid)
        t1 = time.perf_counter()
        for sid, tail in tails:
            mgr.leave(sid, tail=tail)
        t2 = time.perf_counter()
        mgr.step(chunks)  # returns with the partial texts on the host
        t3 = time.perf_counter()
        for sid in tk.finals:
            try:
                self.finals[sid] = mgr.final(sid)
            except KeyError:
                self.missing_finals += 1
        self.spans += [("gen", t_gen, t0), ("join", t0, t1),
                       ("leave", t1, t2), ("step", t2, t3)]
        self.latencies_ms.append((t3 - t0) * 1e3)
        self.frames.append(tk.frames)
        self.tick_end.append(t3)
        self.chunks_fed += len(chunks) + len(tails)


def restream_check(mgr, pump: Pump, first_tick: int, ctx) -> dict:
    """After the window: empty the manager, then stream a few of the
    window's sessions again, each alone, through the same manager (same
    capacity, same program) and compare the transcripts."""
    c, pool = pump.traffic.chunk, pump.pool
    for slot in pump.traffic.slots:
        if slot.state == "live":
            mgr.leave(slot.sid)
    mgr.flush()
    done = sorted(sid for sid in pump.finals
                  if pump.traffic.plans[sid][0] >= first_tick)
    rng = np.random.default_rng([ctx.seed, 3])
    picks = [done[i] for i in sorted(rng.choice(
        len(done), size=min(RESTREAM_SESSIONS, len(done)),
        replace=False))] if done else []
    errs = chars = 0
    for sid in picks:
        n = pump.traffic.plans[sid][1]
        again = "again." + sid
        mgr.join(again)
        for k in range(n // c):
            mgr.step({again: gen_sessions.chunk_of(pool, sid, k)})
        mgr.leave(again, tail=gen_sessions.chunk_of(pool, sid, n // c)
                  [:n % c] if n % c else None)
        mgr.flush()
        errs += edit_distance(mgr.final(again), pump.finals[sid])
        chars += len(pump.finals[sid])
    cer = errs / chars if chars else float(errs > 0)
    return {"restream_sessions": len(picks), "restream_chars": chars,
            "restream_cer": cer,
            "restream_ok": bool(picks) and cer <= RESTREAM_CER_MAX}


def run(ctx: harness.Context) -> dict:
    import jax
    import jax.numpy as jnp

    from deepspeech_tpu.data import CharTokenizer
    from deepspeech_tpu.models import create_model
    from deepspeech_tpu.serving.session import StreamingSessionManager

    phases = {"imports": time.perf_counter() - ctx.t_process}
    cfg = harness.model_config(ctx)
    p = {k: ctx.param(k) for k in (
        "capacity", "chunk_frames", "len_median_frames", "len_sigma",
        "len_min_frames", "len_max_frames", "gap_mean_frames",
        "burst_enter_p", "burst_exit_p", "burst_step_frames",
        "burst_gap_div", "drain_frames", "pool_chunks")}
    f, c = cfg.features.num_features, int(p["chunk_frames"])

    t = time.perf_counter()
    model = create_model(cfg.model)
    variables = jax.jit(lambda key: model.init(
        key, jnp.zeros((1, 2 * c, f), jnp.float32),
        jnp.full((1,), 2 * c, jnp.int32), train=False))(
            jax.random.PRNGKey(ctx.seed))
    jax.block_until_ready(variables)
    phases["weights"] = time.perf_counter() - t

    t = time.perf_counter()
    mgr = StreamingSessionManager(
        cfg, variables["params"], variables.get("batch_stats", {}),
        CharTokenizer.english(), chunk_frames=c, decode="greedy",
        capacity=int(p["capacity"]))
    if mgr.capacity != int(p["capacity"]) \
            or mgr.lag_raw != int(p["drain_frames"]):
        raise SystemExit(
            f"traffic file says capacity {p['capacity']}, drain "
            f"{p['drain_frames']}; the manager has {mgr.capacity}, "
            f"{mgr.lag_raw}")
    checks = {} if ctx.rehearse else harness.kernel_route_checks(cfg)
    checks.update(reference_check(mgr, cfg, ctx))
    phases["manager_and_reference"] = time.perf_counter() - t

    pool = gen_sessions.chunk_pool(p, seed=ctx.seed, num_features=f)
    pump = Pump(mgr, gen_sessions.SessionTraffic(p, seed=ctx.seed), pool)
    t = time.perf_counter()
    warm = int(ctx.param("warmup_ticks", 24))
    for _ in range(warm):
        pump.tick()
    phases["warmup_ticks"] = time.perf_counter() - t
    memory = [harness.memory_now()]
    setup_compiles = ctx.compiles.since((0, 0.0, 0))

    ctx.start_trace()
    snap = ctx.compiles.snapshot()
    t_start = time.perf_counter()
    deadline = t_start + ctx.window_seconds()
    while time.perf_counter() < deadline:
        pump.tick()
    trace_path = ctx.stop_trace()
    in_window = ctx.compiles.since(snap)
    memory.append(harness.memory_now())
    ticks = len(pump.latencies_ms) - warm
    finals_in_window = sum(
        1 for sid in pump.finals
        if pump.traffic.plans[sid][0] >= warm)

    t = time.perf_counter()
    checks.update(restream_check(mgr, pump, warm, ctx))
    if not ctx.rehearse:
        # Private attribute, read only: the chunk program as jitted.
        text = mgr.st._chunk_jit.lower(
            mgr.st.params, mgr.st.batch_stats, mgr.state,
            jnp.zeros((mgr.capacity, c, f), jnp.float32)).as_text()
        checks["chunk_holds_kernels"] = "tpu_custom_call" in text
    phases["checks_after_window"] = time.perf_counter() - t
    memory.append(harness.memory_now())

    checks["compiles_in_window"] = in_window["compiles"]
    checks["capacity_grows"] = mgr.grows
    checks["missing_finals"] = pump.missing_finals
    ok = (checks["compiles_in_window"] == 0 and mgr.grows == 0
          and pump.missing_finals == 0
          and all(v for v in checks.values() if isinstance(v, bool)))
    return {
        "driver": "stream", "model": cfg.model,
        "correct": ok, "checks": checks,
        "attempted": pump.chunks_fed + len(pump.finals),
        "failed": pump.missing_finals,
        "t_window_start": t_start, "t_window_end": pump.tick_end[-1],
        "units": ticks,
        "audio_s": sum(pump.frames[warm:]) * 0.01,
        "latencies_ms": pump.latencies_ms[warm:],
        "spans": pump.spans,
        "gen_s": sum(b - a for n, a, b in pump.spans
                     if n == "gen" and a >= t_start),
        "counters": {
            "setup": setup_compiles, "window": in_window,
            "ticks": ticks, "finals_in_window": finals_in_window,
            "joins_per_tick": sum(
                1 for s in pump.traffic.plans.values() if s[0] >= warm)
            / max(ticks, 1),
            "mean_frames_per_tick": float(np.mean(pump.frames[warm:])),
            "slot_reuses": mgr.reuses},
        "setup_phases": phases, "memory_samples": memory,
        "trace_path": trace_path,
    }
