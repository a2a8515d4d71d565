"""On-chip proof + timing for the Pallas kernels and the beam decoder.

Every Pallas test runs interpret=True on CPU; this script runs the
real kernels (interpret=False) on the TPU chip, checks parity against
the XLA/jnp oracles at real shapes, and times kernel vs oracle so
preset defaults are chosen by measurement.

Run ON THE CHIP (through the chip tool; one process, which holds the
chip), naming the suites to run:

    python tools/chip_experiments.py ctc
    python tools/chip_experiments.py gru_resident
    python tools/chip_experiments.py gru_blocked
    python tools/chip_experiments.py beam

Appends one JSON line per experiment to tools/chip_results.jsonl
(on the chip machine that file is thrown away with the machine: read
the lines from the command's output). Every timing boundary is a
device->host scalar read, which is a sync on any backend.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

RESULTS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "chip_results.jsonl")
# Smoke-testing the script itself on CPU: CHIP_SMALL=1 shrinks shapes,
# CHIP_INTERPRET=1 runs Pallas in interpreter mode.
SMALL = os.environ.get("CHIP_SMALL") == "1"
INTERPRET = os.environ.get("CHIP_INTERPRET") == "1"


def _shrink(*dims):
    return tuple(max(d // 8, 4) for d in dims) if SMALL else dims


def log(rec: dict) -> None:
    # Every record self-describes its provenance so a CPU smoke run can
    # never masquerade as a TPU measurement in the results ledger.
    import jax

    rec = {"time": round(time.time(), 1),
           "backend": jax.default_backend(), **rec}
    if SMALL or INTERPRET:
        rec["smoke"] = {"small": SMALL, "interpret": INTERPRET}
    line = json.dumps(rec)
    print(line, flush=True)
    with open(RESULTS, "a") as f:
        f.write(line + "\n")


def sync(x) -> float:
    """Force completion via a host read; returns a checksum scalar."""
    import jax
    import jax.numpy as jnp

    leaves = [l for l in jax.tree.leaves(x) if hasattr(l, "dtype")]
    return float(sum(jnp.sum(l.astype(jnp.float32)) for l in leaves))


def timeit(fn, *args, iters: int = 5):
    """(seconds/iter, checksum). First call (compile) excluded."""
    out = fn(*args)
    sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    chk = sync(out)
    return (time.perf_counter() - t0) / iters, chk


# Per-dispatch overhead floors any single-call timing of a short op.
# CHIP_K_INNER=k (k>1) additionally times k applications of the op
# inside ONE jit (inputs perturbed per iteration so XLA cannot CSE
# them) and reports total/k — the dispatch floor amortizes away and the
# per-op time emerges.
K_INNER = int(os.environ.get("CHIP_K_INNER", "1"))


def ktime_ms(op, x) -> float:
    """ms per op application, k-amortized inside one jit. ``op`` may
    return any pytree (e.g. a grad tuple); leaves are checksum-summed
    so XLA cannot dead-code any output."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda v: sum(
        jnp.sum(l.astype(jnp.float32))
        for i in range(K_INNER)
        for l in jax.tree.leaves(op(v + i * 1e-6))))
    t, _ = timeit(f, x)
    return t / K_INNER * 1e3


# ---------------------------------------------------------------------------


def suite_ctc() -> None:
    import jax
    import jax.numpy as jnp

    from deepspeech_tpu.ops.ctc import ctc_loss as ctc_jnp
    from deepspeech_tpu.ops.ctc_pallas import ctc_loss_pallas

    for name, (b, t, v, lmax) in {
        "en_small": (*_shrink(16, 400), 29, _shrink(100)[0]),
        "aishell": (*_shrink(16, 400), _shrink(4336)[0], _shrink(40)[0]),
    }.items():
        rng = np.random.default_rng(0)
        logits = jnp.asarray(rng.normal(size=(b, t, v)), jnp.float32)
        label_lens = jnp.asarray(rng.integers(lmax // 2, lmax + 1, size=b),
                                 jnp.int32)
        labels = jnp.asarray(rng.integers(1, v, size=(b, lmax)), jnp.int32)
        labels = labels * (jnp.arange(lmax)[None] < label_lens[:, None])
        input_lens = jnp.full((b,), t, jnp.int32)

        def loss_sum(impl, lg):
            return jnp.sum(impl(lg, labels, input_lens, label_lens))

        f_p = jax.jit(lambda lg: loss_sum(
            functools.partial(ctc_loss_pallas, interpret=INTERPRET), lg))
        f_o = jax.jit(lambda lg: loss_sum(ctc_jnp, lg))
        g_p = jax.jit(jax.grad(lambda lg: loss_sum(
            functools.partial(ctc_loss_pallas, interpret=INTERPRET), lg)))
        g_o = jax.jit(jax.grad(lambda lg: loss_sum(ctc_jnp, lg)))

        lp, lo = float(f_p(logits)), float(f_o(logits))
        gp, go = np.asarray(g_p(logits)), np.asarray(g_o(logits))
        loss_ok = abs(lp - lo) / max(abs(lo), 1) < 1e-4
        grad_err = float(np.max(np.abs(gp - go)))
        t_p, _ = timeit(f_p, logits)
        t_o, _ = timeit(f_o, logits)
        tg_p, _ = timeit(g_p, logits)
        tg_o, _ = timeit(g_o, logits)
        log({"suite": "ctc", "case": name, "b": b, "t": t, "v": v,
             "loss_pallas": lp, "loss_jnp": lo, "loss_ok": loss_ok,
             "grad_max_abs_err": grad_err,
             "fwd_ms": {"pallas": t_p * 1e3, "jnp": t_o * 1e3},
             "grad_ms": {"pallas": tg_p * 1e3, "jnp": tg_o * 1e3}})


def _rnn_case(kind: str, h: int, b: int, t: int, dot_dtype):
    """Parity + timing of one fused Pallas RNN cell vs its XLA-scan
    oracle. ``kind`` is "gru" (3H gates) or "lstm" (4H gates; tapes the
    cell-state sequence — different VMEM/HBM profile, so the GRU
    numbers do not transfer, VERDICT r2 #5)."""
    import jax
    import jax.numpy as jnp

    from deepspeech_tpu.models.rnn import gru_scan, lstm_scan
    from deepspeech_tpu.ops.lstm_pallas import lstm_scan_pallas
    from deepspeech_tpu.ops.rnn_pallas import _dot_jnp_dtype, gru_scan_pallas

    scan = gru_scan if kind == "gru" else lstm_scan
    cell = gru_scan_pallas if kind == "gru" else lstm_scan_pallas
    g = 3 if kind == "gru" else 4

    rng = np.random.default_rng(1)
    xproj = jnp.asarray(rng.normal(size=(b, t, g * h)), jnp.float32)
    w_h = jnp.asarray(rng.normal(size=(h, g * h)) / np.sqrt(h), jnp.float32)
    b_h = jnp.asarray(rng.normal(size=(g * h,)) * 0.1, jnp.float32)
    lens = rng.integers(t // 2, t + 1, size=b)
    mask = jnp.asarray(np.arange(t)[None] < lens[:, None], jnp.float32)

    dd_str = dot_dtype  # validated by _dot_jnp_dtype below
    dd_jnp = None if dot_dtype is None else _dot_jnp_dtype(dot_dtype)

    f_p = jax.jit(lambda xp: cell(xp, mask, w_h, b_h, False,
                                  INTERPRET, dd_str))
    f_o = jax.jit(lambda xp: scan(xp, mask, w_h, b_h, dot_dtype=dd_jnp))
    g_p = jax.jit(jax.grad(lambda xp, wh: jnp.sum(
        cell(xp, mask, wh, b_h, False, INTERPRET, dd_str) ** 2),
        argnums=(0, 1)))
    g_o = jax.jit(jax.grad(lambda xp, wh: jnp.sum(
        scan(xp, mask, wh, b_h, dot_dtype=dd_jnp) ** 2),
        argnums=(0, 1)))

    yp, yo = np.asarray(f_p(xproj)), np.asarray(f_o(xproj))
    fwd_err = (float(np.max(np.abs(yp - yo)))
               / max(1.0, float(np.abs(yo).max())))
    gp = g_p(xproj, w_h)
    go = g_o(xproj, w_h)

    def rel_errs(pair, ref):
        return [float(np.max(np.abs(np.asarray(a) - np.asarray(b_))))
                / max(1.0, float(np.abs(np.asarray(b_)).max()))
                for a, b_ in zip(pair, ref)]

    gerrs = rel_errs(gp, go)
    # At reduced-precision dots, kernel-vs-oracle distance conflates two
    # noise sources (the r2 bf16 rows' grad_rel_errs[1]~0.15 turned out
    # to be ORACLE noise — see test_pallas.py bf16 dW diagnosis).
    # Record each impl's distance from the f32-truth grads so the chip
    # rows say who is off.
    gerrs_truth = None
    if dd_str is not None:
        gt = jax.jit(jax.grad(lambda xp, wh: jnp.sum(
            scan(xp, mask, wh, b_h, dot_dtype=None) ** 2),
            argnums=(0, 1)))(xproj, w_h)
        gerrs_truth = {"pallas": rel_errs(gp, gt), "xla": rel_errs(go, gt)}
    t_p, _ = timeit(f_p, xproj)
    t_o, _ = timeit(f_o, xproj)
    tg_p, _ = timeit(lambda xp: g_p(xp, w_h), xproj)
    tg_o, _ = timeit(lambda xp: g_o(xp, w_h), xproj)
    rec = {"suite": f"{kind}_h{h}", "b": b, "t": t,
           "dot_dtype": dd_str or "float32",
           "fwd_rel_err": fwd_err, "grad_rel_errs": gerrs,
           "fwd_ms": {"pallas": t_p * 1e3, "xla": t_o * 1e3},
           "grad_ms": {"pallas": tg_p * 1e3, "xla": tg_o * 1e3}}
    if gerrs_truth is not None:
        rec["grad_rel_errs_vs_f32_truth"] = gerrs_truth
    if K_INNER > 1:
        rec["fwd_ms_amortized"] = {
            "k": K_INNER,
            "pallas": ktime_ms(lambda xp: cell(
                xp, mask, w_h, b_h, False, INTERPRET, dd_str), xproj),
            "xla": ktime_ms(lambda xp: scan(
                xp, mask, w_h, b_h, dot_dtype=dd_jnp), xproj)}
        grad_of = lambda fn: jax.grad(
            lambda xp, wh: jnp.sum(fn(xp, wh) ** 2), argnums=(0, 1))
        rec["grad_ms_amortized"] = {
            "k": K_INNER,
            "pallas": ktime_ms(lambda xp: grad_of(
                lambda x2, wh: cell(x2, mask, wh, b_h, False, INTERPRET,
                                    dd_str))(xp, w_h), xproj),
            "xla": ktime_ms(lambda xp: grad_of(
                lambda x2, wh: scan(x2, mask, wh, b_h,
                                    dot_dtype=dd_jnp))(xp, w_h), xproj)}
    log(rec)


def suite_gru_resident() -> None:
    h, b, t = (_shrink(800)[0], 4, 16) if SMALL else (800, 16, 400)
    _rnn_case("gru", h=h, b=b, t=t, dot_dtype=None)
    _rnn_case("gru", h=h, b=b, t=t, dot_dtype="bfloat16")
    _bigru_case(h=h, b=b, t=t, dot_dtype="bfloat16")
    _rnn_q_case(h=h, b=b, t=t, dot_dtype="bfloat16")


def _rnn_q_case(h: int, b: int, t: int, dot_dtype, kind: str = "gru"):
    """Weight-only int8 resident kernel (VERDICT r3 #7) vs the
    full-precision Pallas kernel at the same H (resident or
    blocked-streaming, whatever models/rnn would route) vs the XLA
    scan on dequantized weights. At the flagship H=1760 this is the
    serving headline: int8 keeps the weights VMEM-resident where bf16
    must stream 18.6 MB per step. ``kind``: gru (3H) or lstm (4H)."""
    import jax
    import jax.numpy as jnp

    from deepspeech_tpu.models.rnn import gru_scan, lstm_scan
    from deepspeech_tpu.ops.lstm_pallas import (lstm_scan_pallas,
                                                lstm_scan_pallas_q)
    from deepspeech_tpu.ops.rnn_pallas import (_dot_jnp_dtype,
                                               gru_scan_pallas,
                                               gru_scan_pallas_q)

    scan = gru_scan if kind == "gru" else lstm_scan
    cell_fp = gru_scan_pallas if kind == "gru" else lstm_scan_pallas
    cell_q = gru_scan_pallas_q if kind == "gru" else lstm_scan_pallas_q
    g = 3 if kind == "gru" else 4
    rng = np.random.default_rng(5)
    xproj = jnp.asarray(rng.normal(size=(b, t, g * h)), jnp.float32)
    w_h = np.asarray(rng.normal(size=(h, g * h)) / np.sqrt(h), np.float32)
    b_h = jnp.asarray(rng.normal(size=(g * h,)) * 0.1, jnp.float32)
    mask = jnp.ones((b, t), jnp.float32)
    scale = np.abs(w_h).max(axis=0) / 127.0
    scale = np.where(scale == 0, 1.0, scale).astype(np.float32)
    q = jnp.asarray(np.clip(np.rint(w_h / scale), -127, 127), np.int8)
    scale = jnp.asarray(scale)
    w_deq = jnp.asarray(q, jnp.float32) * scale
    dd_jnp = None if dot_dtype is None else _dot_jnp_dtype(dot_dtype)

    fns = {
        "int8_resident": lambda xp: cell_q(
            xp, mask, q, scale, b_h, False, INTERPRET, dot_dtype),
        "pallas_fp": lambda xp: cell_fp(
            xp, mask, w_deq, b_h, False, INTERPRET, dot_dtype),
        "xla_dequant": lambda xp: scan(xp, mask, w_deq, b_h,
                                       dot_dtype=dd_jnp),
    }
    rec = {"suite": f"{kind}_q_h{h}", "b": b, "t": t,
           "dot_dtype": dot_dtype or "float32", "fwd_ms": {}}
    ys = {}
    for name, fn in fns.items():
        f = jax.jit(fn)
        ys[name] = np.asarray(f(xproj))
        t_f, _ = timeit(f, xproj)
        rec["fwd_ms"][name] = t_f * 1e3
        if K_INNER > 1:
            rec.setdefault("fwd_ms_amortized",
                           {"k": K_INNER})[name] = ktime_ms(fn, xproj)
    rec["fwd_rel_err_vs_dequant"] = float(
        np.max(np.abs(ys["int8_resident"] - ys["xla_dequant"]))
        / max(1.0, float(np.abs(ys["xla_dequant"]).max())))
    log(rec)


def _bigru_case(h: int, b: int, t: int, dot_dtype):
    """Fused-bidirectional resident kernel (r3) vs two serialized
    single-direction kernels vs the XLA two-scan sum: does interleaving
    the two independent recurrences hide each step's matmul/VPU
    latency? Decides whether models/rnn.py keeps routing resident
    bidir GRU through bigru_scan_pallas."""
    import jax
    import jax.numpy as jnp

    from deepspeech_tpu.models.rnn import gru_scan
    from deepspeech_tpu.ops.rnn_pallas import (_dot_jnp_dtype,
                                               bigru_scan_pallas,
                                               gru_scan_pallas)

    rng = np.random.default_rng(4)
    xproj = jnp.asarray(rng.normal(size=(b, t, 3 * h)), jnp.float32)
    w_f = jnp.asarray(rng.normal(size=(h, 3 * h)) / np.sqrt(h), jnp.float32)
    w_b = jnp.asarray(rng.normal(size=(h, 3 * h)) / np.sqrt(h), jnp.float32)
    b_f = jnp.asarray(rng.normal(size=(3 * h,)) * 0.1, jnp.float32)
    b_b = jnp.asarray(rng.normal(size=(3 * h,)) * 0.1, jnp.float32)
    mask = jnp.ones((b, t), jnp.float32)
    dd_jnp = None if dot_dtype is None else _dot_jnp_dtype(dot_dtype)

    fns = {
        "fused": lambda xp: bigru_scan_pallas(
            xp, mask, w_f, b_f, w_b, b_b, INTERPRET, dot_dtype),
        "two_kernels": lambda xp: (
            gru_scan_pallas(xp, mask, w_f, b_f, False, INTERPRET,
                            dot_dtype)
            + gru_scan_pallas(xp, mask, w_b, b_b, True, INTERPRET,
                              dot_dtype)),
        "xla": lambda xp: (
            gru_scan(xp, mask, w_f, b_f, dot_dtype=dd_jnp)
            + gru_scan(xp, mask, w_b, b_b, reverse=True,
                       dot_dtype=dd_jnp)),
    }
    rec = {"suite": f"bigru_h{h}", "b": b, "t": t,
           "dot_dtype": dot_dtype or "float32", "fwd_ms": {},
           "grad_ms": {}}
    ys = {}
    for name, fn in fns.items():
        f = jax.jit(fn)
        g = jax.jit(jax.grad(lambda xp: jnp.sum(fn(xp) ** 2)))
        ys[name] = np.asarray(f(xproj))
        t_f, _ = timeit(f, xproj)
        t_g, _ = timeit(g, xproj)
        rec["fwd_ms"][name] = t_f * 1e3
        rec["grad_ms"][name] = t_g * 1e3
        if K_INNER > 1:
            rec.setdefault("fwd_ms_amortized",
                           {"k": K_INNER})[name] = ktime_ms(fn, xproj)
    rec["fwd_rel_err"] = float(
        np.max(np.abs(ys["fused"] - ys["xla"]))
        / max(1.0, float(np.abs(ys["xla"]).max())))
    log(rec)


def suite_gru_blocked() -> None:
    h, b, t = (176, 4, 16) if SMALL else (1760, 16, 400)
    from deepspeech_tpu.ops import rnn_pallas

    budget = rnn_pallas._VMEM_WEIGHT_BUDGET
    if SMALL:  # force the blocked path at the shrunken size
        rnn_pallas._VMEM_WEIGHT_BUDGET = 0
    try:
        _rnn_case("gru", h=h, b=b, t=t, dot_dtype="bfloat16")
    finally:  # later suites (q-cases) need the real residency budget
        rnn_pallas._VMEM_WEIGHT_BUDGET = budget
    if not SMALL:
        # Flagship serving comparison: int8-RESIDENT (9.3 MB, fits)
        # vs the bf16 BLOCKED stream (18.6 MB/step) at H=1760.
        _rnn_q_case(h=h, b=b, t=t, dot_dtype="bfloat16")


def suite_lstm_resident() -> None:
    # 4H gates: H=800 f32 is 10.2 MB — just over the residency budget —
    # so the resident case pins bf16 (5.1 MB) plus a smaller f32 case.
    h, b, t = (_shrink(800)[0], 4, 16) if SMALL else (800, 16, 400)
    _rnn_case("lstm", h=512 if not SMALL else h, b=b, t=t, dot_dtype=None)
    _rnn_case("lstm", h=h, b=b, t=t, dot_dtype="bfloat16")
    _rnn_q_case(h=h, b=b, t=t, dot_dtype="bfloat16", kind="lstm")


def suite_lstm_blocked() -> None:
    h, b, t = (176, 4, 16) if SMALL else (1760, 16, 400)
    from deepspeech_tpu.ops import rnn_pallas

    budget = rnn_pallas._VMEM_WEIGHT_BUDGET
    if SMALL:
        rnn_pallas._VMEM_WEIGHT_BUDGET = 0
    try:
        _rnn_case("lstm", h=h, b=b, t=t, dot_dtype="bfloat16")
    finally:
        rnn_pallas._VMEM_WEIGHT_BUDGET = budget
    if not SMALL:
        # int8 4H at H=1760 is 12.4 MB — beyond even the 1-byte
        # residency budget, so the LSTM flagship q-case pins the
        # largest resident size instead (H=1536 int8 = 9.4 MB).
        _rnn_q_case(h=1536, b=b, t=t, dot_dtype="bfloat16", kind="lstm")


def suite_beam() -> None:
    import jax
    import jax.numpy as jnp

    from deepspeech_tpu.decode.beam import beam_search

    b, t, v, w = (2, 50, 542, 16) if SMALL else (8, 400, 4336, 128)
    rng = np.random.default_rng(2)
    lp = jax.nn.log_softmax(
        jnp.asarray(rng.normal(size=(b, t, v)) * 2, jnp.float32), axis=-1)
    lens = jnp.full((b,), t, jnp.int32)

    # Both merge strategies per prune level: 'sort' is the r2 design
    # (argsort + segment scatters per frame), 'match' the r3 rewrite
    # (VERDICT r2 #7) — the rows decide what 'auto' means on TPU.
    for k in (20, 40, 80):
        for impl in ("match", "sort"):
            f = jax.jit(functools.partial(beam_search, beam_width=w,
                                          prune_top_k=k, max_len=64,
                                          merge_impl=impl))
            t0 = time.perf_counter()
            out = f(lp, lens)
            sync(out)
            compile_s = time.perf_counter() - t0
            t_run, _ = timeit(f, lp, lens, iters=3)
            log({"suite": "beam_aishell", "b": b, "t": t, "v": v, "w": w,
                 "prune_top_k": k, "merge_impl": impl,
                 "compile_s": compile_s,
                 "decode_ms_per_batch": t_run * 1e3,
                 "utt_per_sec": b / t_run})
            # Where do the milliseconds go (VERDICT r2 #7): one trace
            # per impl at the headline prune level.
            prof = os.environ.get("CHIP_PROFILE_DIR")
            if prof and k == 20:
                try:
                    jax.profiler.start_trace(f"{prof}/beam_{impl}")
                    try:
                        sync(f(lp, lens))
                    finally:
                        jax.profiler.stop_trace()
                except Exception as e:
                    log({"suite": "beam_aishell", "case": "trace",
                         "merge_impl": impl,
                         "error": f"{type(e).__name__}: {e}"})

    # Recompile-storm check: second bucket shape must compile once and
    # reuse thereafter.
    f = jax.jit(functools.partial(beam_search, beam_width=w,
                                  prune_top_k=40, max_len=64))
    lp2 = lp[:, :200]
    lens2 = jnp.full((b,), 200, jnp.int32)
    t0 = time.perf_counter()
    sync(f(lp2, lens2))
    second_shape_s = time.perf_counter() - t0
    t_run2, _ = timeit(f, lp2, lens2, iters=3)
    log({"suite": "beam_aishell", "case": "second_bucket",
         "compile_s": second_shape_s, "decode_ms_per_batch": t_run2 * 1e3})


def suite_beam_lm() -> None:
    """On-device LM fusion cost: fused beam vs the plain beam numbers.

    Correctness of the fusion (table == scorer, device == host oracle)
    is CPU-tested in tests/test_beam.py; here the question is purely
    what the per-step [W, P] gather into a [V^k, V] HBM table costs at
    AISHELL scale (bigram, 4336^2 table ~75 MB) and at EN trigram scale
    (tiny table). Random tables time identically to real ones.
    """
    import jax
    import jax.numpy as jnp

    from deepspeech_tpu.decode.beam import beam_search

    rng = np.random.default_rng(3)
    cases = [("aishell_bigram", 2 if SMALL else 8, 50 if SMALL else 400,
              542 if SMALL else 4336, 16 if SMALL else 128, 1),
             ("en_trigram", 2 if SMALL else 16, 50 if SMALL else 400,
              29, 16 if SMALL else 64, 2)]
    for name, b, t, v, w, k1 in cases:
        lp = jax.nn.log_softmax(
            jnp.asarray(rng.normal(size=(b, t, v)) * 2, jnp.float32),
            axis=-1)
        lens = jnp.full((b,), t, jnp.int32)
        table = jnp.asarray(
            rng.normal(size=(v ** k1, v)).astype(np.float32) * 0.5 - 1.0)
        k = 20 if name == "aishell_bigram" else v - 1
        f = jax.jit(functools.partial(beam_search, beam_width=w,
                                      prune_top_k=k, max_len=64))
        fused = functools.partial(f, lm_table=table)
        t0 = time.perf_counter()
        sync(fused(lp, lens))
        compile_s = time.perf_counter() - t0
        t_run, _ = timeit(fused, lp, lens, iters=3)
        # The no-LM baseline under the identical jit wrapper.
        t_plain, _ = timeit(f, lp, lens, iters=3)
        log({"suite": "beam_lm", "case": name, "b": b, "t": t,
             "v": v, "w": w, "prune_top_k": k, "lm_ctx": k1,
             "table_mb": round(table.size * 4 / 2 ** 20, 1),
             "compile_s": compile_s,
             "decode_ms_fused": t_run * 1e3,
             "decode_ms_plain": t_plain * 1e3,
             "fusion_overhead_pct": round(
                 100 * (t_run - t_plain) / max(t_plain, 1e-9), 1)})

    # Hashed-table fusion (r3): TRIGRAM context at AISHELL scale — a
    # capability the dense layout cannot hold (~326 GB). Cost model is
    # different: (k+1)*PROBES keyed gathers per step instead of one
    # dense row gather; this row prices that trade on real HBM.
    from deepspeech_tpu.decode.hashed_lm import hashed_fusion_table
    from deepspeech_tpu.decode.ngram import NGramLM

    b, t, v, w = (2, 50, 542, 16) if SMALL else (8, 400, 4336, 128)
    n_grams = 2_000 if SMALL else 30_000
    chars = [chr(0x4e00 + i) for i in range(v - 1)]
    ngrams = {1: {("<s>",): (-99.0, -0.4), ("</s>",): (-1.5, 0.0),
                  ("<unk>",): (-2.5, -0.3)}, 2: {}, 3: {}}
    for ch in chars[: v // 2]:
        ngrams[1][(ch,)] = (float(rng.uniform(-4, -1)),
                            float(rng.uniform(-0.6, 0.0)))
    v1 = [wd for (wd,) in ngrams[1] if wd not in ("<s>", "</s>")]
    for n, cnt in ((2, n_grams), (3, n_grams)):
        for _ in range(cnt):
            gram = tuple(v1[int(rng.integers(len(v1)))] for _ in range(n))
            ngrams[n][gram] = (float(rng.uniform(-3, -0.3)),
                              float(rng.uniform(-0.5, 0.0)) if n < 3 else 0.0)
    htable = hashed_fusion_table(NGramLM(ngrams, 3),
                                 lambda i: chars[int(i) - 1], v, 0.8, 0.5)
    lp = jax.nn.log_softmax(
        jnp.asarray(rng.normal(size=(b, t, v)) * 2, jnp.float32), axis=-1)
    lens = jnp.full((b,), t, jnp.int32)
    f = jax.jit(functools.partial(beam_search, beam_width=w,
                                  prune_top_k=20, max_len=64))
    fused = functools.partial(f, lm_table=htable)
    t0 = time.perf_counter()
    sync(fused(lp, lens))
    compile_s = time.perf_counter() - t0
    t_run, _ = timeit(fused, lp, lens, iters=3)
    t_plain, _ = timeit(f, lp, lens, iters=3)
    table_mb = sum(int(a.nbytes) for a in
                   htable.ng_keys_ctx + htable.ng_keys_w + htable.ng_vals
                   + htable.bo_keys + htable.bo_vals) / 2 ** 20
    log({"suite": "beam_lm", "case": "aishell_trigram_hashed", "b": b,
         "t": t, "v": v, "w": w, "prune_top_k": 20,
         "lm_ctx": htable.k, "table_mb": round(table_mb, 1),
         "compile_s": compile_s,
         "decode_ms_fused": t_run * 1e3,
         "decode_ms_plain": t_plain * 1e3,
         "fusion_overhead_pct": round(
             100 * (t_run - t_plain) / max(t_plain, 1e-9), 1)})


def suite_streaming() -> None:
    """Per-chunk latency + real-time capacity of the streaming variant.

    Streaming serves live audio, so the metric is per-chunk latency
    with a sync after EVERY chunk (a real server must emit before the
    next chunk arrives), and the derived capacity: how many concurrent
    real-time streams one chip sustains at this batch size.
    """
    import dataclasses

    import jax
    import jax.numpy as jnp

    from deepspeech_tpu.config import get_config
    from deepspeech_tpu.models import create_model
    from deepspeech_tpu.streaming import StreamingTranscriber

    cfg = get_config("ds2_streaming")
    b, chunk = (2, 64) if SMALL else (16, 64)
    if SMALL:
        cfg = dataclasses.replace(
            cfg, model=dataclasses.replace(cfg.model, rnn_hidden=64,
                                           rnn_layers=2,
                                           conv_channels=(4, 4)))
    model = create_model(cfg.model)
    f = cfg.features.num_features
    rng = np.random.default_rng(3)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 64, f), jnp.float32),
                           jnp.asarray([64], jnp.int32), train=False)
    st = StreamingTranscriber(cfg, variables["params"],
                              variables.get("batch_stats", {}),
                              chunk_frames=chunk)
    state = st.init_state(batch=b)
    data = jnp.asarray(rng.normal(size=(b, chunk, f)), jnp.float32)

    state, lo, va = st.process_chunk(state, data)  # compile
    sync((lo, va))
    lats = []
    for _ in range(20):
        t0 = time.perf_counter()
        state, lo, va = st.process_chunk(state, data)
        sync((lo, va))
        lats.append(time.perf_counter() - t0)
    lats.sort()
    n = len(lats)
    # Nearest-rank percentiles: ceil(q*n)-1 (index n-1 would be the max).
    p50 = lats[max(-(-50 * n // 100) - 1, 0)]
    p95 = lats[max(-(-95 * n // 100) - 1, 0)]
    chunk_audio_s = chunk * 0.01  # 10 ms feature stride
    log({"suite": "streaming", "b": b, "chunk_frames": chunk,
         "rnn_layers": cfg.model.rnn_layers,
         "rnn_hidden": cfg.model.rnn_hidden,
         "chunk_ms_p50": p50 * 1e3, "chunk_ms_p95": p95 * 1e3,
         "rtf_per_stream": p50 / chunk_audio_s,
         "realtime_streams_per_chip": b * chunk_audio_s / p50})


def suite_rnnt() -> None:
    """Transducer lattice loss (ops/transducer.py) on the chip: fwd +
    grad timing of the log-semiring associative-scan recursion at an
    EN-like shape, parity vs the O(T*U) DP oracle. Pure XLA (no Pallas
    kernel) — the row shows what the assoc-scan formulation costs on
    the MXU-less VPU path."""
    import jax
    import jax.numpy as jnp

    from deepspeech_tpu.ops.transducer import (transducer_loss,
                                               transducer_loss_ref)

    b, t, u, v = (2, 8, 4, 8) if SMALL else (16, 400, 40, 29)
    rng = np.random.default_rng(7)
    lp = jax.nn.log_softmax(
        jnp.asarray(rng.normal(size=(b, t, u + 1, v)), jnp.float32),
        axis=-1)
    labels = jnp.asarray(rng.integers(1, v, size=(b, u)), jnp.int32)
    il = jnp.asarray(rng.integers(t // 2, t + 1, size=b), jnp.int32)
    ll = jnp.asarray(rng.integers(1, u + 1, size=b), jnp.int32)

    f = jax.jit(lambda x: jnp.mean(transducer_loss(x, labels, il, ll)))
    g = jax.jit(jax.grad(lambda x: jnp.mean(
        transducer_loss(x, labels, il, ll))))
    loss = float(f(lp))
    ref = float(np.mean(transducer_loss_ref(
        np.asarray(lp), np.asarray(labels), np.asarray(il),
        np.asarray(ll))))
    t_f, _ = timeit(f, lp)
    t_g, _ = timeit(g, lp)
    rec = {"suite": f"rnnt_loss_t{t}_u{u}", "b": b, "v": v,
           "loss_rel_err_vs_dp": abs(loss - ref) / max(abs(ref), 1.0),
           "fwd_ms": t_f * 1e3, "grad_ms": t_g * 1e3}
    if K_INNER > 1:
        rec["fwd_ms_amortized"] = {"k": K_INNER,
                                   "xla": ktime_ms(
                                       lambda x: transducer_loss(
                                           x, labels, il, ll), lp)}
    log(rec)


SUITES = {
    "ctc": suite_ctc,
    "gru_resident": suite_gru_resident,
    "gru_blocked": suite_gru_blocked,
    "lstm_resident": suite_lstm_resident,
    "lstm_blocked": suite_lstm_blocked,
    "beam": suite_beam,
    "beam_lm": suite_beam_lm,
    "streaming": suite_streaming,
    "rnnt": suite_rnnt,
}


def main() -> None:
    names = sys.argv[1:] or list(SUITES)
    from deepspeech_tpu.utils.cache import enable_compilation_cache

    enable_compilation_cache()
    import jax

    log({"suite": "env", "devices": [str(d) for d in jax.devices()],
         "default_backend": jax.default_backend()})
    for n in names:
        t0 = time.perf_counter()
        try:
            SUITES[n]()
        except Exception as e:  # record and continue to next suite
            log({"suite": n, "error": f"{type(e).__name__}: {e}"})
        log({"suite": n, "done_in_s": round(time.perf_counter() - t0, 1)})


if __name__ == "__main__":
    main()
