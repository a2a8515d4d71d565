"""On-chip smoke: the main path, once, through the entry points users call.

    python chip_smoke.py              # one TPU chip
    python chip_smoke.py --multichip  # four chips: data-parallel training

One process, every phase in it (a chip belongs to one process). The
phases call the same ``main(argv)`` functions that ``python -m
deepspeech_tpu.train|infer|serve`` call, at the published widths of
presets the repo ships — only steps, utterances and seconds of audio
are cut:

  device     jax.devices(); anything but a TPU ends the run
  train      ds2_full (2 conv + 7 BiGRU-1760 + BN, bf16), b=16, 4 steps
  infer      restores that checkpoint, greedy-decodes 32 utterances
  reference  the Pallas GRU and CTC kernels against the repo's XLA/jnp
             oracles at those widths on a small input; ds2_full's scan
             call at the benchmark cell's own rows and frames in its
             two builds (weights copied once / streamed in column
             blocks): forward the same bits, gradients as near the XLA
             scan's as each other; that call's recurrent weight
             gradient at six, three and one bf16 passes against the
             float64 sum of its operands (dw_h_precision); a whole
             layer of that call, both directions: the backward call
             that sums the pair's input gradient against XLA's sum of
             the two directions' float32 results rounded to bf16, bit
             for bit, the projection's bias gradient it sums on the
             way against the float64 sum of the same float32 values,
             and the three backward calls' device time
             (pair_input_grad)
  serve      ds2_streaming (uni-GRU 5x800 + lookahead 20): a checkpoint
             from two train steps, two generated wavs streamed chunk by
             chunk, finals compared with the offline decode of the same
             wavs
  sync       one jitted call timed to block_until_ready and to a host
             read

Every phase prints one JSON line of set-up facts (wall seconds, compiles
and compile seconds, persistent-cache hits, losses, peak device memory);
they are not benchmark numbers. A phase that raises ends the run with
its traceback. The last line of stdout is the result the driver reads:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``--multichip`` runs, instead of the phases above, the same three
ds2_full steps on all four chips and on one, and compares the losses.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import gc
import io
import json
import math
import os
import shutil
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))

BATCH = 16
SEED = 0  # wavs; the weights come from the presets' train.seed
# Side of the square matmuls the sync probe chains: large enough that
# the device needs tens of milliseconds, so a block_until_ready that
# returned at dispatch would show.
SYNC_N = 4096
# Losses of two runs that differ only in how the batch is split over
# chips: bf16 matmuls keep 8 mantissa bits (eps 2^-8 ~ 0.004), and the
# gradient all-reduce sums in another order, so allow a few eps.
LOSS_RTOL = 0.02
# Kernel against oracle at bf16 dots (r2 on this chip measured 1.3e-3).
GRU_RTOL = 1e-2
CTC_RTOL = 1e-3
# ds2_full.train_1chip's scan call (rows, post-conv frames), where the
# two builds of the H=1760 kernels are compared with each other and
# with the XLA scan.
SCAN_CALL = (32, 850)
# Gradients of that call, as shares of each one's largest magnitude:
# the copy-once build against the streamed build (one float32 sum of
# dh associated another way; each flipped bf16 rounding of dgates then
# feeds the next of 850 steps: the chip reads up to 3.3e-4), and either
# against the XLA scan (the chip reads 2.8e-3, 8.6e-2 and 5.2e-4 for
# both builds alike; the scan's transposed dot takes bf16 operands
# where the kernels' dW_h contracts them as float32). A build that
# lost a column block or a time step reads tenths to ones.
SCAN_BUILDS_RTOL = 2e-3
# ax_k1's latent attention at its published widths, one layer: (rows,
# positions) on which its two forms are compared, the decode form
# (key/value expansion absorbed into query and output, scores against
# the cached rows) against the sequence form (expanded keys and values)
# at the last quarter of the positions, as root-mean-square difference
# over the sequence form's rms. In bfloat16 the two round different
# intermediates (the chip read 0.46% inside the benchmark's check,
# PR 32); a form that lost the rotary part, the norm or a mask reads
# tenths to ones.
MLA_CALL = (8, 288)
MLA_FORMS_RTOL = 2e-2
SCAN_ORACLE_RTOL = {"dxproj": 1e-2, "dw_h": 0.2, "db_h": 2e-3}
# The recurrent weight gradient of that call, [T*B, H]^T x [T*B, 3H]
# from float32 operands (ops/scan_pallas.py recurrent_dw): what the
# three-pass contraction a bf16 model runs (Precision.HIGH) may differ
# by from the float64 sum of the same operands, as largest error over
# largest value and as rms error over rms value, and how many times
# under the distance between the bf16-dot program's dW_h and the
# all-float32 program's (the noise 850 steps of bf16 matmuls have put
# into both operands) it has to stay. Fixed by ISSUE 37 before the
# first reading; the chip's readings: PERF.md section 6, PR 37.
DW_H_LIMIT = 1e-4
DW_H_TIMES_UNDER_NOISE = 20
# The input projection's bias gradient over that call's T*B rows, as
# the pair's summing call accumulates it (the float32 ``dxp_f +
# dxp_b`` a step into ``[8, 3H]`` float32, 3,400 adds an entry),
# against the float64 column sums of the same float32 values, over
# the largest column: float32 summation noise. XLA's ``reduce_sum``
# of the sum ROUNDED to bf16 (what the program was before the kernel
# took the sum in) is read beside it, for the record. The chip's
# readings: PERF.md section 6, PR 55.
PROJ_BIAS_GRAD_RTOL = 2e-5
# Traced calls a side of pair_input_grad's timing.
PAIR_TIMED_CALLS = 10
# xing4_29b_a4b's hyper-connection of one sub-layer: the positions of
# a prefill sub-batch and of a drafting step, and (streams, width).
MHC_CALLS = {"prefill": 6784, "decode": 512}
MHC_STREAMS = (4, 3584)
# The kernels' float32 coefficients against the jax.numpy form's
# (sigmoids in (0, 2), a doubly stochastic matrix: absolute), and their
# bf16 results, over the larger of the value and 1: one bf16 ulp.
MHC_COEF_ATOL = 1e-5
MHC_BF16_RTOL = 2.0 ** -7
MHC_TIMED_CALLS = 10
# Streamed finals against the offline decode of the same audio: the
# two graphs reduce in different orders in bf16, so an argmax near a
# tie may flip; more than this is a wrong stream, not rounding.
STREAM_CER_MAX = 0.1


def emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


# -- observation ----------------------------------------------------------

class CompileCounter:
    """Counts what jax reports about compilation while the phases run:
    every backend compile request (and its seconds), and how many of
    them the persistent cache answered."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration_secs, **_):
        if event == self.COMPILE:
            self.compiles += 1
            self.compile_s += duration_secs

    def _on_event(self, event, **_):
        if event == self.HIT:
            self.cache_hits += 1

    def snapshot(self):
        return self.compiles, self.compile_s, self.cache_hits

    def since(self, snap) -> dict:
        return {"compiles": self.compiles - snap[0],
                "compile_s": round(self.compile_s - snap[1], 2),
                "cache_hits": self.cache_hits - snap[2]}


class _Tee(io.TextIOBase):
    """stdout that passes text through and keeps each line with the
    time it arrived."""

    def __init__(self, out):
        self._out = out
        self._buf = ""
        self.lines = []  # (perf_counter, line)

    def write(self, s):
        self._out.write(s)
        self._buf += s
        while "\n" in self._buf:
            line, self._buf = self._buf.split("\n", 1)
            self.lines.append((time.perf_counter(), line))
        return len(s)

    def flush(self):
        self._out.flush()

    def records(self):
        """(arrival time, parsed object) of every JSON line."""
        out = []
        for t, line in self.lines:
            if line.startswith("{"):
                out.append((t, json.loads(line)))
        return out


def call_main(main, argv):
    """Run one entry point's ``main(argv)``; returns its stdout tee."""
    tee = _Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        main(argv)
    return tee


def run_phase(name: str, counter: CompileCounter, fn, *args) -> None:
    import jax

    gc.collect()  # drop the previous phase's device buffers
    snap = counter.snapshot()
    t0 = time.perf_counter()
    facts = fn(*args)
    rec = {"phase": name,
           "wall_s": round(time.perf_counter() - t0, 2),
           **counter.since(snap), **facts}
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        rec["peak_bytes_in_use"] = stats["peak_bytes_in_use"]
    emit(rec)


# -- checks ---------------------------------------------------------------

def check_device(want: int) -> dict:
    """The device as jax reports it; the run ends here without a TPU."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        fail(f"no TPU: jax.devices() reports {dev}")
    if dev["count"] != want:
        fail(f"need {want} chip(s), jax.devices() reports {dev}")
    return dev


def kernel_route(preset: str) -> dict:
    """What 'auto' resolved to for this preset, and which recurrent
    kernel build its widths select at its batch (the route's answer
    for the Pallas impl, whatever this machine resolves)."""
    from deepspeech_tpu.config import get_config
    from deepspeech_tpu.models.rnn import layer_scan_route
    from deepspeech_tpu.utils.impl import interpret_default, resolve_impl
    from deepspeech_tpu.utils.quantize import kernel_regime

    cfg = get_config(preset)
    on_chip = dataclasses.replace(cfg.model, rnn_impl="pallas")
    route = layer_scan_route(on_chip, cfg.data.batch_size,
                             directions=2 if cfg.model.bidirectional else 1)
    return {"preset": preset,
            "rnn_impl": resolve_impl(cfg.model.rnn_impl, oracle="xla"),
            "loss_impl": resolve_impl(cfg.train.loss_impl, oracle="jnp"),
            "interpret": interpret_default(),
            "kernel_regime": kernel_regime(cfg.model, quantized=False),
            "rnn_route": ("bigru-" + route.variant
                          if route.kernel == "bigru_scan_fwd"
                          else route.variant or "xla"),
            "rnn_hidden": cfg.model.rnn_hidden}


def check_kernels(route: dict, step_text: str) -> dict:
    """'auto' must have chosen the compiled Pallas kernels, and the
    lowered train step must hold them: a run on interpreted kernels or
    on the oracles looks the same from outside."""
    if route["rnn_impl"] != "pallas" or route["loss_impl"] != "pallas":
        fail(f"'auto' did not resolve to pallas: {route}")
    if route["interpret"]:
        fail("Pallas kernels would run interpreted")
    n = step_text.count("tpu_custom_call")
    if n == 0:
        fail("the lowered train step holds no tpu_custom_call")
    return {"tpu_custom_calls": n}


@contextlib.contextmanager
def watch_train_step():
    """Keep a handle on the jitted step ``train.main`` builds, the
    shapes of its first call, and what the devices held while it ran —
    for the HLO and placement checks; the step itself is untouched."""
    import jax

    from deepspeech_tpu import train

    seen = {}
    make = train.make_train_step

    def make_and_watch(*a, **kw):
        step = seen["step"] = make(*a, **kw)

        def watched(*args):
            if "args" not in seen:
                seen["args"] = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)
                seen["batch_devices"] = len(
                    args[1]["features"].sharding.device_set)
            else:  # the first step has run: the state is resident
                seen["bytes_in_use"] = [
                    (d.memory_stats() or {}).get("bytes_in_use")
                    for d in jax.devices()]
            return step(*args)

        return watched

    train.make_train_step = make_and_watch
    try:
        yield seen
    finally:
        train.make_train_step = make


# -- phases ---------------------------------------------------------------

def train_steps(preset: str, n_steps: int, ckpt_dir: str, extra=()):
    """``train.main`` for ``n_steps`` synthetic batches; returns the
    per-step facts and the watched step."""
    from deepspeech_tpu import train

    t0 = time.perf_counter()
    with watch_train_step() as seen:
        tee = call_main(train.main, [
            f"--config={preset}", f"--synthetic={n_steps * BATCH}",
            f"--data.batch_size={BATCH}", "--train.epochs=1",
            "--train.log_every=1", f"--train.checkpoint_dir={ckpt_dir}",
            *extra])
    steps = [(t, r) for t, r in tee.records()
             if r.get("event") == "train_step"]
    losses = [r["loss"] for _, r in steps]
    if len(steps) < n_steps:
        fail(f"{preset}: {len(steps)} optimizer steps logged, "
             f"wanted {n_steps}")
    if not all(math.isfinite(x) for x in losses):
        fail(f"{preset}: non-finite loss in {losses}")
    if not os.path.isdir(os.path.join(ckpt_dir, str(n_steps))):
        fail(f"{preset}: no checkpoint for step {n_steps} in {ckpt_dir}")
    stamps = [t0] + [t for t, _ in steps]
    step_s = [round(b - a, 3) for a, b in zip(stamps, stamps[1:])]
    facts = {"preset": preset, "steps": len(steps), "losses": losses,
             # Set-up and compile land in the first step's seconds.
             "first_step_s": step_s[0], "later_step_s": step_s[1:],
             "checkpoint": ckpt_dir}
    return facts, seen


def phase_train(work: str) -> dict:
    route = kernel_route("ds2_full")
    emit({"resolved": route})
    facts, seen = train_steps("ds2_full", 4, os.path.join(work, "full"))
    lowered = seen["step"].lower(*seen["args"])
    facts.update(check_kernels(route, lowered.as_text()))
    return facts


def phase_infer(work: str) -> dict:
    from deepspeech_tpu import infer

    n = 2 * BATCH
    tee = call_main(infer.main, [
        "--config=ds2_full",
        f"--checkpoint-dir={os.path.join(work, 'full')}",
        f"--synthetic={n}", f"--data.batch_size={BATCH}"])
    done = [r for _, r in tee.records() if r.get("event") == "done"]
    if len(done) != 1 or done[0]["n_utts"] != n:
        fail(f"infer: wanted one 'done' line for {n} utterances, "
             f"got {done}")
    if not all(math.isfinite(done[0][k]) for k in ("wer", "cer")):
        fail(f"infer: non-finite error rate in {done[0]}")
    return {"n_utts": n, "wer": done[0]["wer"], "cer": done[0]["cer"]}


def phase_reference() -> dict:
    """Kernel against oracle at the presets' widths, small B and T."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeech_tpu.models.rnn import gru_scan
    from deepspeech_tpu.ops.ctc import ctc_loss
    from deepspeech_tpu.ops.ctc_pallas import ctc_loss_pallas
    from deepspeech_tpu.ops.rnn_pallas import gru_scan_pallas
    from deepspeech_tpu.utils.impl import interpret_default

    interpret = interpret_default()
    rng = np.random.default_rng(0)
    b, t = 8, 8
    out = {}
    for h in (1760, 800):  # blocked and resident weights
        xp = jnp.asarray(rng.normal(size=(b, t, 3 * h)), jnp.float32)
        wh = jnp.asarray(rng.normal(size=(h, 3 * h)) / np.sqrt(h),
                         jnp.float32)
        bh = jnp.asarray(rng.normal(size=(3 * h,)) * 0.1, jnp.float32)
        lens = rng.integers(t // 2, t + 1, size=b)
        mask = jnp.asarray(np.arange(t)[None] < lens[:, None], jnp.float32)
        got = np.asarray(jax.jit(lambda x, w: gru_scan_pallas(
            x, mask, w, bh, False, interpret, "bfloat16"))(xp, wh))
        want = np.asarray(jax.jit(lambda x, w: gru_scan(
            x, mask, w, bh, dot_dtype=jnp.bfloat16))(xp, wh))
        err = float(np.max(np.abs(got - want))
                    / max(1.0, float(np.abs(want).max())))
        if not err <= GRU_RTOL:
            fail(f"GRU H={h} kernel differs from the XLA scan: {err}")
        out[f"gru_h{h}_rel_err"] = err
    out.update(scan_builds(interpret))
    out.update(dw_h_precision(interpret))
    out.update(pair_input_grad(interpret))
    out.update(attention_forms())
    out.update(mhc_passes(interpret))
    t, v, lmax = 100, 29, 20
    logits = jnp.asarray(rng.normal(size=(b, t, v)), jnp.float32)
    label_lens = jnp.asarray(rng.integers(lmax // 2, lmax + 1, size=b),
                             jnp.int32)
    labels = jnp.asarray(rng.integers(1, v, size=(b, lmax)), jnp.int32)
    labels = labels * (jnp.arange(lmax)[None] < label_lens[:, None])
    in_lens = jnp.full((b,), t, jnp.int32)
    got = np.asarray(jax.jit(lambda lg: ctc_loss_pallas(
        lg, labels, in_lens, label_lens, interpret))(logits))
    want = np.asarray(jax.jit(lambda lg: ctc_loss(
        lg, labels, in_lens, label_lens))(logits))
    err = float(np.max(np.abs(got - want) / np.maximum(np.abs(want), 1.0)))
    if not err <= CTC_RTOL:
        fail(f"CTC kernel differs from the jnp loss: {err}")
    out["ctc_rel_err"] = err
    return out


def attention_forms() -> dict:
    """One layer of ax_k1's latent attention (``models/axk1.py``) at
    the preset's widths, seeded weights, bfloat16: every (row, position)
    of the last quarter of ``MLA_CALL`` through the decode form against
    the cache the sequence form wrote, compared with the sequence
    form's output there."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeech_tpu.config import get_config
    from deepspeech_tpu.models.axk1 import LatentAttention, both_forms

    m = get_config("ax_k1").model
    (b, s), dtype = MLA_CALL, jnp.dtype(m.dtype)
    at = np.arange(s - s // 4, s)
    x = jax.random.normal(jax.random.PRNGKey(0), (b, s, m.lfm_hidden),
                          dtype)
    params = jax.jit(lambda r: jax.tree.map(
        lambda w: w.astype(dtype), LatentAttention(m).init(
            r, x[:1, :2], jnp.arange(2)[None, :])["params"]))(
                jax.random.PRNGKey(1))
    both = jax.jit(lambda p, x: both_forms(m, p, x, at))
    dec, seq = (np.asarray(a, np.float32) for a in both(params, x))
    err = float(np.sqrt(np.mean((dec - seq) ** 2) / np.mean(seq ** 2)))
    if not err <= MLA_FORMS_RTOL:
        fail(f"latent attention: the decode form differs from the "
             f"sequence form by {err}")
    return {"mla_forms_rms_rel": err, "mla_positions_compared": int(
        b * len(at))}


def scan_builds(interpret: bool) -> dict:
    """ds2_full's scan call (``SCAN_CALL``, H=1760, bf16) in the build
    its shapes choose (``pinned``: the weights copied into VMEM once,
    one grid step a time step) and in the streamed build the same call
    takes with the cap at 0 (``blocked``: 512-column blocks), same
    inputs. Forward the two compiled builds give the same bits: the
    gates' columns are independent (an interpreted run rounds per CPU
    fusion, tests/test_pallas.py). Backward ``dh`` is one float32 sum
    associated another way, so the gradients differ by rounding that
    850 steps of bf16 dots carry along; what holds them is the XLA
    scan, from which neither build may lie further than the limits."""
    from unittest import mock

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeech_tpu.models.rnn import gru_scan
    from deepspeech_tpu.ops import rnn_pallas, scan_pallas

    (b, t), h = SCAN_CALL, 1760
    rng = np.random.default_rng(1)
    xp = jnp.asarray(rng.normal(size=(b, t, 3 * h)), jnp.bfloat16)
    wh = jnp.asarray(rng.normal(size=(h, 3 * h)) / np.sqrt(h), jnp.float32)
    bh = jnp.asarray(rng.normal(size=(3 * h,)) * 0.1, jnp.float32)
    lens = rng.integers(t // 2, t + 1, size=b)
    mask = jnp.asarray(np.arange(t)[None] < lens[:, None], jnp.float32)
    dy = jnp.asarray(rng.normal(size=(b, t, h)) * 0.1, jnp.float32)

    def run(scan):
        def ys_and_grads(x, w, bias):
            ys, vjp = jax.vjp(lambda *a: scan(a[0], mask, *a[1:]),
                              x, w, bias)
            return (ys,) + vjp(dy)

        return [np.asarray(a, np.float32)
                for a in jax.jit(ys_and_grads)(xp, wh, bh)]

    def pallas(x, m, w, bias):
        return rnn_pallas.gru_scan_pallas(x, m, w, bias, False, interpret,
                                          "bfloat16")

    pinned = run(pallas)
    with mock.patch.object(scan_pallas, "PINNED_VMEM_CAP", 0):
        streamed = run(pallas)
    oracle = run(lambda x, m, w, bias: gru_scan(
        x, m, w, bias, dot_dtype=jnp.bfloat16))

    def rel(got, want):
        return float(np.max(np.abs(got - want)) / np.abs(want).max())

    differing = int((pinned[0] != streamed[0]).sum())
    if differing and not interpret:
        fail(f"GRU H={h} forward: the copy-once build differs from the "
             f"streamed build in {differing} of {pinned[0].size} values")
    out = {"gru_builds_fwd_differing": differing,
           "gru_builds_fwd_values": int(pinned[0].size)}
    for i, name in enumerate(("ys", "dxproj", "dw_h", "db_h")):
        builds = rel(pinned[i], streamed[i])
        errs = {"pinned": rel(pinned[i], oracle[i]),
                "streamed": rel(streamed[i], oracle[i])}
        if i and not builds <= SCAN_BUILDS_RTOL:
            fail(f"GRU H={h} {name}: the builds differ by {builds}")
        for build, err in errs.items():
            if not err <= (SCAN_ORACLE_RTOL[name] if i else GRU_RTOL):
                fail(f"GRU H={h} {name}: the {build} build differs "
                     f"from the XLA scan by {err}")
            out[f"gru_{build}_{name}_rel_err"] = err
        out[f"gru_builds_{name}_rel_diff"] = builds
    return out


def dw_h_precision(interpret: bool) -> dict:
    """What the precision of the recurrent weight gradient costs and
    buys at ds2_full's scan call (``SCAN_CALL``, H=1760). The operands
    are the ones a real backward pass in bf16 dots hands to
    ``recurrent_dw`` (seeded inputs, a signed cotangent: sums that
    cancel, as Gaussian operands would not). For each of ``HIGHEST``
    (six bf16 passes of the MXU), ``HIGH`` (three) and ``DEFAULT``
    (one; timed and listed, never used): milliseconds a contraction,
    and its error against the float64 sum of the SAME float32 operands
    on the host. Beside them, the distance from that sum to the dW_h
    of the all-float32 program on the same inputs: the noise the bf16
    recurrence has put into the operands before any contraction sees
    them. ``HIGH`` and the program's own dW_h (whatever
    ``recurrent_dw`` lowers to) must hold ``DW_H_LIMIT`` and stay
    ``DW_H_TIMES_UNDER_NOISE`` times under that distance. On the CPU
    every precision is float32 arithmetic: control flow only."""
    from unittest import mock

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeech_tpu.ops import rnn_pallas, scan_pallas

    (b, t), h = SCAN_CALL, 1760
    rng = np.random.default_rng(37)
    # Both programs see the same bf16-rounded projections, so that the
    # distance reads the recurrence's dot type alone.
    xp = jnp.asarray(rng.normal(size=(b, t, 3 * h)), jnp.bfloat16)
    wh = jnp.asarray(rng.normal(size=(h, 3 * h)) / np.sqrt(h), jnp.float32)
    bh = jnp.asarray(rng.normal(size=(3 * h,)) * 0.1, jnp.float32)
    lens = rng.integers(t * 12 // 17, t + 1, size=b)  # the cell's 12-17 s
    mask = jnp.asarray(np.arange(t)[None] < lens[:, None], jnp.float32)
    dy = jnp.asarray(rng.normal(size=(b, t, h)) * 0.1, jnp.float32)

    handed = []
    shipped = scan_pallas.recurrent_dw

    def keep(h_prev, dgates, dot):
        handed.append((h_prev, dgates))
        return shipped(h_prev, dgates, dot)

    def program_dw_h(x, dot_dtype):
        # Eagerly, so that the VJP rule runs on concrete arrays.
        _, vjp = jax.vjp(lambda w: rnn_pallas.gru_scan_pallas(
            x, mask, w, bh, False, interpret, dot_dtype), wh)
        return np.asarray(vjp(dy)[0], np.float64)

    with mock.patch.object(scan_pallas, "recurrent_dw", keep):
        program = program_dw_h(xp, "bfloat16")
    (h_prev, dgates), = handed
    all_float32 = program_dw_h(xp.astype(jnp.float32), None)
    exact = (np.asarray(h_prev, np.float64).reshape(b * t, h).T
             @ np.asarray(dgates, np.float64).reshape(b * t, 3 * h))

    def apart(got):
        d = np.asarray(got, np.float64) - exact
        return {"max_rel": float(np.abs(d).max() / np.abs(exact).max()),
                "rms_rel": float(np.sqrt(np.mean(d ** 2)
                                         / np.mean(exact ** 2)))}

    calls = 10
    rows = {"program": apart(program)}
    for name in ("HIGHEST", "HIGH", "DEFAULT"):
        contract = jax.jit(functools.partial(
            jnp.einsum, "...h,...g->hg",
            precision=getattr(jax.lax.Precision, name)))
        rows[name.lower()] = apart(contract(h_prev, dgates))  # compiled
        t0 = time.perf_counter()
        jax.block_until_ready([contract(h_prev, dgates)
                               for _ in range(calls)])
        rows[name.lower()]["ms"] = (time.perf_counter() - t0) * 1e3 / calls
    noise = apart(all_float32)
    for name in ("high", "program"):
        for kind, err in rows[name].items():
            if kind != "ms" and not err <= min(
                    DW_H_LIMIT, noise[kind] / DW_H_TIMES_UNDER_NOISE):
                fail(f"dW_h at H={h}, {b * t} rows: {name} is {err} "
                     f"({kind}) from the float64 sum of its operands; "
                     f"the limit is {DW_H_LIMIT} and a "
                     f"{DW_H_TIMES_UNDER_NOISE}th of the bf16 "
                     f"recurrence's own {noise[kind]}")
    out = {"dw_h_rows": b * t}
    for name, row in {**rows, "bf16_to_float32": noise}.items():
        out.update({f"dw_h_{name}_{kind}": v for kind, v in row.items()})
    return out


def device_ops(sides, calls: int) -> list:
    """``(event name, device milliseconds)`` of every operation that
    runs on a TPU while each of ``sides`` (thunks) is called ``calls``
    times in turn under the profiler. [] where the trace holds no TPU
    plane (the CPU rehearsal)."""
    import glob

    import jax

    from benchmark.reduce import xplane

    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        try:
            for _ in range(calls):
                for side in sides:
                    jax.block_until_ready(side())
        finally:
            jax.profiler.stop_trace()
        return [(name, (end - start) / 1e6)
                for path in glob.glob(
                    os.path.join(trace_dir, "**", "*.xplane.pb"),
                    recursive=True)
                for device in xplane.load(path).devices.values()
                for start, end, name in device.ops]


def scan_bwd_ms(sides, calls: int) -> dict:
    """Median device milliseconds of every backward scan kernel that
    runs while :func:`device_ops` calls ``sides``, by ``(reverse,
    sum)`` of the call's ``kernel_metadata`` (``sum``: ``pair`` or,
    where the fact is absent, ``own``). {} off the chip."""
    import statistics

    from benchmark.layer_metrics._kernel_id import is_scan_bwd, kernel_facts

    seen = {}
    for name, ms in device_ops(sides, calls):
        facts = kernel_facts(name)
        if is_scan_bwd(facts.get("kernel", "")):
            seen.setdefault((facts["reverse"], facts.get("sum", "own")),
                            []).append(ms)
    return {key: statistics.median(ms) for key, ms in seen.items()}


def pair_input_grad(interpret: bool) -> dict:
    """A whole bidirectional layer of ds2_full's scan call
    (``SCAN_CALL``, H=1760, bf16 ``xproj``, the projection's bias
    apart) backward, two ways on the same inputs. As two functions
    (``gru_scan_pallas`` a direction over ``product + bias``): two
    backward calls that each write their own float32 ``dxp``, XLA's
    ``(a + b).astype(bfloat16)`` of the two and, for the bias, its
    ``reduce_sum`` of that bf16 array (what ``nn.Dense``'s VJP states)
    beside the float32 ``a + b`` itself. As one
    (``gru_scan_pair_pallas``): the forward direction's call, and the
    reverse direction's taking its rows in and writing the sum rounded
    to bf16 with the float32 sum's column sums. The summed ``dxp`` must
    be XLA's bit for bit (summed in float32, rounded once); the bias
    gradient must lie within ``PROJ_BIAS_GRAD_RTOL`` of the float64
    column sums of the float32 ``a + b`` (over the largest column);
    how far XLA's sum of the rounded values lies from them is read
    beside it. Beside them: how many values of the four recurrent
    weight and bias gradients differ between the two programs (the
    same kernels and contractions: 0), and the device time of the
    three backward calls,
    the two programs called in turn ``PAIR_TIMED_CALLS`` times under
    the profiler: a one-direction layer's reverse call (``own``), the
    forward direction's (``first``, the same call in both programs)
    and the summing one. Off the chip the times are None: not
    measured."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeech_tpu.ops import rnn_pallas
    from deepspeech_tpu.ops.scan_pallas import add_proj_bias

    (b, t), h = SCAN_CALL, 1760
    rng = np.random.default_rng(50)
    product = jnp.asarray(rng.normal(size=(b, t, 3 * h)), jnp.bfloat16)
    weights = [jnp.asarray(a, jnp.float32) for _ in range(2) for a in (
        rng.normal(size=(h, 3 * h)) / np.sqrt(h),
        rng.normal(size=(3 * h,)) * 0.1)]
    b_x = jnp.asarray(rng.normal(size=(3 * h,)) * 0.1, jnp.float32)
    lens = rng.integers(t * 12 // 17, t + 1, size=b)  # the cell's 12-17 s
    mask = jnp.asarray(np.arange(t)[None] < lens[:, None], jnp.float32)
    dy = jnp.asarray(rng.normal(size=(b, t, h)) * 0.1, jnp.float32)

    @jax.jit
    def apart(product, b_x, w_f, b_f, w_b, b_b):
        x = add_proj_bias(product, b_x)
        grads = []
        for reverse, w, bias in ((False, w_f, b_f), (True, w_b, b_b)):
            _, pull = jax.vjp(lambda x, w, bias: rnn_pallas.gru_scan_pallas(
                x, mask, w, bias, reverse, interpret, "bfloat16"),
                x, w, bias)
            grads.append(pull(dy))
        (dxp_f, *fw), (dxp_b, *bw) = grads
        dxp = (dxp_f + dxp_b).astype(x.dtype)
        # the bias gradient as flax's Dense transposes it: a
        # reduce_sum of the bf16 cotangent
        db_x = jax.lax.reduce_sum(dxp, axes=(0, 1)).astype(b_x.dtype)
        return (dxp, db_x, *fw, *bw), dxp_f + dxp_b

    @jax.jit
    def as_one(product, b_x, *w):
        _, pull = jax.vjp(
            lambda product, b_x, *w: rnn_pallas.gru_scan_pair_pallas(
                product, mask, b_x, *w, interpret, "bfloat16"),
            product, b_x, *w)
        return pull(dy)

    (want, float32_sum), got = (apart(product, b_x, *weights),
                                as_one(product, b_x, *weights))
    if not got[0].dtype == want[0].dtype == jnp.bfloat16:
        fail(f"the pair's dxp is {got[0].dtype}, XLA's sum {want[0].dtype}: "
             f"both are to be xproj's bfloat16")
    names = ("dxproj", "db_x", "dw_f", "db_f", "dw_b", "db_b")
    differing = {name: int(jnp.sum(a != w))
                 for name, a, w in zip(names, got, want) if name != "db_x"}
    if differing["dxproj"]:
        fail(f"GRU H={h}: the pair's summed dxp differs from XLA's "
             f"(a + b).astype(bfloat16) of the two directions' float32 "
             f"results in {differing['dxproj']} of {got[0].size} values")
    columns = np.zeros(3 * h, np.float64)
    for rows in np.asarray(float32_sum):  # a row of the batch at a time
        columns += rows.astype(np.float64).sum(axis=0)
    kernel_err, rounded_err = (
        float(np.abs(np.asarray(a, np.float64) - columns).max()
              / np.abs(columns).max()) for a in (got[1], want[1]))
    if kernel_err > PROJ_BIAS_GRAD_RTOL:
        fail(f"the projection's bias gradient out of the summing call "
             f"lies {kernel_err} of the largest column from the float64 "
             f"sum of the float32 dxp_f + dxp_b over {b * t} rows (limit "
             f"{PROJ_BIAS_GRAD_RTOL}; XLA's sum of the values rounded to "
             f"bfloat16 lies {rounded_err})")
    ms = scan_bwd_ms([lambda: apart(product, b_x, *weights),
                      lambda: as_one(product, b_x, *weights)],
                     PAIR_TIMED_CALLS)
    out = {"pair_dxp_values": int(got[0].size),
           "pair_proj_bias_grad_rel_err": kernel_err,
           "pair_proj_bias_grad_rounded_rel_err": rounded_err}
    out.update({f"pair_{name}_differing": n
                for name, n in differing.items()})
    for name, key in (("first", ("0", "own")), ("own", ("1", "own")),
                      ("summing", ("1", "pair"))):
        out[f"pair_bwd_{name}_ms"] = ms.get(key)
    return out


def mhc_passes(interpret: bool) -> dict:
    """One sub-layer's hyper-connection at xing4_29b_a4b's two calls
    (``MHC_CALLS`` positions of ``MHC_STREAMS``, bf16, seeded
    parameters with the preset's bias of std 1), two ways on the same
    streams: ``models/mhc.py``'s ``jax.numpy`` form
    (``HyperConnection``, ``read``, ``write``) and the kernels
    ``mhc_read`` / ``mhc_write`` (``ops/mhc_pallas.py``). The kernels'
    coefficients, read mix and written streams are held to the form's
    (``MHC_COEF_ATOL``, ``MHC_BF16_RTOL``). Both write-backs take the
    SAME sub-layer output, the plain form's read mix: the kernel's own
    is another rounding of the same sum at some values (each as near
    the float64 sum), and fed back it comes through ``H_post`` as up
    to two ulps (0.0214 for the limit's 0.0078 on the chip). Beside
    them, each way called ``MHC_TIMED_CALLS`` times under the
    profiler: the device time of each kernel (its Mosaic call alone:
    XLA's copy of the coefficients carries the call's identity too)
    with the GB/s of the bytes the call moves (the streams in, the mix
    out; the streams and the sub-layer's output in, the streams out),
    of what XLA runs about the kernels (where the streams enter and
    leave their ``[N, n * D]`` form, the fold of gain and phi), and of
    everything the ``jax.numpy`` form runs. Off the chip the times are
    None: not measured."""
    import statistics

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.layer_metrics._kernel_id import kernel_facts
    from benchmark.reduce import xplane
    from deepspeech_tpu.config import get_config
    from deepspeech_tpu.models import mhc
    from deepspeech_tpu.ops import mhc_pallas

    n, d = MHC_STREAMS
    cfg = dataclasses.replace(get_config("xing4_29b_a4b").model,
                              hc_streams=n, lfm_hidden=d)
    layer = mhc.HyperConnection(cfg)
    count = n * (n + 2)
    out = {}
    for call, rows in MHC_CALLS.items():
        key = jax.random.PRNGKey(rows)
        x = jax.random.normal(key, (rows, n, d)).astype(jnp.bfloat16)
        params = layer.init(jax.random.PRNGKey(52), x)["params"]

        @jax.jit
        def plain_read(x, params):
            h_pre, h_post, h_res = layer.apply({"params": params}, x)
            coef = jnp.concatenate(
                [h_pre, h_post, h_res.reshape(rows, n * n)], axis=-1)
            return coef, mhc.read(h_pre, x)

        @jax.jit
        def plain_write(x, y, coef):
            _, h_post, h_res = mhc.unpack(coef, n)
            return mhc.write(h_res, h_post, x, y)

        @jax.jit
        def kernel_read(x, params):
            return mhc_pallas.read(
                x, params["norm"], params["phi"], params["alpha"],
                params["bias"], norm_eps=cfg.lfm_norm_eps,
                clamp=cfg.hc_res_clamp, iters=cfg.hc_sinkhorn_iters,
                eps=cfg.hc_eps, interpret=interpret)

        kernel_write = functools.partial(mhc_pallas.write,
                                         interpret=interpret)
        want_coef, y = plain_read(x, params)
        coef, mix = kernel_read(x, params)
        errs = {"coef": float(jnp.max(jnp.abs(coef - want_coef)))}
        for name, got, ref in (
                ("mix", mix, y),
                ("streams", kernel_write(x, y, coef),
                 plain_write(x, y, want_coef))):
            got, ref = (np.asarray(a, np.float32) for a in (got, ref))
            errs[name] = float(np.max(
                np.abs(got - ref) / np.maximum(np.abs(ref), 1.0)))
        out.update({f"mhc_{call}_{k}_err": v for k, v in errs.items()})
        size = x.dtype.itemsize
        moved = {"mhc_read": rows * (n * d + d) * size + rows * count * 4,
                 "mhc_write": rows * (2 * n * d + d) * size
                 + rows * count * 4}
        seen, around = {}, 0.0
        for name, ms in device_ops(
                [lambda: kernel_write(x, y, kernel_read(x, params)[0])],
                MHC_TIMED_CALLS):
            kernel = kernel_facts(name).get("kernel")
            if kernel in moved and xplane.KERNEL_MARK in name:
                seen.setdefault(kernel, []).append(ms)
            else:
                around += ms
        for kernel, nbytes in moved.items():
            ms = statistics.median(seen[kernel]) if seen else None
            out[f"mhc_{call}_{kernel[4:]}_ms"] = ms
            out[f"mhc_{call}_{kernel[4:]}_gb_s"] = (
                nbytes / ms / 1e6 if ms else None)
        # what XLA runs about the kernels, a call, and the plain form whole
        plain_ms = sum(ms for _, ms in device_ops(
            [lambda: plain_write(x, y, plain_read(x, params)[0])],
            MHC_TIMED_CALLS))
        for name, ms in (("around", around), ("plain", plain_ms)):
            out[f"mhc_{call}_{name}_ms"] = (
                ms / MHC_TIMED_CALLS if seen else None)
        # the times above are printed with the fault: they say what ran
        if not (errs["coef"] <= MHC_COEF_ATOL
                and errs["mix"] <= MHC_BF16_RTOL
                and errs["streams"] <= MHC_BF16_RTOL):
            fail(f"the hyper-connection kernels at {rows} positions differ "
                 f"from the jax.numpy form: {errs} (limits {MHC_COEF_ATOL} "
                 f"and {MHC_BF16_RTOL}); read so far: {json.dumps(out)}")
    return out


def make_wavs(wav_dir: str, seed: int):
    """Two tone-coded utterances (tools/rehearsal.py's synthesiser) and
    a manifest of them; returns (wav paths, texts, manifest path)."""
    import numpy as np

    sys.path.insert(0, os.path.join(REPO, "tools"))
    from rehearsal import RATE, WORDS, synth, write_wav

    rng = np.random.default_rng(seed)
    os.makedirs(wav_dir, exist_ok=True)
    wavs, texts = [], []
    manifest = os.path.join(wav_dir, "wavs.jsonl")
    with open(manifest, "w") as f:
        for i, n_words in enumerate((4, 6)):  # ~2 s and ~3.5 s
            text = " ".join(rng.choice(WORDS, size=n_words))
            audio = synth(text, rng)
            path = os.path.join(wav_dir, f"utt{i}.wav")
            write_wav(path, audio)
            wavs.append(path)
            texts.append(text)
            f.write(json.dumps({"audio": path, "text": text,
                                "duration": len(audio) / RATE}) + "\n")
    return wavs, texts, manifest


def phase_serve(work: str) -> dict:
    from deepspeech_tpu import infer, serve
    from deepspeech_tpu.metrics import cer

    route = kernel_route("ds2_streaming")
    emit({"resolved": route})
    ckpt = os.path.join(work, "streaming")
    trained, _ = train_steps("ds2_streaming", 2, ckpt)
    wavs, texts, manifest = make_wavs(os.path.join(work, "wavs"), SEED)

    t0 = time.perf_counter()
    tee = call_main(serve.main, [
        "--config=ds2_streaming", f"--checkpoint-dir={ckpt}",
        "--decode=greedy", *wavs])
    recs = tee.records()
    chunks = [(t, r) for t, r in recs if "chunk" in r]
    finals = [r["final"] for _, r in recs if "final" in r]
    if not chunks or any(len(r["partials"]) != len(wavs)
                         for _, r in chunks):
        fail(f"serve: wanted chunk partials for {len(wavs)} streams, "
             f"got {[r for _, r in chunks][:3]}")
    if len(finals) != 1 or len(finals[0]) != len(wavs):
        fail(f"serve: wanted one final per wav, got {finals}")

    # The offline graph over the same audio is the reference for what
    # the chunked engine streamed.
    tee = call_main(infer.main, [
        "--config=ds2_streaming", f"--checkpoint-dir={ckpt}",
        f"--manifest={manifest}", f"--data.batch_size={len(wavs)}"])
    offline = {r["ref"]: r["hyp"] for _, r in tee.records()
               if r.get("event") == "utt"}
    if sorted(offline) != sorted(texts):
        fail(f"serve: offline decode covered {sorted(offline)}")
    stream_cer = cer([offline[t] for t in texts], finals[0])
    if not stream_cer <= STREAM_CER_MAX:
        fail(f"serve: streamed finals differ from the offline decode "
             f"(CER {stream_cer}): {finals[0]} vs "
             f"{[offline[t] for t in texts]}")
    return {"train_losses": trained["losses"],
            "streams": len(wavs), "chunks": len(chunks),
            # Set-up and compile land in the first chunk's seconds.
            "first_chunk_s": round(chunks[0][0] - t0, 3),
            "later_chunk_ms": [r["ms"] for _, r in chunks[1:]],
            "final_chars": [len(x) for x in finals[0]],
            "stream_vs_offline_cer": stream_cer}


def phase_sync() -> dict:
    """Is ``block_until_ready`` the sync? Time one jitted call three
    ways: to dispatch, to block_until_ready, to a host read."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def work(x):
        for _ in range(64):
            x = jnp.tanh(x @ x) * 0.5
        return x

    x = jnp.full((SYNC_N, SYNC_N), 0.01, jnp.bfloat16)
    float(work(x)[0, 0])  # compile + warm
    t0 = time.perf_counter()
    work(x)
    dispatch = time.perf_counter() - t0
    float(work(x)[0, 0])  # drain
    t0 = time.perf_counter()
    jax.block_until_ready(work(x))
    blocked = time.perf_counter() - t0
    t0 = time.perf_counter()
    float(work(x)[0, 0])
    host_read = time.perf_counter() - t0
    return {"dispatch_ms": round(dispatch * 1e3, 3),
            "block_until_ready_ms": round(blocked * 1e3, 3),
            "host_read_ms": round(host_read * 1e3, 3)}


def phase_multichip(work: str) -> dict:
    """The same three ds2_full steps on every chip and on one."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    from _aot_common import count_collectives

    route = kernel_route("ds2_full")
    emit({"resolved": route})
    many, seen = train_steps("ds2_full", 3, os.path.join(work, "dp"))
    hlo = seen["step"].lower(*seen["args"]).compile().as_text()
    collectives = count_collectives(hlo, keep_zero=False)
    check_kernels(route, hlo)
    one, seen_one = train_steps("ds2_full", 3, os.path.join(work, "one"),
                                extra=["--train.mesh_shape=1,1"])
    n = seen["batch_devices"]
    facts = {"losses": many["losses"], "losses_one_chip": one["losses"],
             "loss_rtol": LOSS_RTOL, "batch_devices": n,
             "batch_devices_one_chip": seen_one["batch_devices"],
             "collectives": collectives,
             "bytes_in_use": seen["bytes_in_use"],
             "first_step_s": many["first_step_s"],
             "later_step_s": many["later_step_s"]}
    emit({"multichip": facts})  # before the checks: a failed run shows them
    if n != 4 or seen_one["batch_devices"] != 1:
        fail(f"batch spans {n} devices (wanted 4) and "
             f"{seen_one['batch_devices']} (wanted 1)")
    if not collectives.get("all-reduce"):
        fail(f"the compiled step holds no all-reduce: {collectives}")
    # Parameters and momentum alone are hundreds of MB on every chip.
    if not all(b and b > 100e6 for b in seen["bytes_in_use"]):
        fail(f"a device holds next to nothing: {seen['bytes_in_use']}")
    for a, b in zip(many["losses"], one["losses"]):
        if abs(a - b) > LOSS_RTOL * max(1.0, abs(b)):
            fail(f"4-chip and 1-chip losses differ: {many['losses']} "
                 f"vs {one['losses']}")
    return {"losses_agree": True}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="four chips: data-parallel ds2_full training "
                         "against the same steps on one chip, and "
                         "nothing else")
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    device = check_device(4 if args.multichip else 1)
    from deepspeech_tpu import native
    from deepspeech_tpu.utils.cache import resolve_cache_dir

    emit({"phase": "device", **device,
          "cache_dir": resolve_cache_dir(),
          "native": ("built" if native.available()
                     else f"unavailable: {native.build_error()}")})
    counter = CompileCounter()
    start = counter.snapshot()
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        if args.multichip:
            run_phase("multichip", counter, phase_multichip, work)
        else:
            run_phase("train", counter, phase_train, work)
            run_phase("infer", counter, phase_infer, work)
            run_phase("reference", counter, phase_reference)
            run_phase("serve", counter, phase_serve, work)
            run_phase("sync", counter, phase_sync)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    emit({"phase": "total",
          "wall_s": round(time.perf_counter() - t0, 2),
          **counter.since(start)})
    emit({"ok": True, "device": device})


if __name__ == "__main__":
    main()
