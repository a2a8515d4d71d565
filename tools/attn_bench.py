"""``gqa_attn_fwd`` on the chip at a cell's shapes: time and error of the
kernel against the blockwise loop it replaces, a tile size at a time,
(with ``--grad`` also the kernel's forward + backward pair,
``gqa_attn_bwd_dq`` and ``gqa_attn_bwd_dkv``, under ``jax.grad``), then
one whole ``Attention`` layer (projections, norms, gate and ``o``
included) with the kernel and with the loop, and what the cell's
two-forms check would read with each.

  chiprun -- python3 tools/attn_bench.py --tiles 256x512 512x512 \
      > chiprun_out/attn_bench.jsonl

``--mode decode``: ``gqa_attn_decode`` against the plain decode form
(``models/lfm2.cached_attend``) at the cell's decode step: 16 streams,
a sliding layer's ring of 4,096 rows and the global layer's cache of
6,784, positions as the traffic file draws them (stratified lengths,
3.6 word-pieces a second) at the loop's steps ``--steps`` (a stream
whose tokens are out is not live), a row tile at a time
(``--row-tiles``); ``ms`` is one call inside a jitted loop of
``--iters`` calls, ``gbps_fetched`` / ``gbps_reach`` the rows the form
moves and the rows in reach, 4 kB each, over it; then the two-forms
reading of a layer with the kernels and without.

One JSON line a reading. ``ms``: the median of ``--iters`` timed calls
(``block_until_ready``); ``need_tflop``: the mixing operations of the
keys IN REACH (``4 x heads x head`` a key, as
``benchmark/costs/trinity.prefill_attention_flops`` counts them, every
position valid); ``peak_pct``: those over the time over the published
bf16 peak (``benchmark/peaks.json``); ``rms_rel``: root-mean-square
difference from the loop's result over its root mean square. On the CPU
(``--rehearse``) the kernel runs interpreted at a toy size: control
flow only.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def attend(q, k, v, i0: int, j0: int, window: int):
    """``models/lfm2.py`` ``Attention``'s ``attend`` on its own: queries
    ``i0 ..`` ``q [B, sq, kv, rep, hd]`` against the keys ``j0 ..`` ``k,
    v [B, sk, kv, hd]``."""
    import jax
    import jax.numpy as jnp

    from deepspeech_tpu.models.lfm2 import reach_mask

    scores = jnp.einsum("bqgrd,bkgd->bgrqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores * (q.shape[-1] ** -0.5)
    scores = jnp.where(
        reach_mask(i0, q.shape[1], j0, k.shape[1], window), scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bgrqk,bkgd->bqgrd", probs, v)


def blockwise(window: int, block: int):
    """``Attention``'s loop over query blocks as a function of q, k, v:
    what the kernel replaces, and its oracle (``tests/
    test_attn_pallas.py`` holds the kernel to it)."""
    import jax.numpy as jnp

    def run(q, k, v):
        s, outs = q.shape[1], []
        for i0 in range(0, s, block):
            i1 = min(i0 + block, s)
            j0 = max(0, i0 - window + 1) if window else 0
            outs.append(attend(q[:, i0:i1], k[:, j0:i1], v[:, j0:i1],
                               i0, j0, window))
        return jnp.concatenate(outs, axis=1)
    return run


def stream_lengths(seed: int, n: int):
    """Prefix positions and decode steps of ``n`` streams from the
    parameters of ``benchmark/traffic/transcribe_long_7min_b16.json``,
    drawn as ``gen/batches.make_batches`` draws them (stratified valid
    lengths, labels a frame)."""
    import numpy as np

    traffic = json.load(open(os.path.join(
        ROOT, "benchmark", "traffic", "transcribe_long_7min_b16.json")))
    lo, hi = traffic["valid_frames"]
    strata = lo + (hi + 1 - lo) * (np.arange(n) + 0.5) / n
    frames = np.random.default_rng(seed).permutation(
        strata.astype(np.int64))
    steps = np.round(traffic["labels_per_frame"] * frames).astype(np.int64)
    return -(-frames // 8), steps + 1


def decode_mode(args, m, timed_loop, rms_rel, device) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeech_tpu.models import lfm2
    from deepspeech_tpu.ops import attn_pallas

    nkv, rep, hd = m.lfm_kv_heads, m.lfm_heads // m.lfm_kv_heads, \
        lfm2.head_dim(m)
    dtype, b = jnp.dtype(m.dtype), args.rows
    prefix, steps = stream_lengths(args.seed, b)
    caches = ((m.lfm_window, m.lfm_window), (args.cache_rows, 0))
    if args.rehearse:
        prefix, steps = prefix // 128, steps // 64
        caches = ((m.lfm_window, m.lfm_window), (64, 0))
    row_bytes = 2 * nkv * hd * dtype.itemsize
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 3)
    q = jax.random.normal(keys[0], (b, nkv, rep, hd), dtype)
    for rows, window in caches:
        k = jax.random.normal(keys[1], (b, rows, nkv, hd), dtype)
        v = jax.random.normal(keys[2], (b, rows, nkv, hd), dtype)
        for j in args.steps:
            pos = jnp.asarray(prefix + j, jnp.int32)
            live = jnp.asarray(j < steps)
            reach = int(np.sum(np.where(
                j < steps, np.minimum(prefix + j + 1, window or 1 << 30),
                0)))

            def plain(q, k, v, pos, live, window=window):
                return jnp.where(
                    live[:, None, None, None],
                    lfm2.cached_attend(q, k, v, pos, window), 0)

            forms = [("xla", None, plain)] + [
                ("gqa_attn_decode", rt,
                 lambda q, k, v, pos, live, rt=rt, window=window:
                 attn_pallas.gqa_decode(q, k, v, pos, live, window, rt,
                                        args.rehearse))
                for rt in args.row_tiles]
            want = None
            for what, rt, fn in forms:
                got = jax.jit(fn)(q, k, v, pos, live)
                want = got if want is None else want
                ms = timed_loop(fn, q, k, v, pos, live)
                fetched = b * rows if rt is None else int(
                    attn_pallas.rows_fetched(pos, live, rows, window, rt))
                print(json.dumps({
                    "what": what, "rows": rows, "window": window,
                    "step": j, "live": int(np.sum(j < steps)),
                    "row_tile": rt, "ms": ms, "rows_reach": reach,
                    "rows_fetched": fetched,
                    "gbps_fetched": fetched * row_bytes / ms / 1e6,
                    "gbps_reach": reach * row_bytes / ms / 1e6,
                    "rms_rel": rms_rel(got, want), "device": device}),
                    flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["prefill", "decode"],
                    default="prefill")
    ap.add_argument("--row-tiles", type=int, nargs="+", default=[512])
    ap.add_argument("--steps", type=int, nargs="+",
                    default=[0, 700, 1250, 1400])
    ap.add_argument("--cache-rows", type=int, default=6784)
    ap.add_argument("--rows", type=int, default=0,
                    help="2 prefill rows, 16 decode streams")
    ap.add_argument("--positions", type=int, default=5250)
    ap.add_argument("--windows", type=int, nargs="+", default=[4096, 0])
    ap.add_argument("--tiles", nargs="+", default=["256x512"],
                    help="QUERIESxKEYS a tile")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-kernel", action="store_true",
                    help="skip the kernel alone")
    ap.add_argument("--preset", default="trinity_large",
                    help="whose heads, window and layer the shapes are")
    ap.add_argument("--grad", action="store_true",
                    help="also time the kernel's forward + backward "
                         "(gqa_attn_bwd_dq, gqa_attn_bwd_dkv) a tile size")
    ap.add_argument("--no-layer", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeech_tpu.config import get_config
    from deepspeech_tpu.models import lfm2
    from deepspeech_tpu.ops import attn_pallas

    args.rows = args.rows or (16 if args.mode == "decode" else 2)
    m = get_config(args.preset).model
    block, s = 512, args.positions
    if args.rehearse:
        m = dataclasses.replace(m, lfm_hidden=64, lfm_heads=4,
                                lfm_kv_heads=2, lfm_head_dim=16,
                                lfm_window=24, dtype="float32")
        block, s = 16, 70
        args.tiles, args.windows = ["16x8"], [24, 0]
        args.row_tiles, args.steps, args.iters = [8], [0, 9, 20], 2
    nkv, rep, hd = m.lfm_kv_heads, m.lfm_heads // m.lfm_kv_heads, \
        lfm2.head_dim(m)
    dtype = jnp.dtype(m.dtype)
    device = jax.devices()[0].device_kind
    peak = json.load(open(os.path.join(
        ROOT, "benchmark", "peaks.json"))).get(device, {}).get("bf16_flops")

    def timed(fn, *xs):
        jax.block_until_ready(fn(*xs))
        out = []
        for _ in range(args.iters):
            t = time.perf_counter()
            jax.block_until_ready(fn(*xs))
            out.append(1e3 * (time.perf_counter() - t))
        return statistics.median(out)

    def timed_loop(fn, q, *xs):
        """One call of ``fn(q, *xs)`` inside a jitted loop of
        ``--iters``, each call's query the one before's result's (a
        call of 0.3 ms alone is mostly its dispatch)."""
        many = jax.jit(lambda q, *xs: jax.lax.fori_loop(
            0, args.iters,
            lambda _, q: (q + 1e-3 * fn(q, *xs)).astype(q.dtype), q))
        jax.block_until_ready(many(q, *xs))
        out = []
        for _ in range(3):
            t = time.perf_counter()
            jax.block_until_ready(many(q, *xs))
            out.append(1e3 * (time.perf_counter() - t) / args.iters)
        return statistics.median(out)

    def rms_rel(got, want):
        got, want = (jnp.asarray(x, jnp.float32) for x in (got, want))
        return float(jnp.sqrt(jnp.mean((got - want) ** 2)
                              / jnp.mean(want ** 2)))

    if args.mode == "decode":
        decode_mode(args, m, timed_loop, rms_rel, device)
        args.no_kernel = True

    keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
    q = jax.random.normal(keys[0], (args.rows, s, nkv, rep, hd), dtype)
    k = jax.random.normal(keys[1], (args.rows, s, nkv, hd), dtype)
    v = jax.random.normal(keys[2], (args.rows, s, nkv, hd), dtype)
    for window in () if args.no_kernel else args.windows:
        reach = sum(min(i + 1, window) if window else i + 1
                    for i in range(s))
        need = 4.0 * nkv * rep * hd * reach * args.rows
        oracle = jax.jit(blockwise(window, block))
        want = oracle(q, k, v)
        ms = timed(oracle, q, k, v)
        line = {"what": "loop", "window": window, "ms": ms,
                "need_tflop": need / 1e12, "device": device}
        if peak:
            line["peak_pct"] = 100 * need / (1e-3 * ms * peak)
        print(json.dumps(line), flush=True)
        for tile in args.tiles:
            tq, tk = (int(x) for x in tile.split("x"))
            def kernel(q, k, v, tq=tq, tk=tk, window=window):
                return attn_pallas.gqa_attention(q, k, v, window, tq, tk,
                                                 args.rehearse)

            fn = jax.jit(kernel)
            t = time.perf_counter()
            got = jax.block_until_ready(fn(q, k, v))
            first = time.perf_counter() - t
            ms = timed(fn, q, k, v)
            line = {"what": "gqa_attn_fwd", "window": window,
                    "q_tile": tq, "k_tile": tk, "ms": ms,
                    "first_call_s": first,
                    "rms_rel": rms_rel(got, want),
                    **attn_pallas.tile_counts(s, window, tq, tk)}
            if peak:
                line["peak_pct"] = 100 * need / (1e-3 * ms * peak)
            print(json.dumps(line), flush=True)
            if not args.grad:
                continue
            # forward (with its log-sum-exp) + gqa_attn_bwd_dq + _dkv;
            # the backward pair NEEDS twice the forward's operations
            both = jax.jit(jax.grad(lambda *x: jnp.sum(
                kernel(*x).astype(jnp.float32)), (0, 1, 2)))
            jax.block_until_ready(both(q, k, v))
            line = {"what": "gqa_attn_fwd+bwd", "window": window,
                    "q_tile": tq, "k_tile": tk,
                    "ms": timed(both, q, k, v)}
            if peak:
                line["peak_pct"] = 100 * 3 * need / (
                    1e-3 * line["ms"] * peak)
            print(json.dumps(line), flush=True)

    if args.no_layer:
        return
    from benchmark.drivers.transcribe_long import FORMS_AT

    decode = args.mode == "decode"
    x = jax.random.normal(
        keys[3], (1 if decode else args.rows, s, m.lfm_hidden), dtype)
    at = np.asarray([p for p in FORMS_AT if p < s] if s > 4096
                    else range(0, s, 7))
    for kind in ("sliding_attention", "full_attention"):
        layer = lfm2.Attention(m, kind, block)
        params = jax.jit(lambda: jax.tree.map(
            lambda p: p.astype(dtype), layer.init(
                jax.random.PRNGKey(args.seed + 1),
                jnp.zeros((1, 4, m.lfm_hidden), dtype))["params"]))()
        cache_rows = min(m.lfm_window, s) if "sliding" in kind else s
        on_tpu, outs = lfm2.on_tpu, {}
        for what in ("kernel", "loop"):
            # the module asks ``on_tpu`` while it is traced, and a trace
            # is cached by function: a function of its own each time
            lfm2.on_tpu = on_tpu if what == "kernel" else (lambda: False)
            try:
                line = {"what": f"layer_{what}", "kind": kind}
                if not decode:
                    fn = jax.jit(
                        lambda p, x: layer.apply({"params": p}, x)[0])
                    outs[what] = fn(params, x)
                    line["ms"] = timed(fn, params, x)
                # the decode form against the sequence form, as the
                # cell's reference check reads it (``ref_forms_rms_rel``):
                # both kernels, or neither
                dec, seq = jax.jit(lambda p, x: lfm2.both_forms(
                    m, kind, p, x, at, cache_rows, block))(params, x[:1])
            finally:
                lfm2.on_tpu = on_tpu
            print(json.dumps(dict(line, forms_rms_rel=rms_rel(dec, seq))),
                  flush=True)
        if not decode:
            print(json.dumps({"what": "layer_rms_rel", "kind": kind,
                              "rms_rel": rms_rel(outs["kernel"],
                                                 outs["loop"])}),
                  flush=True)


if __name__ == "__main__":
    main()
