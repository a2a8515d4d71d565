"""The kernels of ``minicpm_sala.transcribe_long_20min_b32`` ALONE on
the chip, at the cell's shapes, each compared with its plain oracle and
timed (``ops/attn_pallas.py`` ``gqa_attn_select_decode`` /
``gqa_attn_select_fwd``; ``ops/ssd_pallas.py`` with a group a head):

  chiprun -- python3 tools/sala_bench.py [--streams 32]

``select_decode``: one decode step of the sparse layer (``--streams``
caches of 19,328 rows, 2 key/value heads of 128, positions 11,251 ..
19,320, 97 blocks a (stream, head): the local window's 32 as one run of
rows, the other 65 by index), swept over the list entries a grid step
fetches. ``select_fwd``: one prefill sub-batch (2 x 15,000
positions). ``state_step``: one decode step of a linear layer (32 heads
of [128, 128] float32 a stream, all 32 single-head groups a grid
step). ``chunk_scan``: a linear layer over a prefill sub-batch. Prints
one JSON line a reading. 3 chip-minutes.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def timed(fn, *args, reps: int = 20) -> float:
    import jax

    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / reps


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--streams", type=int, default=32)
    ap.add_argument("--skip-fwd", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import harness
    from benchmark.reference.minicpm_sala_ref import rms_rel
    from deepspeech_tpu.config import get_config
    from deepspeech_tpu.models import lfm2
    from deepspeech_tpu.ops import attn_pallas as ap_
    from deepspeech_tpu.ops import ssd_pallas as ssd

    m = get_config("minicpm_sala").model
    kind = jax.devices()[0].device_kind
    peaks = harness.peaks_for(kind)
    hbm = peaks["hbm_bytes_per_s"]
    b, rows, nkv, hd = args.streams, m.lfm_seq_positions, m.lfm_kv_heads, 128
    rep, block = m.lfm_heads // nkv, m.sparse_block
    ks = jax.random.split(jax.random.PRNGKey(0), 12)
    bf = jnp.bfloat16

    def say(**kw):
        print(json.dumps({"device": kind, **kw}), flush=True)

    # -- the decode form under a selection
    pos = jnp.asarray(np.linspace(11251, 19320, b).astype(np.int32))
    q = jax.random.normal(ks[0], (b, nkv, rep, hd)).astype(bf)
    keys = jax.random.normal(ks[1], (b, nkv, rows, hd)).astype(bf)
    values = jax.random.normal(ks[2], (b, nkv, rows, hd)).astype(bf)
    pooled = lfm2.pool_keys(m, keys.swapaxes(1, 2))
    live = jnp.arange(b) % 8 != 7
    select = jax.jit(lambda q, pooled, pos: lfm2.select_mask(
        m, lfm2.block_scores(m, q[:, None], pooled, pos[:, None],
                             jnp.zeros((b, 1), bool), rows // block))[:, :, 0])
    sel = select(q, pooled, pos) & live[:, None, None]
    t_select = timed(select, q, pooled, pos)
    want = jax.jit(lambda *v: lfm2.cached_attend_selected(*v, block))(
        q, keys, values, sel, pos)
    needed = int(jnp.sum(jnp.where(live, lfm2.rows_selected(m, pos), 0))) \
        * 2 * nkv * hd * 2
    say(kernel="sparse_select", streams=b, ms=1e3 * t_select,
        selected=int(sel[0, 0].sum()))
    first = jnp.maximum(pos // block - m.sparse_window // block + 1, 0)
    for per in (4, 8, 16):
        length = -(-lfm2.select_list_len(m) // per) * per
        listed = jax.jit(lambda s: ap_.select_list(s, length, first, per))
        kernel = jax.jit(lambda q, k, v, i, c, p: ap_.gqa_select_decode(
            q, k, v, i, c, p, first * block, live, block, m.sparse_window,
            per))
        idx, count = listed(sel)
        got = kernel(q, keys, values, idx, count, pos)
        t_k = timed(kernel, q, keys, values, idx, count, pos)
        say(kernel="gqa_attn_select_decode", per_step=per, streams=b,
            kernel_ms=1e3 * t_k, list_ms=1e3 * timed(listed, sel),
            needed_mb=needed / 1e6, hbm_pct=100 * needed / hbm / t_k,
            rms_rel=rms_rel(np.asarray(got, np.float32),
                            np.asarray(want, np.float32)))
    dense = jax.jit(lambda q, k, v, p, l: ap_.gqa_decode(
        q, k.swapaxes(1, 2), v.swapaxes(1, 2), p, l, 0))
    say(kernel="gqa_attn_decode (every row + a transposition, for scale)",
        streams=b, kernel_ms=1e3 * timed(dense, q, keys, values, pos, live))
    del keys, values, pooled, want, got

    # -- a linear layer's step
    h, n = m.lin_heads, m.lin_head_dim
    a = -jnp.asarray(lfm2.decay_slopes(m, 2))
    state = jax.random.normal(ks[3], (b, h, n, n))
    step = (jax.random.normal(ks[4], (b, h, n)).astype(bf),
            jnp.ones((b, h)), a,
            jax.random.normal(ks[5], (b, h, n)).astype(bf),
            jax.random.normal(ks[6], (b, h, n)).astype(bf), None, live)
    y0, new0 = jax.jit(ssd.step_oracle)(state, *step)
    moved = int(jnp.sum(live)) * 2 * 4 * h * n * n
    for gb in (32,):
        kernel = jax.jit(lambda s, *v: ssd.state_step(
            s, *v, group_block=gb), donate_argnums=0)
        y, new = kernel(state + 0, *step)
        err = {"y_rms_rel": rms_rel(np.asarray(y, np.float32),
                                    np.asarray(y0, np.float32)),
               "state_rms_rel": rms_rel(new, new0)}
        jax.block_until_ready(new)
        t = time.perf_counter()
        for _ in range(20):
            _, new = kernel(new, *step)
        jax.block_until_ready(new)
        t_k = (time.perf_counter() - t) / 20
        say(kernel="ssd_state_step", group_block=gb, streams=b,
            kernel_ms=1e3 * t_k, needed_mb=moved / 1e6,
            hbm_pct=100 * moved / hbm / t_k, **err)
    del state, new, new0

    if args.skip_fwd:
        return
    # -- a prefill sub-batch: the linear layer's scan, the selection
    # and the attention under it
    rows_, s = 2, 15000
    lens = np.asarray([15000, 11251])
    valid = jnp.arange(s)[None, :] < lens[:, None]
    x = jax.random.normal(ks[7], (rows_, s, h, n)).astype(bf)
    bm = jax.random.normal(ks[8], (rows_, s, h, n)).astype(bf) * 0.1
    cm = jax.random.normal(ks[9], (rows_, s, h, n)).astype(bf) * 0.1
    scan = (x, jnp.ones((rows_, s, h)), a, bm, cm, None, valid)
    kernel = jax.jit(lambda *v: ssd.chunk_scan(*v, m.ssm_chunk))
    y, st = kernel(*scan)
    y0, st0 = jax.jit(ssd.scan_oracle)(*scan)
    v_ = np.asarray(valid)
    say(kernel="ssd_chunk_scan", rows=rows_, positions=s,
        kernel_ms=1e3 * timed(kernel, *scan, reps=5),
        y_rms_rel=rms_rel(np.asarray(y, np.float32)[v_],
                          np.asarray(y0, np.float32)[v_]),
        state_rms_rel=rms_rel(st, st0))
    del x, bm, cm, scan, y, y0

    q = jax.random.normal(ks[10], (rows_, s, nkv, rep, hd)).astype(bf)
    k = jax.random.normal(ks[11], (rows_, s, nkv, hd)).astype(bf)
    v = jax.random.normal(ks[1], (rows_, s, nkv, hd)).astype(bf)
    blocks = -(-s // block)
    sel = jax.random.bernoulli(ks[2], 0.4, (rows_, nkv, s, blocks))
    sel = sel.at[..., 0].set(True)
    kernel = jax.jit(lambda *v: ap_.gqa_select_attention(*v, block))
    got = kernel(q, k, v, sel)
    t_k = timed(kernel, q, k, v, sel, reps=5)
    at = slice(14000, 14512)
    want = jax.jit(lambda q, k, v, s_: lfm2.selected_attend(
        q[:, at], k[:, :14512], v[:, :14512], s_[:, :, at], 14000, block))(
            q, k, v, sel)
    say(kernel="gqa_attn_select_fwd", rows=rows_, positions=s,
        kernel_ms=1e3 * t_k,
        dense_causal_tflops=4 * rows_ * m.lfm_heads * hd * s * s / 2
        / t_k / 1e12,
        rms_rel=rms_rel(np.asarray(got[:, at], np.float32),
                        np.asarray(want, np.float32)))


if __name__ == "__main__":
    main()
