"""The two state-space kernels ALONE on the chip, at the shapes of
``falcon_h1_34b.transcribe_16s`` (``ops/ssd_pallas.py``): each timed
against its plain oracle and compared with it, with the kernel's share
of its roofline by ``benchmark/costs/falcon_h1.py``.

  chiprun -- python3 tools/ssd_bench.py [--streams 128] [--rows 32]

``ssd_chunk_scan``: one prefill sub-batch of one layer (``--rows``
utterances of 212 positions, valid 150-207, 32 heads of 128, state 256,
2 groups, bfloat16). ``ssd_state_step``: one decode step of one layer
(``--streams`` states of 4.2 MB float32, every eighth stream not live).
Prints one JSON line a kernel. 2 chip-minutes.
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
    ap.add_argument("--streams", type=int, default=128)
    ap.add_argument("--rows", type=int, default=32)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import harness
    from benchmark.costs import falcon_h1
    from benchmark.reference.falcon_h1_ref import rms_rel
    from deepspeech_tpu.config import get_config
    from deepspeech_tpu.ops import ssd_pallas as ssd

    m = get_config("falcon_h1_34b").model
    kind = jax.devices()[0].device_kind
    peaks = harness.peaks_for(kind)
    h, n, g = m.ssm_heads, m.ssm_state, m.ssm_groups
    p = m.ssm_d_ssm // h
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    a = -jnp.exp(jax.random.uniform(ks[0], (h,), maxval=2.7))
    d = jnp.ones((h,))

    b, s = args.rows, 212
    lens = np.linspace(150, 207, b).astype(np.int32)
    x = (0.1 * jax.random.normal(ks[1], (b, s, h, p))).astype(jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(ks[2], (b, s, h)) - 3)
    bm = (0.1 * jax.random.normal(ks[3], (b, s, g, n))).astype(jnp.bfloat16)
    cm = (0.1 * jax.random.normal(ks[4], (b, s, g, n))).astype(jnp.bfloat16)
    valid = jnp.arange(s)[None, :] < lens[:, None]
    kernel = jax.jit(lambda *v: ssd.chunk_scan(*v, m.ssm_chunk))
    oracle = jax.jit(ssd.scan_oracle)
    scan = (x, dt, a, bm, cm, d, valid)
    y, state = kernel(*scan)
    y0, state0 = oracle(*scan)
    t_k, t_o = timed(kernel, *scan), timed(oracle, *scan, reps=3)
    flops = sum(falcon_h1.scan_flops(m, int(v)) for v in lens)
    moved = sum(falcon_h1.scan_bytes(m, int(v)) for v in lens)
    least, bound = falcon_h1.roofline_seconds(
        {"flops": flops, "bytes": moved}, peaks["bf16_flops"],
        peaks["hbm_bytes_per_s"])
    print(json.dumps({
        "kernel": "ssd_chunk_scan", "device": kind, "rows": b,
        "positions": s, "kernel_ms": 1e3 * t_k, "oracle_ms": 1e3 * t_o,
        "needed_gflop": flops / 1e9, "needed_mb": moved / 1e6,
        "roofline_ms": 1e3 * least, "bound": bound,
        "roofline_pct": 100 * least / t_k,
        "y_rms_rel": rms_rel(np.asarray(y, np.float32)[np.asarray(valid)],
                             np.asarray(y0, np.float32)[np.asarray(valid)]),
        "state_rms_rel": rms_rel(state, state0)}), flush=True)
    del y, y0, state0, scan

    b = args.streams
    live = jnp.arange(b) % 8 != 7
    states = jnp.tile(state[:1], (b, 1, 1, 1))
    # every stream takes position 0 of the first utterance
    step = (*(jnp.tile(v[:1, 0], (b,) + (1,) * (v.ndim - 2))
              for v in (x, dt)), a,
            *(jnp.tile(v[:1, 0], (b, 1, 1)) for v in (bm, cm)), d, live)
    kernel = jax.jit(ssd.state_step, donate_argnums=0)
    oracle = jax.jit(ssd.step_oracle)
    y0, new0 = oracle(states, *step)
    y, new = kernel(states + 0, *step)
    err = {"y_rms_rel": rms_rel(np.asarray(y, np.float32),
                                np.asarray(y0, np.float32)),
           "state_rms_rel": rms_rel(new, new0)}
    del y, y0, new0
    t_o = timed(oracle, states, *step, reps=5)
    # the state is donated and updated in place: feed each call the last
    jax.block_until_ready(new)
    t = time.perf_counter()
    for _ in range(20):
        _, new = kernel(new, *step)
    jax.block_until_ready(new)
    t_k = (time.perf_counter() - t) / 20
    moved = int(jnp.sum(live)) * falcon_h1.step_bytes(m)
    print(json.dumps({
        "kernel": "ssd_state_step", "device": kind, "streams": b,
        "live": int(jnp.sum(live)), "kernel_ms": 1e3 * t_k,
        "oracle_ms": 1e3 * t_o, "needed_mb": moved / 1e6,
        "roofline_ms": 1e3 * moved / peaks["hbm_bytes_per_s"],
        "hbm_pct": 100 * moved / peaks["hbm_bytes_per_s"] / t_k, **err}),
        flush=True)


if __name__ == "__main__":
    main()
