"""AOT-compile the training step for a REAL v5e target — no chip needed.

A chip answers timing questions; this tool answers the compiler-level
half without one. jax.experimental.topologies + the installed libtpu build a
v5e TopologyDescription locally, and ``jit(...).lower(...).compile()``
against a mesh of those abstract devices runs the REAL TPU compiler
(Mosaic included for Pallas kernels when they compile ahead-of-time):

- HBM accounting per sweep point (argument/temp/output bytes vs the
  chip's 16 GB) — validates batch choices before chip time.
- TPU-optimized HLO — e.g. whether XLA's all-reduce combiner collapses
  the per-leaf gradient psums (the CPU-backend HLO shows 107 separate
  all-reduces for the DP step; the TPU pipeline is what counts).
- cost_analysis() flops — a LOWER BOUND cross-check of utils/flops.py's
  analytic model (the MFU denominator in the bench artifact): XLA's
  HloCostAnalysis counts a lax.scan/while body ONCE regardless of trip
  count (verified empirically: a 50-step scanned matmul reports 1x the
  body flops, its unrolled twin reports 50x), so the scanned recurrent
  matmuls of the RNN stack are mostly absent from this number. The
  analytic model remains the denominator of record; a compiler flops
  figure BELOW it is expected, one ABOVE it would flag undercounting.

Usage (CPU env, real libtpu):

  JAX_PLATFORMS=cpu python tools/aot_tpu.py --preset ds2_full \
      --batch 16 --frames 800 --topology v5e:2x2 --ndev 1

Prints ONE JSON line per invocation (diagnostics on stderr). Notes:
the smallest constructible v5e topology here is 2x2 (4 chips,
chips_per_host_bounds is fixed); ``--ndev 1`` carves a 1-device mesh
out of it, which compiles the same single-chip program the bench's
jit would. Executables are NOT runnable on this host (abstract
devices) — this is a compiler oracle, not a benchmark.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _aot_common import (count_collectives, cycles_by_op, log,  # noqa: E402
                         setup_aot_env)

setup_aot_env()

V5E_HBM_BYTES = 16 * 1024**3

_log = functools.partial(log, "aot_tpu")


def serve_lm(args, cfg, topo) -> None:
    """A preset that is SERVED by ``decode.mode="lm_greedy"``: compile
    its two programs (``decode/lm_greedy.py``: prefill of one sub-batch,
    the decode loop) for one described chip at ``--batch`` streams of
    ``--frames`` frames, and print each one's arguments and temporaries.
    The weights and the cache are arguments of both (the cache donated),
    so a program's peak is its arguments + temporaries, beside which
    the process holds the batches in flight."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from deepspeech_tpu.decode.lm_greedy import LMGreedy
    from deepspeech_tpu.models.lfm2 import seeded_variables

    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=chip), tree)

    m = cfg.model
    params, buffers = on_chip(jax.eval_shape(
        lambda: seeded_variables(cfg, 0)))
    engine = LMGreedy(cfg, params, buffers)
    os.environ["DS2N_ASSUME_TPU"] = "1"
    b, t = args.batch, engine.steps_max
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=chip)
    # An array a latent layer or draft module, else a tuple: (keys,
    # values) a grouped-query layer, (keys, values, state, convolution
    # inputs) a hybrid layer, (keys, values, pooled keys) a sparse
    # layer, (state,) a linear one; with a draft module the loop also
    # takes each stream's first draft.
    cache = [tuple(sds(x.shape, x.dtype) for x in s)
             if isinstance(s, tuple) else sds(s[0].shape, s[0].dtype)
             for s in engine.cache_shapes(b, args.frames)]
    draft = (sds((b,), jnp.int32),) if m.lm_draft_layers else ()
    programs = {
        "prefill": (engine._prefill, (
            params, buffers, cache,
            sds((b, args.frames, cfg.features.num_features), jnp.float32),
            sds((b,), jnp.int32), sds((), jnp.int32))),
        "decode": (engine._decode, (
            params, buffers, cache, sds((b,), jnp.int32),
            sds((b,), jnp.int32), sds((b, t), jnp.int32),
            sds((min(cfg.decode.lm_watch_rows, b),), jnp.int32),
            sds((), jnp.bool_))
            + draft),
    }
    out = {"tool": "aot_tpu", "preset": args.preset, "batch": b,
           "frames": args.frames, "serve": "lm_greedy",
           "prefill_rows": cfg.decode.lm_prefill_rows,
           "layers": len(m.lfm_layer_types),
           "draft_modules": m.lm_draft_layers,
           "device_kind": str(topo.devices[0].device_kind)}
    for name, (fn, shapes) in programs.items():
        t0 = time.time()
        _log(f"lowering + TPU-compiling {name}...")
        lowered = jax.jit(fn, donate_argnums=(2,)).lower(*shapes)
        comp = lowered.compile()
        ma = comp.memory_analysis()
        row = {"argument_bytes": int(ma.argument_size_in_bytes),
               "temp_bytes": int(ma.temp_size_in_bytes),
               "output_bytes": int(ma.output_size_in_bytes),
               "alias_bytes": int(ma.alias_size_in_bytes),
               "mosaic_calls": lowered.as_text().count("tpu_custom_call"),
               "compile_s": round(time.time() - t0, 1)}
        row["peak_estimate_bytes"] = (
            row["argument_bytes"] + row["temp_bytes"]
            + row["output_bytes"] - row["alias_bytes"])
        row["free_of_16gib_bytes"] = (V5E_HBM_BYTES
                                      - row["peak_estimate_bytes"])
        out[name] = row
        if args.hlo_out:
            with open(f"{args.hlo_out}.{name}", "w") as f:
                f.write(comp.as_text())
    print(json.dumps(out))


def log_cycles(hlo: str, top: int = 40) -> None:
    """The compiler's cost model by line of the program, largest
    first: sizes an XLA-level change before anyone asks for it."""
    by_op = cycles_by_op(hlo)
    _log(f"estimated_cycles "
         f"{sum(c for c, _ in by_op.values()) / 1e6:.2f} M over "
         f"{sum(n for _, n in by_op.values())} instructions; by op_name "
         f"(M cycles, instructions):")
    for op, (cycles, n) in sorted(by_op.items(),
                                  key=lambda kv: -kv[1][0])[:top]:
        _log(f"{cycles / 1e6:10.2f} {n:4d}  {op[-100:] or '(none)'}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="ds2_full")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--frames", type=int, default=800)
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--ndev", type=int, default=1,
                    help="mesh size carved from the topology (data axis)")
    ap.add_argument("--rnn-impl", default="", dest="rnn_impl")
    ap.add_argument("--loss-impl", default="", dest="loss_impl")
    ap.add_argument("--accum", type=int, default=0,
                    help="gradient-accumulation microbatching (>1)")
    ap.add_argument("--objective", default="",
                    help="override train.objective (e.g. rnnt)")
    ap.add_argument("--set", action="append", default=[], dest="overrides",
                    metavar="SECTION.KEY=VALUE",
                    help="override one field of the preset (repeatable),"
                         " e.g. model.moe_rows_bound=0.25")
    ap.add_argument("--compiler-option", action="append", default=[],
                    dest="compiler_options", metavar="K=V",
                    help="TPU-compile-only XLA option (repeatable), e.g. "
                         "xla_tpu_scoped_vmem_limit_kib=24576 — passed "
                         "via compile(compiler_options=...) because "
                         "global XLA_FLAGS is also parsed (and rejected) "
                         "by the cpu runtime client")
    ap.add_argument("--hlo-out", default="",
                    help="dump optimized HLO here, and log its "
                         "estimated_cycles summed per op_name")
    ap.add_argument("--emit-store", default="", metavar="DIR",
                    help="serialize the compiled TRAIN step into this "
                         "warm-store root (utils/aotstore) under the "
                         "portable TPU fingerprint, tier 'train' — a "
                         "tier no serving replica keys by, so train "
                         "executables never preload into a decoder")
    ap.add_argument("--store-version", default="base",
                    help="model-version component of the store key")
    args = ap.parse_args()

    import numpy as np
    import jax
    from jax.experimental import topologies
    from jax.sharding import Mesh

    from deepspeech_tpu.config import apply_overrides, get_config
    from deepspeech_tpu.data.synthetic import synthetic_batch
    from deepspeech_tpu.data.tokenizer import CharTokenizer  # noqa: F401
    from deepspeech_tpu.train import (create_train_state, make_optimizer,
                                      make_train_step, state_shardings)
    from deepspeech_tpu.parallel.mesh import batch_sharding

    t_all = time.time()
    topo = topologies.get_topology_desc(args.topology, "tpu")
    if args.ndev > len(topo.devices):
        raise SystemExit(f"--ndev {args.ndev} > topology devices "
                         f"{len(topo.devices)}")
    mesh = Mesh(np.array(topo.devices[:args.ndev]).reshape(args.ndev, 1),
                ("data", "model"))

    cfg = apply_overrides(get_config(args.preset),
                          dict(kv.split("=", 1) for kv in args.overrides))
    if cfg.decode.mode == "lm_greedy":
        serve_lm(args, cfg, topo)
        return
    model_cfg = cfg.model
    train_cfg = cfg.train
    if args.rnn_impl:
        model_cfg = dataclasses.replace(model_cfg, rnn_impl=args.rnn_impl)
    if args.loss_impl:
        train_cfg = dataclasses.replace(train_cfg, loss_impl=args.loss_impl)
    if args.accum > 1:
        train_cfg = dataclasses.replace(train_cfg, accum_steps=args.accum)
    if args.objective:
        train_cfg = dataclasses.replace(train_cfg,
                                        objective=args.objective)
    # The transducer's lattice is padded to max_label_len, so a preset
    # that trains one keeps its own (rnnt_he2019: 64 word-pieces).
    rnnt = train_cfg.objective == "rnnt"
    lm = train_cfg.objective == "lm"
    max_label_len = cfg.data.max_label_len if rnnt or lm else 160
    cfg = dataclasses.replace(
        cfg, model=model_cfg, train=train_cfg,
        data=dataclasses.replace(cfg.data, batch_size=args.batch,
                                 bucket_frames=(args.frames,),
                                 max_label_len=max_label_len))

    batch, _ = synthetic_batch(cfg, args.batch, args.frames,
                               min(120, max_label_len))
    rng = jax.random.PRNGKey(0)
    optimizer = make_optimizer(cfg, 100)
    # Param init runs EAGERLY on the cpu runtime — keep the on-chip
    # override off for it (a non-interpret pallas_call would be
    # rejected by the cpu backend) and init through the XLA-scan
    # oracle (a forced-pallas init would crawl through the Pallas
    # interpreter at flagship width); param trees are impl-independent.
    os.environ.pop("DS2N_ASSUME_TPU", None)
    cfg_init = dataclasses.replace(
        cfg, model=dataclasses.replace(cfg.model, rnn_impl="xla"))
    _log("initializing params on host...")
    if lm:
        # Half a billion parameters and two moments: shapes only.
        state = jax.eval_shape(
            lambda r: create_train_state(cfg_init, r, batch, optimizer,
                                         mesh=mesh)[1], rng)
    else:
        _, state = create_train_state(cfg_init, rng, batch, optimizer,
                                      mesh=mesh)
    # Rebuild the MODEL with the requested impls for the traced step
    # (construction is cheap; no eager compute happens here).
    if rnnt:
        from deepspeech_tpu.models.transducer import create_rnnt_model
        model = create_rnnt_model(cfg.model, mesh=mesh)
    elif lm:
        from deepspeech_tpu.models.lfm2 import create_lfm2_model
        model = create_lfm2_model(cfg.model, max_label_len)
    else:
        from deepspeech_tpu.models import create_model
        model = create_model(cfg.model, mesh=mesh)
    # From here the step is TRACED, not executed: resolve 'auto' impls
    # and interpret exactly as on the chip (utils/impl.on_tpu), so the
    # lowering emits the Pallas/Mosaic kernels for the v5e target.
    os.environ["DS2N_ASSUME_TPU"] = "1"
    state_sh = state_shardings(mesh, state,
                               zero_opt=cfg.train.zero_opt_sharding)
    step = make_train_step(cfg, model, optimizer, mesh, state_sh)

    state_shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype), state)
    batch_shapes = {k: jax.ShapeDtypeStruct(np.asarray(v).shape,
                                            np.asarray(v).dtype)
                    for k, v in batch.items()}
    batch_sh = {k: batch_sharding(mesh) for k in batch}

    _log(f"lowering + TPU-compiling on {mesh.devices.size} x "
         f"{topo.devices[0].device_kind}...")
    t0 = time.time()
    jitted = jax.jit(step, donate_argnums=0,
                     in_shardings=(state_sh, batch_sh))
    for kv in args.compiler_options:
        if "=" not in kv:
            ap.error(f"--compiler-option needs K=V, got {kv!r}")
    copts = dict(kv.split("=", 1) for kv in args.compiler_options)
    comp = jitted.lower(state_shapes, batch_shapes).compile(
        compiler_options=copts or None)
    compile_s = time.time() - t0

    ma = comp.memory_analysis()
    hbm = {
        "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
        "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
        "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
        "alias_bytes": int(getattr(ma, "alias_size_in_bytes", 0)),
    }
    # Donated state aliases outputs, so live peak ~ args + temp.
    peak = hbm["argument_bytes"] + hbm["temp_bytes"]
    hbm["peak_estimate_bytes"] = peak
    hbm["fits_v5e_16gb"] = bool(peak < V5E_HBM_BYTES * 0.95)

    hlo = comp.as_text()
    colls = count_collectives(hlo)
    if args.hlo_out:
        with open(args.hlo_out, "w") as f:
            f.write(hlo)
        log_cycles(hlo)

    store_row = {}
    if args.emit_store:
        from deepspeech_tpu.utils import aotstore

        store = aotstore.AotStore(
            args.emit_store, fingerprint=aotstore.fingerprint_for("tpu"))
        key = aotstore.StoreKey(args.preset, "train", args.store_version,
                                args.batch, args.frames)
        try:
            blob = aotstore.serialize_compiled(comp)
            path = store.put(
                key, blob, aotstore.FORMAT_EXECUTABLE,
                sig=aotstore.tree_signature((state_shapes, batch_shapes)),
                tool="aot_tpu", topology=args.topology, ndev=args.ndev)
            store_row = {"store_entry": os.path.basename(path),
                         "store_bytes": len(blob)}
        except Exception as e:  # noqa: BLE001 - emission is best-effort
            store_row = {"store_error": f"{type(e).__name__}: "
                                        f"{str(e)[:200]}"}

    ca = comp.cost_analysis() or {}
    flops = ca.get("flops")

    from deepspeech_tpu.utils.flops import ds2_step_flops

    # utils/flops.py knows the DS2 stack only: a transducer step and
    # a decoder-only step get no analytic number here
    # (benchmark/costs/rnnt.py and costs/lfm2.py have theirs).
    analytic = None
    if not (rnnt or lm):
        try:
            analytic = float(ds2_step_flops(
                cfg.model, args.batch, args.frames,
                num_features=cfg.features.num_features))
        except Exception as e:  # keep the compiler numbers either way
            _log(f"analytic flops unavailable: {type(e).__name__}: {e}")

    print(json.dumps({
        "tool": "aot_tpu",
        "preset": args.preset,
        "batch": args.batch,
        "frames": args.frames,
        "impls": (cfg.model.moe_impl if lm else
                  f"{cfg.model.rnn_impl}/{cfg.train.loss_impl}"),
        "objective": cfg.train.objective,
        # Non-default compiles must be reproducible from the row alone
        # (a 'fits' verdict under a raised VMEM budget is not a
        # default-config result).
        "compiler_options": copts,
        "topology": args.topology,
        "ndev": args.ndev,
        "device_kind": str(topo.devices[0].device_kind),
        "compile_s": round(compile_s, 1),
        "total_s": round(time.time() - t_all, 1),
        "hbm": hbm,
        "collectives": colls,
        # Lower bound: scan bodies counted once (see module docstring).
        "xla_flops_lower_bound": flops,
        "analytic_flops_per_step": analytic,
        **store_row,
    }))


if __name__ == "__main__":
    main()
