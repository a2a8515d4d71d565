"""AOT-compile the composed SERVING path for a real v5e target.

Fourth leg of the offline-TPU-evidence suite: the whole offline
inference program — jitted forward (bf16 Pallas kernels, or int8 PTQ
with the recurrent matrices threaded int8 into the resident q-kernel
via utils/quantize.keep_recurrent_q) composed with on-device greedy
decode — lowered and compiled by the real XLA-TPU + Mosaic pipeline.
This is the `infer --quantize-weights=int8` / `serve` headline path;
its speed is not measured here, only its COMPILE validity and HBM
footprint.

  JAX_PLATFORMS=cpu python tools/aot_infer.py      # bf16 + int8 legs

One JSON line per leg: {leg, ok, compile_s, hbm_peak_bytes, error?}.

`--emit-store <dir>` additionally serializes each leg's compiled
executable into a warm-store (utils/aotstore) under the PORTABLE
v5e fingerprint (`fingerprint_for("tpu")`) so a TPU host restarts
zero-compile from artifacts built on this CPU box: the bf16 leg lands
under tier `fp`, the int8 leg under tier `int8`, both keyed
`(ds2_full, <tier>, --store-version, b8xt800)`. Serialization failure
(e.g. a jaxlib without executable serialization for topology-only
compiles) degrades to the `"hlo"` (jax.export) format, then to a
`store_error` field on the leg's JSON row — never a tool failure.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _aot_common import log, setup_aot_env, shape_tree  # noqa: E402

setup_aot_env()
_log = functools.partial(log, "aot_infer")


def main() -> None:
    import argparse

    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from deepspeech_tpu.config import get_config
    from deepspeech_tpu.data.synthetic import synthetic_batch
    from deepspeech_tpu.models import create_model

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--emit-store", default="", metavar="DIR",
                    help="serialize each leg's executable into this "
                         "warm-store root (portable v5e fingerprint)")
    ap.add_argument("--store-version", default="base",
                    help="model-version component of the store key")
    args = ap.parse_args()

    topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    sh = SingleDeviceSharding(topo.devices[0])

    batch_size, frames = 8, 800
    cfg = get_config("ds2_full")
    batch, _ = synthetic_batch(cfg, batch_size, frames, 120)

    # Host init through the XLA oracle (ASSUME off): params only.
    os.environ.pop("DS2N_ASSUME_TPU", None)
    cfg_init = dataclasses.replace(
        cfg, model=dataclasses.replace(cfg.model, rnn_impl="xla"))
    model_init = create_model(cfg_init.model)
    _log("initializing params on host...")
    variables = model_init.init(
        jax.random.PRNGKey(0), jnp.asarray(batch["features"]),
        jnp.asarray(batch["feat_lens"]), train=False)
    params, stats = variables["params"], variables.get("batch_stats", {})

    # From here everything is TRACED for the v5e target.
    os.environ["DS2N_ASSUME_TPU"] = "1"
    model = create_model(cfg.model)

    feats_s = jax.ShapeDtypeStruct(np.asarray(batch["features"]).shape,
                                   np.float32)
    lens_s = jax.ShapeDtypeStruct((batch_size,), np.int32)

    def emit(leg, t0, comp=None, err=None, extra=None):
        rec = {"leg": leg, "ok": err is None,
               "compile_s": round(time.time() - t0, 1)}
        if comp is not None:
            ma = comp.memory_analysis()
            # Nothing is donated on this path, so live peak includes
            # the outputs (unlike aot_tpu.py's donated-state step).
            rec["hbm_peak_bytes"] = int(
                getattr(ma, "argument_size_in_bytes", 0)
                + getattr(ma, "temp_size_in_bytes", 0)
                + getattr(ma, "output_size_in_bytes", 0))
        if extra:
            rec.update(extra)
        if err is not None:
            rec["error"] = f"{type(err).__name__}: {str(err)[:300]}"
        print(json.dumps(rec), flush=True)

    def emit_store(comp, jitfn, abstract_args, tier, sig_tree):
        """--emit-store leg: xc first, hlo on serialize failure,
        store_error on both failing. Extra fields land on the leg's
        JSON row. ``sig_tree`` is the (params, batch_stats) pair whose
        signature the runtime checks before installing the entry."""
        if not args.emit_store:
            return {}
        import jax.export as jexport

        from deepspeech_tpu.utils import aotstore

        store = aotstore.AotStore(
            args.emit_store,
            fingerprint=aotstore.fingerprint_for("tpu"))
        key = aotstore.StoreKey("ds2_full", tier, args.store_version,
                                batch_size, frames)
        sig = aotstore.tree_signature(sig_tree)
        errs = []
        for fmt, ser in (
                (aotstore.FORMAT_EXECUTABLE,
                 lambda: aotstore.serialize_compiled(comp)),
                (aotstore.FORMAT_EXPORTED,
                 lambda: aotstore.serialize_exported(
                     jexport.export(jitfn)(*abstract_args)))):
            try:
                blob = ser()
                path = store.put(key, blob, fmt, sig=sig,
                                 tool="aot_infer", topology="v5e:2x2")
                _log(f"emitted {fmt} entry "
                     f"{os.path.basename(path)} ({len(blob)} bytes)")
                return {"store_entry": os.path.basename(path),
                        "store_format": fmt,
                        "store_bytes": len(blob)}
            except Exception as e:  # noqa: BLE001 - never fatal
                errs.append(f"{fmt}: {type(e).__name__}: "
                            f"{str(e)[:150]}")
        return {"store_error": "; ".join(errs)}

    def s8_custom_calls(hlo: str) -> int:
        """Custom-call definitions consuming an int8 operand — the
        in-binary signature of the resident q-kernel (its [H, 3H] int8
        weight rides the operand list; a dequant-at-entry program
        feeds the kernels bf16/f32 instead)."""
        return sum(1 for ln in hlo.splitlines()
                   if "tpu_custom_call" in ln and "s8[" in ln)

    # ---- leg 1: bf16 forward + on-device greedy ----
    from deepspeech_tpu.decode.greedy import greedy_decode

    def fwd_greedy(p, bs, feats, lens):
        logits, out_lens = model.apply({"params": p, "batch_stats": bs},
                                       feats, lens, train=False)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return greedy_decode(lp, out_lens)

    t0 = time.time()
    try:
        # in_shardings on the topology device is what retargets the
        # lowering to TPU (without it jit lowers for the cpu runtime
        # and rejects non-interpret pallas_calls).
        jitted = jax.jit(fwd_greedy, in_shardings=(sh, sh, sh, sh))
        abstract = (shape_tree(params), shape_tree(stats), feats_s,
                    lens_s)
        comp = jitted.lower(*abstract).compile()
        # Control for leg 2's in-binary check: the bf16 program has
        # Pallas custom calls but NONE fed by an int8 operand — an s8
        # feed here would mean quantization leaked into the premium
        # tier's program.
        bf16_hlo = comp.as_text()
        n_s8_bf16 = s8_custom_calls(bf16_hlo)
        assert n_s8_bf16 == 0, (
            f"bf16 control leg has {n_s8_bf16} int8-fed custom "
            f"call(s) — quantization leaked into the full-precision "
            f"program")
        emit("infer_greedy_bf16", t0, comp, extra={
            "tpu_custom_calls": bf16_hlo.count('custom_call_target="tpu_custom_call"'),
            "s8_fed_custom_calls": n_s8_bf16,
            **emit_store(comp, jitted, abstract, "fp",
                         (params, stats))})
    except Exception as e:
        emit("infer_greedy_bf16", t0, err=e)

    # ---- leg 2: int8 PTQ forward (resident q-kernel) + greedy ----
    from deepspeech_tpu.utils.quantize import (dequantize_params,
                                               keep_recurrent_q,
                                               quantize_params)

    t0 = time.time()
    try:
        qtree, report = quantize_params(params)
        # PTQ must actually bite before the residency proof means
        # anything: a _QUANT_SUFFIXES regression that matched nothing
        # would "pass" leg 2 with a fully fp program.
        assert report["quantized"] > 0, (
            "quantize_params quantized 0 leaves — PTQ suffix match "
            "regressed")
        keep_q = keep_recurrent_q(cfg.model)
        assert keep_q is not None, (
            "int8-resident regime must engage for the flagship "
            "(rnn_impl resolves pallas under DS2N_ASSUME_TPU, H=1760 "
            "fits the 1-byte budget)")

        def fwd_greedy_q(qp, bs, feats, lens):
            p = dequantize_params(qp, keep=keep_q)
            logits, out_lens = model.apply(
                {"params": p, "batch_stats": bs}, feats, lens,
                train=False)
            lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            return greedy_decode(lp, out_lens)

        jitted_q = jax.jit(fwd_greedy_q, in_shardings=(sh, sh, sh, sh))
        abstract_q = (shape_tree(qtree), shape_tree(stats), feats_s,
                      lens_s)
        comp = jitted_q.lower(*abstract_q).compile()
        hlo = comp.as_text()
        # In-binary residency proof, not just a count: every recurrent
        # q-kernel call site must consume its weight as s8 (14 = 7
        # layers x 2 directions for ds2_full). A keep_recurrent_q
        # regression that silently dequantized at entry would emit the
        # same NUMBER of custom calls, all bf16-fed — caught here.
        n_s8 = s8_custom_calls(hlo)
        assert n_s8 == 2 * cfg.model.rnn_layers, (
            f"expected {2 * cfg.model.rnn_layers} int8-fed q-kernel "
            f"call sites, found {n_s8} — the resident regime did not "
            f"engage")
        emit("infer_greedy_int8_resident", t0, comp, extra={
            "tpu_custom_calls": hlo.count('custom_call_target="tpu_custom_call"'),
            "s8_fed_custom_calls": n_s8,
            "quantized_leaves": report["quantized"],
            **emit_store(comp, jitted_q, abstract_q, "int8",
                         (qtree, stats))})
    except Exception as e:
        emit("infer_greedy_int8_resident", t0, err=e)


if __name__ == "__main__":
    main()
