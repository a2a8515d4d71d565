"""AOT-compile the MULTICHIP programs for a real multi-chip v5e target.

Third leg of the offline-TPU-evidence suite (aot_tpu.py = single-chip
step, aot_kernels.py = routed kernels): the driver's dryrun proves the
sharded programs EXECUTE on 8 virtual CPU devices, but the CPU
backend's SPMD partitioner and collective lowering are not the TPU's.
Here FOUR surfaces are lowered and compiled by the REAL XLA-TPU
pipeline against a described topology (``--topology``, default v5e:2x4
= 8 abstract chips; v5e:2x2 is the four-chip host the chip tool gives):

- full train step on a {'data':N/4,'pipe':2,'model':2} mesh — GPipe
  ppermute hops, TP head, ZeRO-1 buffers, gradient psums;
- sp_loss value+grad on a data=N mesh — conv halo exchange, the CTC
  alpha-band relay, and the reverse cotangent relay as TPU collectives;
- sp_beam — beam state relayed across time shards;
- sp_forward — conv halos + recurrence carry relay, decode's substrate.

Shapes mirror the dryrun (tiny: compile VALIDITY is the claim; HBM and
speed at scale are the single-chip tool's and the chip's job). Prints
one JSON line per leg: {leg, ok, compile_s, collectives, error?}.

  JAX_PLATFORMS=cpu python tools/aot_multichip.py [--topology v5e:2x2]

The data-parallel ds2_full step itself (what ``chip_smoke.py
--multichip`` runs) is ``tools/aot_tpu.py --ndev 4``.
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
from _aot_common import count_collectives, log, setup_aot_env  # noqa: E402

setup_aot_env()
_log = functools.partial(log, "aot_multichip")


def _emit(leg: str, t0: float, comp=None, err: Exception | None = None):
    rec = {"leg": leg, "ok": err is None,
           "compile_s": round(time.time() - t0, 1)}
    if comp is not None:
        rec["collectives"] = count_collectives(comp.as_text(),
                                               keep_zero=False)
    if err is not None:
        rec["error"] = f"{type(err).__name__}: {str(err)[:300]}"
    print(json.dumps(rec), flush=True)


def main() -> None:
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh

    from deepspeech_tpu.config import get_config
    from deepspeech_tpu.data.synthetic import synthetic_batch
    from deepspeech_tpu.parallel.mesh import batch_sharding
    from deepspeech_tpu.train import (create_train_state, make_optimizer,
                                      make_train_step, state_shardings)

    ap = argparse.ArgumentParser()
    ap.add_argument("--topology", default="v5e:2x4")
    args = ap.parse_args()
    topo = topologies.get_topology_desc(args.topology, "tpu")
    devs = np.array(topo.devices)
    n = devs.size
    if n % 4:
        raise SystemExit(f"{args.topology}: {n} chips; the pipe=2 x "
                         f"model=2 leg needs a multiple of 4")
    dp = n // 4

    # ---- leg 1: full train step on {'data':dp,'pipe':2,'model':2} ----
    cfg = get_config("dev_slice")
    cfg = dataclasses.replace(
        cfg,
        model=dataclasses.replace(cfg.model, rnn_hidden=64, rnn_layers=3,
                                  conv_channels=(4, 4), vocab_size=32,
                                  dtype="float32", rnn_remat_chunk=4,
                                  pipeline_stages=2,
                                  pipeline_microbatches=2),
        data=dataclasses.replace(cfg.data, batch_size=16,
                                 bucket_frames=(32,), max_label_len=8),
        train=dataclasses.replace(cfg.train, checkpoint_dir="",
                                  mesh_shape=(dp, 2, 2),
                                  zero_opt_sharding=True),
    )
    mesh = Mesh(devs.reshape(dp, 2, 2), ("data", "pipe", "model"))
    batch, _ = synthetic_batch(cfg, 16, 32, 4)
    optimizer = make_optimizer(cfg, 10)
    _log("leg 1: init params (host) + compile pp/tp/zero step...")
    t0 = time.time()
    try:
        model, state = create_train_state(cfg, jax.random.PRNGKey(0),
                                          batch, optimizer, mesh=mesh)
        state_sh = state_shardings(mesh, state, zero_opt=True)
        step = make_train_step(cfg, model, optimizer, mesh, state_sh)
        state_shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x),
                                           np.asarray(x).dtype), state)
        batch_shapes = {k: jax.ShapeDtypeStruct(np.asarray(v).shape,
                                                np.asarray(v).dtype)
                        for k, v in batch.items()}
        batch_sh = {k: batch_sharding(mesh) for k in batch}
        comp = jax.jit(step, donate_argnums=0,
                       in_shardings=(state_sh, batch_sh)) \
            .lower(state_shapes, batch_shapes).compile()
        _emit(f"train_step_dp{dp}_pp2_tp2", t0, comp)
    except Exception as e:
        _emit(f"train_step_dp{dp}_pp2_tp2", t0, err=e)

    # ---- legs 2-4: sequence parallelism over data=n ----
    # Shared setup inside its own try: a seqpar/init regression must
    # still produce one {ok:false} record PER LEG, not a raw traceback
    # that leaves the jsonl short (one line per leg is the contract).
    t0 = time.time()
    try:
        from deepspeech_tpu.models import create_model
        from deepspeech_tpu.parallel.seqpar import (sp_beam_search,
                                                    sp_forward,
                                                    sp_frame_multiple,
                                                    sp_loss)

        sp_mesh = Mesh(devs.reshape(n, 1), ("data", "model"))
        sp_cfg = dataclasses.replace(cfg.model, pipeline_stages=1,
                                     rnn_layers=2)
        sp_model = create_model(sp_cfg)
        t = 10 * sp_frame_multiple(sp_cfg, n)
        feats = np.random.default_rng(0).normal(
            size=(2, t, 161)).astype(np.float32)
        lens = np.asarray([t, t // 2], np.int32)
        variables = sp_model.init(jax.random.PRNGKey(0),
                                  jnp.asarray(feats[:1, :32]),
                                  jnp.asarray(np.asarray([32], np.int32)),
                                  train=False)
        labels = jnp.asarray([[1, 2, 3, 0], [2, 1, 0, 0]], jnp.int32)
        label_lens = jnp.asarray([3, 2], jnp.int32)
    except Exception as e:
        for leg in (f"sp_loss_grad_data{n}", f"sp_beam_data{n}",
                    f"sp_forward_data{n}"):
            _emit(leg, t0, err=e)
        return

    def sp_loss_fn(params, feats_, lens_):
        loss_v, _ = sp_loss(sp_cfg, {**variables, "params": params},
                            feats_, lens_, labels, label_lens, sp_mesh)
        return loss_v

    _log("leg 2: compile sp_loss value+grad over data=n...")
    t0 = time.time()
    try:
        params_shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x),
                                           np.asarray(x).dtype),
            variables["params"])
        comp = jax.jit(jax.value_and_grad(sp_loss_fn)).lower(
            params_shapes,
            jax.ShapeDtypeStruct(feats.shape, feats.dtype),
            jax.ShapeDtypeStruct(lens.shape, lens.dtype)).compile()
        _emit(f"sp_loss_grad_data{n}", t0, comp)
    except Exception as e:
        _emit(f"sp_loss_grad_data{n}", t0, err=e)

    def sp_beam_fn(feats_, lens_):
        return sp_beam_search(sp_cfg, variables, feats_, lens_, sp_mesh,
                              beam_width=4, prune_top_k=8, max_len=16)

    _log("leg 3: compile sp_beam over data=n...")
    t0 = time.time()
    try:
        comp = jax.jit(sp_beam_fn).lower(
            jax.ShapeDtypeStruct(feats.shape, feats.dtype),
            jax.ShapeDtypeStruct(lens.shape, lens.dtype)).compile()
        _emit(f"sp_beam_data{n}", t0, comp)
    except Exception as e:
        _emit(f"sp_beam_data{n}", t0, err=e)

    def sp_fwd_fn(feats_, lens_):
        return sp_forward(sp_cfg, variables, feats_, lens_, sp_mesh)

    _log("leg 4: compile sp_forward over data=n...")
    t0 = time.time()
    try:
        comp = jax.jit(sp_fwd_fn).lower(
            jax.ShapeDtypeStruct(feats.shape, feats.dtype),
            jax.ShapeDtypeStruct(lens.shape, lens.dtype)).compile()
        _emit(f"sp_forward_data{n}", t0, comp)
    except Exception as e:
        _emit(f"sp_forward_data{n}", t0, err=e)


if __name__ == "__main__":
    main()
