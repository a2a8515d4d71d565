"""The readings behind ``benchmark/drivers/train_long.REF_TOL``: for
each seed, the cell's own comparison (``train_long.ReferenceCheck``: a
forward-and-backward program of the step's shapes and ONE compiled
``trainer.train_step`` on the seeded recording tiled to the cell's
batch, weights from the seed, against the plain reference at the
published widths); then the control the limits must fail, run through
``train_lfm2.within`` as the cell runs its own readings: the reference
with float8 (e4m3) weights against the reference. One process, one
compile of each program: the weights and the recording are arguments.

  chiprun -- python3 tools/smallthinker_ref_seeds.py --seeds 101 102 ... \
      --control-seeds 2 > chiprun_out/smallthinker_ref_seeds.jsonl

Prints one JSON line per seed (the readings; for a control seed the
control's readings, its multiples of the limits and whether it came
out ``within``) and a last line with the largest reading of each
quantity. On the CPU it runs the configuration file's ``rehearsal``
sizes (``--rehearse``) for control flow only.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=2,
                    help="run the control on the first N seeds")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp

    from benchmark import harness
    from benchmark.drivers import train_lfm2, train_long
    from benchmark.drivers.train import Pipeline
    from benchmark.gen import batches as gen_batches
    from deepspeech_tpu.config import apply_overrides
    from deepspeech_tpu.data import CharTokenizer
    from deepspeech_tpu.train import Trainer, create_train_state
    from deepspeech_tpu.utils.cache import enable_compilation_cache

    enable_compilation_cache()
    load = lambda *p: json.load(open(os.path.join(ROOT, *p)))  # noqa: E731
    config, traffic = "smallthinker_21b_a3b", "train_long_7min"
    ctx = harness.Context(
        cell={"name": f"{config}.{traffic}", "config": config},
        config=load("benchmark", "configs", config + ".json"),
        traffic=load("benchmark", "traffic", traffic + ".json"),
        seed=args.seeds[0], seconds=0, trace=False,
        rehearse=args.rehearse, chips=1, t_process=0.0, peaks=None,
        compiles=None, trace_dir="")
    cfg = harness.model_config(ctx)
    frames = int(ctx.param("bucket_frames"))
    cfg = apply_overrides(cfg, {
        "data.batch_size": int(ctx.param("per_chip_batch")),
        "data.bucket_frames": (frames,),
        "model.lfm_seq_positions": int(ctx.param("seq_positions")),
        "train.checkpoint_dir": "", "train.seed": ctx.seed})
    mcfg, v = cfg.model, cfg.model.vocab_size
    pool = gen_batches.make_batches(
        {k: ctx.param(k) for k in (
            "per_chip_batch", "bucket_frames", "valid_frames",
            "labels_per_frame")} | {"pool_batches": 1},
        seed=ctx.seed, chips=1, vocab_size=v,
        max_label_len=cfg.data.max_label_len,
        num_features=cfg.features.num_features, time_stride=1)

    class Quiet:
        def log(self, event, **fields):
            pass

    trainer = Trainer(cfg, Pipeline(pool, 1),
                      CharTokenizer.synthetic_zh(v - 1), logger=Quiet())
    check = train_long.ReferenceCheck(trainer, cfg, ctx)
    clip = ctx.config["train"]["grad_clip_norm"]
    float8 = jax.jit(lambda p, x: train_long.plain_outputs(
        mcfg, p, x, check.s, clip, None, ("float8_weights",)))
    sums = jax.jit(train_lfm2.leaf_sums)
    limits = {**train_long.REF_TOL,
              "chosen_differ": train_long.REF_CHOSEN_DIFFER}

    worst = {}
    for i, seed in enumerate(args.seeds):
        if i:  # this seed's weights in the place of the last one's
            jax.tree.map(lambda x: x.is_deleted() or x.delete(),
                         trainer.state)
            _, state = create_train_state(
                cfg, jax.random.PRNGKey(seed), pool[0], trainer.optimizer,
                mesh=trainer.mesh)
            trainer.state = jax.device_put(state, trainer.state_sh)
        ctx.seed = seed
        out = check.run()
        errs = {k[4:-8]: x for k, x in out.items()
                if k.endswith("_rms_rel")}
        errs["chosen_differ"] = out["ref_chosen_differ_share"]
        line = {"seed": seed, "device": jax.devices()[0].device_kind,
                "errors": errs, "within": out["ref_ok"],
                "worst_leaves": out["ref_worst_leaves"],
                "rows_routed": out["ref_rows_routed"],
                "row_capacity": out["ref_row_capacity"]}
        if i < args.control_seeds:
            # Room for two more sets of gradients: the moments go.
            jax.tree.map(lambda x: x.delete(), trainer.state.opt_state)
            params = trainer.state.params
            batch = tuple(jnp.asarray(x)
                          for x in train_long.sample(cfg, ctx))
            got = float8(params, batch)
            want = check.plain(params, batch, got["chosen"])
            both = jax.device_get(sums(got.pop("grads"),
                                       want.pop("grads")))
            both = {"grads": both, "step": {"grads": both}}
            read = train_long.errors(mcfg, jax.device_get(got),
                                     jax.device_get(want), both)
            line["float8"] = {
                "errors": read,
                "over_limits": {k: round(x / limits[k], 2)
                                for k, x in read.items()},
                "within": train_lfm2.within(
                    read, train_long.REF_TOL,
                    train_long.REF_CHOSEN_DIFFER)}
        for k, x in errs.items():
            worst[k] = max(worst.get(k, 0.0), x)
        print(json.dumps(line), flush=True)
    print(json.dumps({"worst": worst, "seeds": len(args.seeds)}))


if __name__ == "__main__":
    main()
