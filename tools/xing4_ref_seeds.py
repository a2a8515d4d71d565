"""The readings behind ``benchmark/drivers/transcribe_mtp.REF_TOL``: for
each seed, the cell's own comparison (``transcribe_mtp.ReferenceCheck``:
the compiled prefill program and self-drafting loop on the seeded
sample tiled to the cell's batch with forced tokens, weights from the
seed, against the plain reference of model and draft module at the
published widths); then the controls the limits must fail, each run
through ``transcribe_mtp.sound`` as the cell runs its own readings: the
reference with float8 (e4m3) weights (and any other fault of
``xing4_ref.FAULTS`` named by ``--controls``), against the reference.
One process, one compile of each program: the weights and the sample
are arguments.

  chiprun -- python3 tools/xing4_ref_seeds.py --seeds 101 102 ... \
      --control-seeds 2 > chiprun_out/xing4_ref_seeds.jsonl

Prints one JSON line per seed (the readings; for a control seed each
control's readings, its multiples of the limits and whether it came out
``within``) and a last line with the largest reading of each quantity.
On the CPU it runs the configuration file's ``rehearsal`` sizes
(``--rehearse``) for control flow only.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=2,
                    help="run the controls on the first N seeds")
    ap.add_argument("--controls", nargs="*", default=["float8_weights"],
                    help="which faults of xing4_ref.FAULTS (a control "
                         "is a second full forward of the reference)")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    from benchmark import harness
    from benchmark.drivers import transcribe_lm, transcribe_mtp
    from benchmark.reference import xing4_ref
    from deepspeech_tpu.decode.lm_greedy import LMGreedy
    from deepspeech_tpu.models.lfm2 import seeded_variables
    from deepspeech_tpu.utils.cache import enable_compilation_cache

    enable_compilation_cache()
    load = lambda *p: json.load(open(os.path.join(ROOT, *p)))  # noqa: E731
    cell = "xing4_29b_a4b.transcribe_mtp_16s_b256"
    ctx = harness.Context(
        cell={"name": cell, "config": "xing4_29b_a4b"},
        config=load("benchmark", "configs", "xing4_29b_a4b.json"),
        traffic=load("benchmark", "traffic", "transcribe_mtp_16s_b256.json"),
        seed=args.seeds[0], seconds=0, trace=False,
        rehearse=args.rehearse, chips=1, t_process=0.0, peaks=None,
        compiles=None, trace_dir="")
    cfg = transcribe_lm.cell_config(ctx)
    limits = {**transcribe_mtp.REF_TOL,
              "chosen_differ": transcribe_mtp.REF_CHOSEN_DIFFER,
              "h_res_columns": transcribe_mtp.H_RES_COLUMNS,
              "h_res_rows": transcribe_mtp.H_RES_ROWS}
    faults = args.controls

    engine = check = None
    worst = {}
    for i, seed in enumerate(args.seeds):
        if engine is not None:  # this seed's weights in the last one's place
            jax.tree.map(lambda x: x.delete(), engine.params)
        params, buffers = seeded_variables(cfg, seed)
        if engine is None:
            engine = LMGreedy(cfg, params, buffers)
            check = transcribe_mtp.ReferenceCheck(
                types.SimpleNamespace(lm_greedy=engine), cfg, ctx)
        engine.params, engine.buffers = params, buffers
        ctx.seed = seed
        out = check.run()
        errs = {k[4:-8]: x for k, x in out.items()
                if k.endswith("_rms_rel")}
        errs["chosen_differ"] = out["ref_chosen_differ_share"]
        errs["h_res_columns"] = out["ref_h_res_columns_from_one"]
        errs["h_res_rows"] = out["ref_h_res_rows_from_one"]
        line = {"seed": seed, "device": jax.devices()[0].device_kind,
                "errors": errs, "within": out["ref_ok"],
                "steps": out["ref_steps"]}
        if i < args.control_seeds:
            sample = transcribe_lm._sample(cfg, ctx)
            ref = lambda f=(): jax.device_get(xing4_ref.forward(  # noqa: E731
                cfg.model, params, buffers, sample["features"],
                sample["feat_lens"], sample["labels"],
                sample["label_lens"], cfg.model.lfm_seq_positions, f))
            want = ref()
            for fault in faults:
                read = transcribe_mtp.errors(ref((fault,)), want)
                line[fault] = {
                    "errors": read,
                    "over_limits": {k: round(x / limits[k], 2)
                                    for k, x in read.items()},
                    "within": transcribe_mtp.sound(
                        read, transcribe_mtp.REF_TOL,
                        transcribe_mtp.REF_CHOSEN_DIFFER)}
        for k, x in errs.items():
            worst[k] = max(worst.get(k, 0.0), x)
        print(json.dumps(line), flush=True)
    print(json.dumps({"worst": worst, "seeds": len(args.seeds),
                      "twice_worst": {k: 2 * x for k, x in worst.items()}}),
          flush=True)


if __name__ == "__main__":
    main()
