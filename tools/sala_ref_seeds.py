"""The readings behind ``benchmark/drivers/transcribe_sparse.REF_TOL``:
for each seed, the cell's own comparison
(``transcribe_sparse.ReferenceCheck``: the compiled prefill program and
decode loop on the seeded sample of 2 recordings tiled to the cell's 32
streams with forced tokens, weights from the seed, against the plain
reference at the published widths); then the controls the limits must
fail, each run through ``transcribe_sparse.within`` as the cell runs its
own readings: the reference with float8 (e4m3) weights and the reference
with its linear layers' state carried in bfloat16 (and any other fault
of ``minicpm_sala_ref.FAULTS`` named by ``--controls``) against the
reference. One process, one compile of each program: the weights and
the sample are arguments.

  chiprun -- python3 tools/sala_ref_seeds.py --seeds 101 102 ... \
      --control-seeds 1 > chiprun_out/sala_ref_seeds.jsonl

Prints one JSON line per seed (the readings; for a control seed each
control's readings, its multiples of the limits and whether it came out
``within``) and a last line with the largest reading of each quantity.
A seed is a served call of the whole cell (about half a minute) and a
reference pass; a control is one more reference pass. On the CPU it
runs the configuration file's ``rehearsal`` sizes (``--rehearse``) for
control flow only.
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
    ap.add_argument("--control-seeds", type=int, default=1,
                    help="run the controls on the first N seeds")
    ap.add_argument("--controls", nargs="*",
                    default=["float8_weights", "bf16_state"],
                    help="which faults of minicpm_sala_ref.FAULTS (a "
                         "control is a second full forward of the "
                         "reference)")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    from benchmark import harness
    from benchmark.drivers import transcribe_lm, transcribe_sparse
    from deepspeech_tpu.decode.lm_greedy import LMGreedy
    from deepspeech_tpu.models.lfm2 import seeded_variables
    from deepspeech_tpu.utils.cache import enable_compilation_cache

    enable_compilation_cache()
    load = lambda *p: json.load(open(os.path.join(ROOT, *p)))  # noqa: E731
    cell = "minicpm_sala.transcribe_long_20min_b32"
    ctx = harness.Context(
        cell={"name": cell, "config": "minicpm_sala"},
        config=load("benchmark", "configs", "minicpm_sala.json"),
        traffic=load("benchmark", "traffic",
                     "transcribe_long_20min_b32.json"),
        seed=args.seeds[0], seconds=0, trace=False,
        rehearse=args.rehearse, chips=1, t_process=0.0, peaks=None,
        compiles=None, trace_dir="")
    cfg = transcribe_lm.cell_config(ctx)
    m, w = cfg.model, cfg.decode.lm_watch_rows
    limits = transcribe_sparse.REF_TOL
    differ = transcribe_sparse.REF_CHOSEN_DIFFER

    def judged(read: dict) -> dict:
        return {"errors": read,
                "over_limits": {k: round(x / limits[k], 2)
                                for k, x in read.items() if k in limits},
                "within": transcribe_sparse.within(read, limits, differ)}

    engine = check = None
    worst = {}
    for i, seed in enumerate(args.seeds):
        if engine is not None:  # this seed's weights in the last one's place
            jax.tree.map(lambda x: x.delete(), engine.params)
        params, buffers = seeded_variables(cfg, seed)
        if engine is None:
            engine = LMGreedy(cfg, params, buffers)
            check = transcribe_sparse.ReferenceCheck(
                types.SimpleNamespace(lm_greedy=engine), cfg, ctx)
        engine.params, engine.buffers = params, buffers
        ctx.seed = seed
        out = check.run()
        errs = {k[4:-8]: x for k, x in out.items()
                if k.endswith("_rms_rel")}
        errs["chosen_differ"] = out["ref_chosen_differ_share"]
        line = {"seed": seed, "device": jax.devices()[0].device_kind,
                "errors": errs, "within": out["ref_ok"],
                "steps": out["ref_steps"], "call_s": out["ref_call_s"],
                "counts_ok": out["ref_counts"]}
        if i < args.control_seeds:
            # the cache is released: the reference has the chip's rest
            engine.last_call = engine._cache = None
            sample = transcribe_lm._sample(cfg, ctx)
            last = -(-sample["feat_lens"][:w] // m.frame_stack) \
                + sample["label_lens"][:w]
            ref = lambda f=(): transcribe_sparse.reference(  # noqa: E731
                m, params, sample, w, f)
            want = ref()
            for fault in args.controls:
                line[fault] = judged(transcribe_sparse.errors(
                    transcribe_sparse.reference_as_system(ref((fault,))),
                    want, last, m))
            del want
        for k, x in errs.items():
            worst[k] = max(worst.get(k, 0.0), x)
        print(json.dumps(line), flush=True)
    print(json.dumps({"worst": worst, "seeds": len(args.seeds),
                      "twice_worst": {k: 2 * x for k, x in worst.items()}}),
          flush=True)


if __name__ == "__main__":
    main()
