"""One-shot end-to-end rehearsal of the full CLI call stack.

SURVEY.md §3.1/§3.2 as ONE pipeline, outside pytest: wav files on disk
-> manifest -> native threaded loader -> SortaGrad buckets -> train CLI
(overfit) -> orbax checkpoint -> infer CLI with beam_fused + ARPA LM
fusion -> WER report.

No speech corpus exists in this environment, so the corpus is
synthesized: every character is a 120 ms pure tone at a character-
specific frequency (spaces are silence), which makes the transcripts
genuinely learnable from audio by the conv+GRU stack — a real
acoustic-model rehearsal, not a feature-tensor shortcut. A word-bigram
ARPA LM is estimated from the training transcripts so LM fusion runs
with real weight.

Usage:  python tools/rehearsal.py [--workdir DIR] [--utts 50]
            [--epochs 40] [--keep]

The train and infer CLIs run as child processes; this launcher never
imports jax, so with ``--on-chip`` the children — one after the other —
are the only processes that touch the chip.

Exit code 0 iff the final WER <= --wer-gate (default 0.05).
"""

from __future__ import annotations

import argparse
import collections
import json
import math
import os
import shutil
import subprocess
import sys
import tempfile
import wave

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORDS = ["ace", "bad", "cab", "dance", "each", "fade", "gig", "hash",
         "ink", "jab", "keg", "lamb", "mace", "nab", "oak", "pace",
         "quad", "race", "sack", "tame"]
# Mandarin mode: a 40-char CJK inventory; "words" are 1-2 char
# compounds, no spaces (the spaceless-vocab char-CTC policy,
# BASELINE.json:11). The tokenizer is derived from the corpus by
# resolve_tokenizer and persisted next to the checkpoint.
ZH_CHARS = [chr(0x4E00 + i) for i in range(40)]
RATE = 16000
CHAR_MS = 120


def _char_freq(ch: str) -> float:
    if "a" <= ch <= "z":
        # a..z -> 300..3800 Hz, far enough apart for 161 bins.
        return 300.0 + (ord(ch) - ord("a")) * 135.0
    # CJK inventory: same band, indexed by codepoint offset.
    return 300.0 + (ord(ch) - 0x4E00) % 40 * 87.0


def synth(text: str, rng: np.random.Generator) -> np.ndarray:
    n = int(RATE * CHAR_MS / 1000)
    t = np.arange(n) / RATE
    chunks = []
    for ch in text:
        if ch == " ":
            chunks.append(np.zeros(n, np.float32))
            continue
        tone = np.sin(2 * math.pi * _char_freq(ch) * t)
        # Fade the edges so char boundaries are visible, add light noise.
        env = np.minimum(1.0, np.minimum(np.arange(n), n - np.arange(n))
                         / (0.1 * n))
        chunks.append((0.4 * tone * env).astype(np.float32))
    audio = np.concatenate(chunks)
    audio = audio + rng.normal(0, 0.003, audio.shape).astype(np.float32)
    return np.clip(audio, -1, 1)


def write_wav(path: str, audio: np.ndarray) -> None:
    with wave.open(path, "wb") as w:
        w.setnchannels(1)
        w.setsampwidth(2)
        w.setframerate(RATE)
        w.writeframes((audio * 32767).astype("<i2").tobytes())


def make_corpus(workdir: str, n_utts: int, seed: int = 0,
                lang: str = "en"):
    """Write wavs + manifest; return (manifest_path, transcripts)."""
    rng = np.random.default_rng(seed)
    wav_dir = os.path.join(workdir, "wavs")
    os.makedirs(wav_dir, exist_ok=True)
    if lang == "zh":
        words = ["".join(rng.choice(ZH_CHARS, size=int(rng.integers(1, 3))))
                 for _ in range(24)]
        joiner = ""  # spaceless char CTC
    else:
        words, joiner = WORDS, " "
    lines, texts = [], []
    for i in range(n_utts):
        n_words = int(rng.integers(2, 4))
        text = joiner.join(rng.choice(words) for _ in range(n_words))
        audio = synth(text, rng)
        path = os.path.join(wav_dir, f"utt{i:03d}.wav")
        write_wav(path, audio)
        texts.append(text)
        lines.append({"audio": path, "text": text,
                      "duration": len(audio) / RATE})
    manifest = os.path.join(workdir, "train.jsonl")
    with open(manifest, "w") as f:
        for rec in lines:
            f.write(json.dumps(rec) + "\n")
    return manifest, texts


def estimate_arpa(texts, path: str, order: int = 2) -> None:
    """Word n-gram ARPA (order 2 or 3) with add-one backoff,
    KenLM-style log10. Order 3 exercises the hashed device-fusion
    tables (trigram context; the dense layout also handles it at this
    tiny vocab)."""
    uni = collections.Counter()
    bi = collections.Counter()
    tri = collections.Counter()
    for t in texts:
        words = ["<s>"] + t.split() + ["</s>"]
        uni.update(words)
        bi.update(zip(words, words[1:]))
        if order >= 3:
            tri.update(zip(words, words[1:], words[2:]))
    vocab = sorted(uni) + ["<unk>"]
    n_uni = sum(uni.values()) + len(vocab)
    with open(path, "w") as f:
        f.write("\\data\\\n")
        f.write(f"ngram 1={len(vocab)}\n")
        f.write(f"ngram 2={len(bi)}\n")
        if order >= 3:
            f.write(f"ngram 3={len(tri)}\n")
        f.write("\n\\1-grams:\n")
        for w in vocab:
            p = (uni.get(w, 0) + 1) / n_uni
            f.write(f"{math.log10(p):.4f}\t{w}\t-0.3010\n")
        f.write("\n\\2-grams:\n")
        for (a, b), c in sorted(bi.items()):
            p = c / uni[a]
            bo = "\t-0.3010" if order >= 3 else ""
            f.write(f"{math.log10(p):.4f}\t{a} {b}{bo}\n")
        if order >= 3:
            f.write("\n\\3-grams:\n")
            for (a, b, c3), c in sorted(tri.items()):
                p = c / bi[(a, b)]
                f.write(f"{math.log10(p):.4f}\t{a} {b} {c3}\n")
        f.write("\\end\\\n")


def run_cli(module: str, args, log_path: str,
            on_chip: bool = False, n_virtual_devices: int = 0) -> str:
    """Run a CLI module and return captured stdout.

    Default: scrubbed CPU env (hermetic rehearsals). ``on_chip=True``
    keeps the ambient env, so the child runs on whatever platform jax
    selects there (the TPU, on the chip machine).
    """
    if on_chip:
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [REPO] + [p for p in env.get("PYTHONPATH", "").split(
                os.pathsep) if p])
    else:
        if REPO not in sys.path:
            sys.path.insert(0, REPO)
        from deepspeech_tpu.utils.envscrub import scrubbed_cpu_env

        env = scrubbed_cpu_env(REPO, n_virtual_devices or 1)
    cmd = [sys.executable, "-m", module] + args
    print(f"[rehearsal] $ {' '.join(cmd)}", flush=True)
    proc = subprocess.run(cmd, cwd=REPO, env=env, text=True,
                          stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    with open(log_path, "w") as f:
        f.write(proc.stdout)
    if proc.returncode != 0:
        print(proc.stdout[-4000:])
        raise SystemExit(f"{module} failed rc={proc.returncode}")
    return proc.stdout


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workdir", default="")
    ap.add_argument("--utts", type=int, default=50)
    ap.add_argument("--epochs", type=int, default=120)
    ap.add_argument("--wer-gate", type=float, default=0.05)
    ap.add_argument("--on-chip", action="store_true",
                    help="run train/infer with the ambient (TPU) env "
                         "instead of the scrubbed CPU env — pair with "
                         "--extra=--model.rnn_impl=pallas "
                         "--extra=--train.loss_impl=pallas for the "
                         "on-chip composed-kernel train->ckpt->infer "
                         "proof")
    ap.add_argument("--keep", action="store_true",
                    help="keep the workdir (default: delete on success)")
    ap.add_argument("--augment", action="store_true",
                    help="train with waveform augmentation (data.augment)")
    ap.add_argument("--streaming", action="store_true",
                    help="streaming variant: unidirectional GRU + "
                         "lookahead conv, decoded chunk-by-chunk via "
                         "decode.mode=streaming instead of beam+LM")
    ap.add_argument("--device-lm", action="store_true",
                    help="decode with beam_fused_device: on-device beam "
                         "search with the ARPA LM compiled to a dense "
                         "fusion table (char-level; pairs well with "
                         "--lang zh)")
    ap.add_argument("--lang", choices=["en", "zh"], default="en",
                    help="zh = Mandarin-style spaceless char CTC: corpus-"
                         "derived CJK tokenizer, char-level LM fusion, "
                         "CER gate (the AISHELL workload shape)")
    ap.add_argument("--extra", action="append", default=[],
                    help="extra --section.key=value override appended to "
                         "BOTH the train and infer invocations (e.g. "
                         "--extra=--model.rnn_impl=pallas for the "
                         "on-chip composed-Pallas-step proof)")
    ap.add_argument("--device-lm-impl", choices=["auto", "dense", "hashed"],
                    default="auto",
                    help="fusion-table layout for --device-lm; 'hashed' "
                         "also bumps the estimated ARPA to order 3 so "
                         "the on-device Katz chain exercises trigram "
                         "context (decode.device_lm_impl)")
    ap.add_argument("--sp", action="store_true",
                    help="sequence-parallel leg: TRAIN with "
                         "train.sequence_parallel=true on an 8-virtual-"
                         "device mesh (time sharded, CTC alpha relays) "
                         "and decode with decode.mode=sp_greedy — the "
                         "full long-audio pipeline proof")
    ap.add_argument("--rnnt", action="store_true",
                    help="RNN-T leg (experimental family): TRAIN with "
                         "train.objective=rnnt (causal encoder + "
                         "prediction net + joint, transducer lattice "
                         "loss) and decode with decode.mode=rnnt_greedy")
    args = ap.parse_args()
    if args.rnnt and (args.sp or args.streaming or args.device_lm):
        ap.error("--rnnt pairs with the plain leg only")
    if args.sp and (args.streaming or args.device_lm):
        ap.error("--sp pairs with the plain bidirectional leg only")
    if args.sp and args.on_chip:
        ap.error("--sp needs the 8-virtual-device CPU mesh; the single "
                 "real chip cannot host a multi-shard sequence-parallel "
                 "run")
    if args.device_lm and args.streaming:
        ap.error("--device-lm and --streaming are mutually exclusive "
                 "(streaming mode decodes greedily, no LM)")
    if args.device_lm and args.lang != "zh":
        ap.error("--device-lm rehearses char-level fusion; the en leg "
                 "builds a word-level ARPA that device fusion would "
                 "score via <unk>. Use --lang zh.")

    workdir = args.workdir or tempfile.mkdtemp(prefix="ds2_rehearsal_")
    os.makedirs(workdir, exist_ok=True)
    ckpt_dir = os.path.join(workdir, "ckpt")
    print(f"[rehearsal] workdir={workdir}")

    manifest, texts = make_corpus(workdir, args.utts, lang=args.lang)
    arpa = os.path.join(workdir, "words.arpa")
    # zh: char-level LM — fusion treats each char as a "word"
    # (spaceless vocab policy in infer.py), so the LM is estimated over
    # space-joined characters.
    estimate_arpa([" ".join(t) for t in texts] if args.lang == "zh"
                  else texts, arpa,
                  order=3 if args.device_lm_impl == "hashed" else 2)
    print(f"[rehearsal] corpus: {args.utts} utts, "
          f"{len(set(texts))} unique transcripts; LM: {arpa}")

    overrides = [
        "--model.rnn_hidden=64", "--model.rnn_layers=2",
        "--model.conv_channels=8,8", "--model.dtype=float32",
        "--data.batch_size=10", "--data.bucket_frames=120,180,240",
        "--data.max_label_len=24", "--data.min_duration_s=0.1",
        "--train.optimizer=adamw", "--train.learning_rate=3e-3",
        # dev_slice's DS2-era 1.1x/epoch anneal reaches ~0 by epoch 60;
        # the overfit rehearsal wants a near-flat schedule instead.
        "--train.lr_anneal=1.005",
        "--train.warmup_steps=60", "--train.log_every=25",
        "--train.checkpoint_every_steps=0",
    ] + list(args.extra)
    if args.streaming:
        # The live-serving variant (SURVEY §2 component 7): causal GRU +
        # lookahead conv, later decoded through the chunked engine.
        overrides += ["--model.bidirectional=false",
                      "--model.lookahead_context=8"]
    if args.augment:
        overrides += ["--data.augment=true"]
    if args.rnnt:
        # Transducer family: causal encoder (the prediction net carries
        # the label context), modest widths for the CPU lattice.
        # PREPEND so user --extra overrides survive (later flags win in
        # apply_overrides — same contract as the sp branch).
        overrides = ["--train.objective=rnnt",
                     "--model.bidirectional=false",
                     "--model.rnnt_pred_hidden=48",
                     "--model.rnnt_joint_dim=96"] + overrides
    n_virt = 8 if args.sp else 0
    if args.sp:
        # Buckets must divide by shards * time_stride = 16: swap only
        # the script's own default (a user --extra override survives —
        # later flags win in apply_overrides).
        overrides = [o for o in overrides
                     if o != "--data.bucket_frames=120,180,240"]
        overrides = (["--data.bucket_frames=128,192,256"] + overrides
                     + ["--train.sequence_parallel=true",
                        "--train.mesh_shape=8,1",
                        "--train.loss_impl=jnp"])
    if args.lang == "zh":
        # Tokenizer inventory derives from the manifest transcripts and
        # persists into the checkpoint dir (resolve_tokenizer policy);
        # infer restores it from there.
        overrides += ["--data.language=zh"]
    train_out = run_cli(
        "deepspeech_tpu.train",
        ["--config=dev_slice", f"--data.train_manifest={manifest}",
         f"--train.epochs={args.epochs}",
         f"--train.checkpoint_dir={ckpt_dir}"] + overrides,
        os.path.join(workdir, "train.log"), on_chip=args.on_chip,
        n_virtual_devices=n_virt)
    last_loss = [json.loads(l)["loss"] for l in train_out.splitlines()
                 if l.startswith("{") and '"train_step"' in l][-1]
    print(f"[rehearsal] training done, final logged loss={last_loss:.3f}")

    if args.rnnt:
        decode_args = ["--decode.mode=rnnt_greedy"]
    elif args.sp:
        decode_args = ["--decode.mode=sp_greedy"]
    elif args.streaming:
        decode_args = ["--decode.mode=streaming", "--decode.chunk_frames=64"]
    else:
        mode = "beam_fused_device" if args.device_lm else "beam_fused"
        decode_args = [f"--decode.mode={mode}", "--decode.beam_width=32",
                       f"--decode.lm_path={arpa}", "--decode.lm_alpha=0.4",
                       "--decode.lm_beta=1.0",
                       f"--decode.device_lm_impl={args.device_lm_impl}"]
    infer_out = run_cli(
        "deepspeech_tpu.infer",
        ["--config=dev_slice", f"--manifest={manifest}",
         f"--checkpoint-dir={ckpt_dir}",
         "--data.min_duration_s=0.1"] + decode_args + overrides,
        os.path.join(workdir, "infer.log"), on_chip=args.on_chip,
        n_virtual_devices=n_virt)
    summary = json.loads([l for l in infer_out.splitlines()
                          if '"done"' in l][-1])
    print(f"[rehearsal] WER={summary['wer']:.4f} CER={summary['cer']:.4f} "
          f"n={summary['n_utts']}")
    # Spaceless zh text makes WER an utterance-error rate; CER is the
    # headline Mandarin metric (BASELINE.json:11).
    gate_metric = "cer" if args.lang == "zh" else "wer"
    ok = summary[gate_metric] <= args.wer_gate
    print(json.dumps({"event": "rehearsal_done", "ok": ok,
                      "wer": summary["wer"], "cer": summary["cer"],
                      "loss": last_loss, "workdir": workdir}))
    if ok and not args.keep and not args.workdir:
        shutil.rmtree(workdir, ignore_errors=True)
    raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
