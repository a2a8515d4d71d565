"""Synthetic speech-like data for tests and benchmarks.

No LibriSpeech audio ships in this environment, so the end-to-end tests
(SURVEY.md §4.6 overfit gate) and ``train --synthetic`` run on a deterministic
synthetic task: each "utterance" is a feature sequence whose frames
encode its label sequence through a fixed random linear map plus noise —
learnable by the real model, shaped like real batches.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np

from ..config import Config
from .manifest import Utterance
from .pipeline import Batch, pad_batch
from .tokenizer import CharTokenizer


def synthetic_batch(cfg: Config, batch_size: int, frames: int,
                    label_len: int, seed: int = 0,
                    frames_per_label: int = 8) -> Tuple[Batch, List[List[int]]]:
    """A batch whose features linearly encode repeated label frames."""
    rng = np.random.default_rng(seed)
    v = cfg.model.vocab_size
    f = cfg.features.num_features
    emb = np.random.default_rng(7).normal(size=(v, f)).astype(np.float32)
    feats, labels = [], []
    for i in range(batch_size):
        ln = int(rng.integers(max(label_len // 2, 1), label_len + 1))
        y = rng.integers(1, v, size=ln).tolist()
        t = min(ln * frames_per_label, frames)
        stretch = np.repeat(np.asarray(y), frames_per_label)[:t]
        x = emb[stretch] + 0.1 * rng.normal(size=(t, f)).astype(np.float32)
        feats.append(x.astype(np.float32))
        labels.append(y)
    batch = pad_batch(feats, labels, frames, cfg.data.max_label_len,
                      cfg.model.time_stride)
    return batch, labels


def synthetic_utterances(n: int, seed: int = 0,
                         min_s: float = 1.0, max_s: float = 8.0,
                         tokenizer: CharTokenizer = None) -> List[Utterance]:
    """Manifest-level synthetic utterances (no audio files on disk)."""
    rng = np.random.default_rng(seed)
    words = ["speech", "deep", "tpu", "kernel", "audio", "model", "train"]
    utts = []
    for i in range(n):
        dur = float(rng.uniform(min_s, max_s))
        text = " ".join(rng.choice(words, size=rng.integers(2, 8)))
        utts.append(Utterance(audio=f"synthetic://{i}", text=text,
                              duration=dur))
    return utts
