"""Seeded training batches: one general generator, parameters from a
traffic file.

Parameters (``benchmark/traffic/<mix>.json``, driver ``train``):

  per_chip_batch   utterances per chip per step
  bucket_frames    frames every batch is padded to (one compiled shape)
  valid_frames     [lo, hi]: valid length of each utterance, uniform;
                   stratified, so every batch of every seed holds the
                   same total of valid audio and only the order and the
                   contents change with the seed (a run's throughput
                   must not depend on the seed's luck)
  labels_per_frame label length = round(this * valid frames), clipped
                   to the configuration's ``max_label_len`` and to what
                   CTC can align ((T'-1)//2)
  pool_batches     distinct batches made; the window cycles them

Features are standard-normal float32 (what a normalised spectrogram
looks like to the model), zero past each utterance's length; labels are
uniform over the non-blank classes. Everything comes from one
``numpy`` Generator seeded with ``--seed``, consumed in a fixed order,
so the same seed gives the same bytes.
"""

from __future__ import annotations

import numpy as np

FRAME_SECONDS = 0.01  # FeatureConfig.stride_ms = 10


def make_batches(params: dict, *, seed: int, chips: int, vocab_size: int,
                 max_label_len: int, num_features: int, time_stride: int
                 ) -> list:
    rng = np.random.default_rng(seed)
    rows = int(params["per_chip_batch"]) * chips
    frames = int(params["bucket_frames"])
    lo, hi = params["valid_frames"]
    if not 1 <= lo <= hi <= frames:
        raise ValueError(f"valid_frames {lo, hi} outside 1..{frames}")
    out = []
    for _ in range(int(params["pool_batches"])):
        strata = lo + (hi + 1 - lo) * (np.arange(rows) + 0.5) / rows
        lens = rng.permutation(strata.astype(np.int32))
        feats = rng.standard_normal((rows, frames, num_features),
                                    dtype=np.float32)
        feats *= np.arange(frames)[None, :, None] < lens[:, None, None]
        feasible = (-(-lens // time_stride) - 1) // 2
        label_lens = np.minimum(
            np.round(params["labels_per_frame"] * lens).astype(np.int32),
            np.minimum(max_label_len, feasible)).astype(np.int32)
        labels = rng.integers(1, vocab_size, size=(rows, max_label_len)
                              ).astype(np.int32)
        labels *= np.arange(max_label_len)[None, :] < label_lens[:, None]
        out.append({"features": feats, "feat_lens": lens,
                    "labels": labels, "label_lens": label_lens})
    return out


def audio_seconds(batch: dict) -> float:
    """Seconds of valid (unpadded) audio in one batch."""
    return float(batch["feat_lens"].sum()) * FRAME_SECONDS
