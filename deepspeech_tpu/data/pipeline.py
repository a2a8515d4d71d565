"""Host data pipeline: manifest -> featurized, padded, bucketed batches.

Replaces the reference's prefetch-worker loader (SURVEY.md §2 component 4)
with two overlap stages: a background thread that featurizes/pads batch
k+1 while batch k computes (``epoch``'s queue), and a double-buffered
``device_prefetch`` wrapper that issues the host->device transfer of
batch k+1 while the device is still busy with batch k — so neither the
featurization nor the PCIe/ICI copy sits on the step's critical path.

Batch contract (SURVEY.md §1 L1): dict of
  features   [B, T_bucket, F] float32
  feat_lens  [B]              int32   (frames before padding)
  labels     [B, L_max]       int32   (blank=0 padded)
  label_lens [B]              int32

Corrupt-sample quarantine (``DataConfig.quarantine_corrupt``, on by
default): a sample with non-finite features, an empty label, or a
label longer than its frames can carry (the CTC T' >= 2L+1 bound)
never reaches the device — its batch row is replaced by a healthy
donor row (shapes unchanged), the event is counted
(``samples_quarantined{trigger=...}``) and written as a
``corrupt_sample`` postmortem record. The ``corrupt_batch`` fault kind
injects exactly this damage at the ``pipeline.materialize`` point.
"""

from __future__ import annotations

import queue
import threading
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from .. import obs
from ..config import Config
from ..resilience import faults
from ..resilience import postmortem as _postmortem
from .features import featurize_np, load_audio, num_frames
from .manifest import Utterance, load_manifest
from .sampler import BatchPlan, SortaGradSampler
from .tokenizer import CharTokenizer


Batch = Dict[str, np.ndarray]


def device_prefetch(batches, put_fn=None, depth: int = 2):
    """Double-buffer host batches onto the device.

    Issues ``put_fn`` (default ``jax.device_put``) for batch k+1 before
    yielding batch k: transfers are async dispatches, so the copy of
    the NEXT batch rides along while the device computes the current
    one. ``depth=2`` is true double buffering (one in flight, one being
    consumed); deeper only helps if transfers are slower than steps.
    Works on any batch iterator — the training loop wraps it around
    ``DataPipeline.epoch`` with ``put_fn=shard_batch``, the infer loop
    around its ``(batch, n_valid)`` stream with a features-only put.
    """
    if depth < 1:
        raise ValueError(f"device_prefetch depth must be >= 1, got {depth}")
    if put_fn is None:
        import jax

        put_fn = jax.device_put
    from collections import deque

    from .. import obs
    from ..resilience import faults

    _end = object()
    it = iter(batches)
    buf: "deque" = deque()
    while True:
        # Spans split the host side of the step: how long the producer
        # (featurize/assemble) made us wait vs. how long the put/shard
        # dispatch took. Transfers are async, so the device copy itself
        # overlaps compute — the transfer span is dispatch cost only.
        with obs.span("pipeline.data_wait"):
            b = next(it, _end)
        if b is _end:
            break
        with obs.span("pipeline.device_prefetch"):
            # Chaos hook: an installed FaultPlan can stall the transfer
            # (kind "latency" — an I/O spike) or fail it outright.
            faults.inject("pipeline.device_prefetch")
            buf.append(put_fn(b))
        if len(buf) >= depth:
            yield buf.popleft()
    while buf:
        yield buf.popleft()


def pad_batch(features: List[np.ndarray], labels: List[List[int]],
              bucket_frames: int, max_label_len: int,
              time_stride: int) -> Batch:
    """Pad a list of [T_i, F] features + label lists to static shapes.

    Enforces the CTC feasibility constraint T' >= 2L+1 where
    T' = frames // time_stride (SURVEY.md §3.4): labels are clipped to
    the longest feasible length; utterances violating it should have
    been filtered upstream, so this is a belt-and-braces guard.
    """
    b = len(features)
    f = features[0].shape[1]
    feats = np.zeros((b, bucket_frames, f), dtype=np.float32)
    feat_lens = np.zeros((b,), dtype=np.int32)
    labs = np.zeros((b, max_label_len), dtype=np.int32)
    lab_lens = np.zeros((b,), dtype=np.int32)
    for i, (x, y) in enumerate(zip(features, labels)):
        t = min(x.shape[0], bucket_frames)
        feats[i, :t] = x[:t]
        feat_lens[i] = t
        # Output frames use SAME padding: T' = ceil(t / stride), matching
        # models.conv.conv_out_lens.
        max_feasible = max(((-(-t // time_stride)) - 1) // 2, 0)
        y = y[:min(len(y), max_label_len, max_feasible)]
        labs[i, :len(y)] = y
        lab_lens[i] = len(y)
    return {"features": feats, "feat_lens": feat_lens,
            "labels": labs, "label_lens": lab_lens}


def _max_feasible_labels(frames: int, bucket_frames: int,
                         time_stride: int) -> int:
    """CTC feasibility bound for one utterance: the longest label a
    ``frames``-frame sample (clipped to the bucket) can align."""
    t = min(int(frames), bucket_frames)
    return max(((-(-t // time_stride)) - 1) // 2, 0)


def _quarantine(i: int, trigger: str, *, ids, step, registry, pm,
                **stats) -> None:
    """Count + record one quarantined sample."""
    reg = registry if registry is not None else obs.registry()
    reg.count("samples_quarantined")
    reg.count("samples_quarantined", labels={"trigger": trigger})
    writer = pm if pm is not None else _postmortem.writer()
    utt = str(ids[i]) if ids is not None and i < len(ids) else str(i)
    writer.write("corrupt_sample", trigger, utt=utt, row=int(i),
                 step=step, **stats)


def scrub_samples(feats: List[np.ndarray], labels: List[List[int]], *,
                  bucket_frames: int, max_label_len: int,
                  time_stride: int, ids: Optional[Sequence] = None,
                  step: Optional[int] = None, enabled: bool = True,
                  registry=None, pm=None
                  ) -> Tuple[List[np.ndarray], List[List[int]], int]:
    """Chaos hook + corrupt-sample quarantine over per-utterance lists
    (the path in front of :func:`pad_batch`).

    Flags non-finite features, empty labels, and labels longer than
    their frames can carry; each flagged sample's row is replaced by
    the first healthy sample (batch shape and size unchanged). If the
    entire batch is corrupt, features are sanitized in place
    (``nan_to_num``) and labels clipped — degraded but trainable beats
    a dead run. Returns ``(feats, labels, n_quarantined)``.

    The ``pipeline.materialize`` injection point fires here: kind
    ``corrupt_batch`` poisons sample 0's features with NaN *before*
    the scan — with quarantine on, the scrubber catches it; with
    quarantine off, the poison flows downstream for the training
    guardian to absorb.
    """
    feats = list(feats)
    labels = list(labels)
    spec = faults.inject("pipeline.materialize")
    if spec is not None and spec.kind == "corrupt_batch" and feats:
        feats[0] = np.full_like(feats[0], np.nan)
    if not enabled or not feats:
        return feats, labels, 0

    def problem(x: np.ndarray, y: List[int]) -> Optional[str]:
        if not np.isfinite(x).all():
            return "nonfinite_features"
        if len(y) == 0:
            return "empty_label"
        if min(len(y), max_label_len) > _max_feasible_labels(
                x.shape[0], bucket_frames, time_stride):
            return "overlong_label"
        return None

    problems = [problem(x, y) for x, y in zip(feats, labels)]
    donor = next((i for i, p in enumerate(problems) if p is None), None)
    n_bad = 0
    for i, p in enumerate(problems):
        if p is None:
            continue
        n_bad += 1
        _quarantine(i, p, ids=ids, step=step, registry=registry, pm=pm,
                    frames=int(feats[i].shape[0]),
                    label_len=int(len(labels[i])))
        if donor is not None:
            feats[i] = feats[donor]
            labels[i] = labels[donor]
        else:
            feats[i] = np.nan_to_num(feats[i], copy=True,
                                     posinf=0.0, neginf=0.0)
            labels[i] = labels[i][:_max_feasible_labels(
                feats[i].shape[0], bucket_frames, time_stride)]
    return feats, labels, n_bad


def scrub_padded_batch(batch: Batch, *,
                       ids: Optional[Sequence] = None,
                       step: Optional[int] = None, enabled: bool = True,
                       registry=None, pm=None) -> Tuple[Batch, int]:
    """Quarantine scan over an already-padded batch dict (the native
    loader's output, and synthetic/bench streams). Same policy as
    :func:`scrub_samples`, minus the overlong-label check — padding
    already clipped labels to feasibility, so the post-clip symptom is
    an empty label. Mutates ``batch`` rows in place (callers own their
    batch dicts); returns ``(batch, n_quarantined)``."""
    spec = faults.inject("pipeline.materialize")
    feats = batch["features"]
    if spec is not None and spec.kind == "corrupt_batch" \
            and len(feats):
        feats[0] = np.nan
    if not enabled or not len(feats):
        return batch, 0
    finite = np.isfinite(feats).all(axis=tuple(range(1, feats.ndim)))
    empty = np.asarray(batch["label_lens"]) == 0
    bad = ~finite | empty
    if not bad.any():
        return batch, 0
    donors = np.flatnonzero(~bad)
    donor = int(donors[0]) if len(donors) else None
    n_bad = 0
    for i in np.flatnonzero(bad):
        i = int(i)
        n_bad += 1
        trigger = "nonfinite_features" if not finite[i] else "empty_label"
        _quarantine(i, trigger, ids=ids, step=step, registry=registry,
                    pm=pm, frames=int(batch["feat_lens"][i]),
                    label_len=int(batch["label_lens"][i]))
        if donor is not None:
            for k in batch:
                batch[k][i] = batch[k][donor]
        else:
            feats[i] = np.nan_to_num(feats[i], posinf=0.0, neginf=0.0)
    return batch, n_bad


class DataPipeline:
    """End-to-end host pipeline for one manifest."""

    # Cache featurized utterances only for small (overfit-slice-sized)
    # datasets; a 960h corpus would accumulate hundreds of GB.
    MAX_CACHED_UTTS = 2048

    def __init__(self, cfg: Config, tokenizer: CharTokenizer,
                 manifest_path: Optional[str] = None,
                 utterances: Optional[List[Utterance]] = None,
                 prefetch: int = 2, cache: Optional[bool] = None):
        """``cache``: override the size heuristic for the feature cache
        (None = cache iff the corpus fits MAX_CACHED_UTTS). cache=False
        forces the big-corpus path — fresh featurization per batch via
        the native loader when available — the real host-input cost
        at any size."""
        self.cfg = cfg
        self.tokenizer = tokenizer
        if utterances is None:
            utterances = load_manifest(
                manifest_path, cfg.data.min_duration_s, cfg.data.max_duration_s)
        self.utts = utterances
        frames_per_sec = 1000.0 / cfg.features.stride_ms
        self.sampler = SortaGradSampler(
            [u.duration for u in self.utts], frames_per_sec,
            cfg.data.bucket_frames, cfg.data.batch_size,
            sortagrad=cfg.data.sortagrad, seed=cfg.data.shuffle_seed)
        self.prefetch = prefetch
        self._cache: Dict[int, np.ndarray] = {}
        self._cache_enabled = (len(self.utts) <= self.MAX_CACHED_UTTS
                               if cache is None else cache)
        # Native C++ loader (threaded wav->features, GIL-free): engaged
        # for big uncached corpora, where per-batch featurization is on
        # the training critical path; small cached sets featurize once
        # through numpy and hit the cache thereafter.
        self._native = False
        if cfg.data.native_loader and not self._cache_enabled:
            from .. import native

            self._native = native.available()

    def _features_for(self, idx: int) -> np.ndarray:
        if idx in self._cache:
            return self._cache[idx]
        audio = load_audio(self.utts[idx].audio,
                           self.cfg.features.sample_rate)
        feats = featurize_np(audio, self.cfg.features)
        if self._cache_enabled:
            self._cache[idx] = feats
        return feats

    def _materialize(self, plan: BatchPlan,
                     epoch: Optional[int] = None) -> Batch:
        """Materialize a batch plan; multi-process jobs build only the
        rows this process owns (the rest stay zero — ``shard_batch``
        assembles the global array from each process's rows).
        ``epoch`` is set for training batches and keys the (optional)
        waveform augmentation; None (eval/peek) never augments."""
        import jax

        b = len(plan.indices)
        if jax.process_count() > 1:
            from ..parallel.mesh import process_local_span

            lo, hi = process_local_span(b)
            if (lo, hi) != (0, b):
                sub = BatchPlan(plan.indices[lo:hi], plan.bucket_frames,
                                plan.bucket)
                local = self._materialize_local(sub, epoch)
                out = {k: np.zeros((b,) + v.shape[1:], v.dtype)
                       for k, v in local.items()}
                for k, v in local.items():
                    out[k][lo:hi] = v
                return out
        return self._materialize_local(plan, epoch)

    def _utt_ids(self, plan: BatchPlan) -> List[str]:
        return [self.utts[int(i)].audio or str(int(i))
                for i in plan.indices]

    def _materialize_local(self, plan: BatchPlan,
                           epoch: Optional[int] = None) -> Batch:
        labels = [self.tokenizer.encode(self.utts[int(i)].text)
                  for i in plan.indices]
        augment = self.cfg.data.augment and epoch is not None
        spec_aug = self.cfg.data.spec_augment and epoch is not None
        quarantine = self.cfg.data.quarantine_corrupt
        if self._native and not augment:
            # Feature-domain masking composes with the native loader's
            # batch output (only waveform augment needs fresh
            # featurization): mask the valid rows in place.
            batch = self._materialize_native(plan, labels)
            if batch is not None:
                if spec_aug:
                    from .augment import spec_augment_features

                    for r, i in enumerate(plan.indices):
                        n = int(batch["feat_lens"][r])
                        spec_augment_features(
                            batch["features"][r, :n],
                            self.cfg.data.shuffle_seed, epoch, int(i),
                            copy=False)
                batch, _ = scrub_padded_batch(
                    batch, ids=self._utt_ids(plan), enabled=quarantine)
                return batch
        if augment:
            from .augment import augment_audio

            feats = []
            for i in plan.indices:
                i = int(i)
                audio = load_audio(self.utts[i].audio,
                                   self.cfg.features.sample_rate)
                audio = augment_audio(audio, self.cfg.features.sample_rate,
                                      self.cfg.data.shuffle_seed, epoch, i)
                feats.append(featurize_np(audio, self.cfg.features))
        else:
            feats = [self._features_for(int(i)) for i in plan.indices]
        if spec_aug:
            from .augment import spec_augment_features

            # Truncate to the bucket BEFORE masking so mask draws and
            # the fill mean see exactly the frames that survive
            # pad_batch — keeps native and numpy paths identical for
            # over-length utterances.
            feats = [spec_augment_features(f[:plan.bucket_frames],
                                           self.cfg.data.shuffle_seed,
                                           epoch, int(i))
                     for f, i in zip(feats, plan.indices)]
        feats, labels, _ = scrub_samples(
            feats, labels, bucket_frames=plan.bucket_frames,
            max_label_len=self.cfg.data.max_label_len,
            time_stride=self.cfg.model.time_stride,
            ids=self._utt_ids(plan), enabled=quarantine)
        return pad_batch(feats, labels, plan.bucket_frames,
                         self.cfg.data.max_label_len,
                         self.cfg.model.time_stride)

    def _materialize_native(self, plan: BatchPlan,
                            labels: List[List[int]]) -> Optional[Batch]:
        """Batch wav->features through the C++ thread pool.

        Returns None (caller falls back to numpy) when any utterance is
        not a .wav file or fails to parse natively.
        """
        from .. import native

        paths = [self.utts[int(i)].audio for i in plan.indices]
        if not all(p.endswith(".wav") for p in paths):
            return None
        feats, frames = native.load_featurize_batch(
            paths, self.cfg.features, max_frames=plan.bucket_frames)
        if np.any(frames < 0):
            return None
        b = len(paths)
        labs = np.zeros((b, self.cfg.data.max_label_len), dtype=np.int32)
        lab_lens = np.zeros((b,), dtype=np.int32)
        stride = self.cfg.model.time_stride
        for i, y in enumerate(labels):
            t = int(frames[i])
            max_feasible = max(((-(-t // stride)) - 1) // 2, 0)
            y = y[:min(len(y), self.cfg.data.max_label_len, max_feasible)]
            labs[i, :len(y)] = y
            lab_lens[i] = len(y)
        return {"features": feats, "feat_lens": frames.astype(np.int32),
                "labels": labs, "label_lens": lab_lens}

    def peek(self) -> Batch:
        """First epoch-0 batch, materialized synchronously (no worker)."""
        plan = next(iter(self.sampler.epoch(0)))
        return self._materialize(plan)

    def eval_epoch(self) -> Iterator[Tuple[Batch, int]]:
        """Yield (batch, n_valid) covering EVERY utterance exactly once.

        Unlike training epochs, partial trailing batches are not dropped:
        the last batch of each bucket is padded by repeating its final
        utterance and ``n_valid`` tells the caller how many rows count.
        """
        order = np.argsort(self.sampler.frames, kind="stable")
        order = order[self.sampler._valid[order]]
        by_bucket: Dict[int, List[int]] = {}
        for i in order:
            by_bucket.setdefault(int(self.sampler.bucket_of[i]), []).append(int(i))
        bs = self.cfg.data.batch_size
        for b, members in sorted(by_bucket.items()):
            for start in range(0, len(members), bs):
                chunk = members[start:start + bs]
                n_valid = len(chunk)
                chunk = chunk + [chunk[-1]] * (bs - n_valid)
                plan = BatchPlan(np.asarray(chunk, np.int64),
                                 self.sampler.bucket_frames[b], b)
                yield self._materialize(plan), n_valid

    def epoch(self, epoch_idx: int) -> Iterator[Batch]:
        """Batches for one epoch, with background prefetch."""
        plans = self.sampler.epoch(epoch_idx)
        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch)
        stop = object()

        def worker():
            try:
                for plan in plans:
                    q.put(self._materialize(plan, epoch=epoch_idx))
                q.put(stop)
            except BaseException as e:  # re-raised in the consumer
                q.put(e)

        t = threading.Thread(target=worker, daemon=True)
        t.start()
        while True:
            item = q.get()
            if item is stop:
                break
            if isinstance(item, BaseException):
                raise item
            yield item

    def batches_per_epoch(self, epoch_idx: int) -> int:
        return self.sampler.batches_per_epoch(epoch_idx)
