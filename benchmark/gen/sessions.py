"""Seeded voice-session traffic in audio time: one general generator,
parameters from a traffic file.

A population of ``capacity`` slots, each alternating a session and a
gap. The session part and the burst chain are a copy of what was sound
in ``deepspeech_tpu/serving/trafficmodel.py`` (clipped log-normal
lengths, a two-state calm/burst Markov chain stepped every
``burst_step_frames``); what that model lacks is added here: sessions
belong to slots, so concurrency is bounded by ``capacity`` by
construction, and joins and leaves land on tick boundaries.

Parameters (``benchmark/traffic/<mix>.json``, driver ``stream``):

  capacity           slots (= the session manager's capacity)
  chunk_frames       frames per tick (64 = 640 ms)
  len_median_frames, len_sigma, len_min_frames, len_max_frames
                     session length, clipped log-normal; drawn in
                     shuffled cycles of LEN_STRATA quantiles, so the
                     audio a window holds depends little on the seed
  gap_mean_frames    mean of the exponential gap between a slot's
                     sessions
  burst_enter_p, burst_exit_p, burst_step_frames, burst_gap_div
                     calm/burst chain; in burst a drawn gap is divided
  drain_frames       frames of lag after a session's last frame before
                     the engine has emitted everything (the slot stays
                     occupied until then)
  pool_chunks        distinct feature chunks to draw audio from

Mechanics, per tick k (audio frames [k*C, (k+1)*C)):

  1. a slot whose gap has run out by k*C joins a new session;
  2. a session with fewer than C frames left leaves, its last partial
     chunk being the ``tail`` (possibly empty);
  3. every other attached session feeds one full chunk;
  4. after the tick, a leaving session whose last frame lies
     ``drain_frames`` or more behind the clock is final; its slot's gap
     starts then.

One ``numpy`` Generator seeded with ``--seed``, consumed in slot order
tick by tick: the same seed gives the same schedule, byte for byte.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import List, Tuple

import numpy as np


LEN_STRATA = 64


@dataclasses.dataclass
class Tick:
    index: int
    joins: List[str]                 # sids joining before this tick
    leaves: List[Tuple[str, int]]    # (sid, tail frames) leaving now
    feeds: List[Tuple[str, int]]     # (sid, chunk ordinal) full chunks
    finals: List[str]                # sids final once this tick is done
    frames: int                      # valid audio frames in this tick


@dataclasses.dataclass
class _Slot:
    sid: str = ""
    free_at: int = 0       # frame at which the gap ends (when idle)
    start: int = 0         # session start frame
    length: int = 0        # session length, frames
    fed: int = 0           # frames fed so far
    state: str = "gap"     # gap | live | draining
    serial: int = 0


class SessionTraffic:
    """Iterate ``next_tick()``; ``plans`` keeps every session's
    (start tick, length) for the re-streaming check."""

    def __init__(self, params: dict, *, seed: int):
        self.p = dict(params)
        self.capacity = int(params["capacity"])
        self.chunk = int(params["chunk_frames"])
        self.rng = np.random.default_rng(seed)
        self.tick = 0
        self.burst = 0
        self._burst_step = -1
        self.plans = {}  # sid -> (join tick, length frames, slot)
        self._lengths: List[int] = []
        self.slots = [_Slot() for _ in range(self.capacity)]
        for s in self.slots:  # stagger the first joins
            s.free_at = int(self._gap() * 2.0 * self.rng.random())

    # -- draws ----------------------------------------------------------
    def _length(self) -> int:
        if not self._lengths:
            p = self.p
            z = statistics.NormalDist()
            for i in self.rng.permutation(LEN_STRATA):
                n = int(round(p["len_median_frames"] * math.exp(
                    p["len_sigma"] * z.inv_cdf((i + 0.5) / LEN_STRATA))))
                self._lengths.append(
                    min(max(n, int(p["len_min_frames"])),
                        int(p["len_max_frames"])))
        return self._lengths.pop()

    def _gap(self) -> float:
        g = float(self.rng.exponential(self.p["gap_mean_frames"]))
        return g / self.p["burst_gap_div"] if self.burst else g

    def _advance_burst(self, frame: int) -> None:
        step = frame // int(self.p["burst_step_frames"])
        while self._burst_step < step:
            self._burst_step += 1
            u = float(self.rng.random())
            if self.burst == 0 and u < self.p["burst_enter_p"]:
                self.burst = 1
            elif self.burst == 1 and u < self.p["burst_exit_p"]:
                self.burst = 0

    # -- one tick -------------------------------------------------------
    def next_tick(self) -> Tick:
        c, k = self.chunk, self.tick
        now = k * c
        self._advance_burst(now)
        joins, leaves, feeds, finals = [], [], [], []
        frames = 0
        for i, s in enumerate(self.slots):
            if s.state == "gap" and s.free_at <= now:
                s.serial += 1
                s.sid = f"s{i}.{s.serial}"
                s.start, s.length, s.fed = now, self._length(), 0
                s.state = "live"
                self.plans[s.sid] = (k, s.length, i)
                joins.append(s.sid)
            if s.state == "live":
                left = s.length - s.fed
                if left < c:
                    leaves.append((s.sid, left))
                    s.fed += left
                    frames += left
                    s.state = "draining"
                else:
                    feeds.append((s.sid, s.fed // c))
                    s.fed += c
                    frames += c
        end = now + c
        for s in self.slots:
            if (s.state == "draining"
                    and end >= s.start + s.length
                    + int(self.p["drain_frames"])):
                finals.append(s.sid)
                s.state = "gap"
                s.free_at = end + int(round(self._gap()))
        self.tick += 1
        return Tick(k, joins, leaves, feeds, finals, frames)

    def occupied(self) -> int:
        return sum(s.state != "gap" for s in self.slots)


def chunk_pool(params: dict, *, seed: int, num_features: int
               ) -> np.ndarray:
    """[pool_chunks, chunk_frames, F] float32 feature chunks."""
    rng = np.random.default_rng([seed, 1])
    return rng.standard_normal(
        (int(params["pool_chunks"]), int(params["chunk_frames"]),
         num_features), dtype=np.float32)


def chunk_of(pool: np.ndarray, sid: str, ordinal: int) -> np.ndarray:
    """The ``ordinal``-th chunk of session ``sid``: a fixed walk
    through the pool that starts where the sid says."""
    slot, serial = sid[1:].split(".")
    return pool[(int(slot) * 7 + int(serial) * 13 + ordinal) % len(pool)]
