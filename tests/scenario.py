"""What the scenario tests of several subsystems share: the sizes of
the two tiny models that ``conftest.py`` builds (13 features, ladder
edges 64/128, batch 4), a manual clock, a seeded traffic replay, a counter-family sum, the
burst edges of a traffic schedule, and a model-free session manager that keeps a ledger of its chunks.
One copy, here; the fixtures themselves (``tiny_offline``,
``tiny_streaming``, ``obs_lint``) are in ``conftest.py``.
"""

import numpy as np

NF = 13
EDGES = (64, 128)


class ManualClock:
    """Injectable clock: time moves only when a test says so."""

    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


def poisson_requests(n, rps=300.0, seed=0, t_max=max(EDGES)):
    """Seeded traffic: ``(arrivals [s], feats)`` with Poisson arrivals
    at ``rps`` and lengths uniform in [t_max/8, t_max]."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rps, size=n))
    lens = rng.integers(max(t_max // 8, 8), t_max, size=n, endpoint=True)
    return arrivals, [rng.standard_normal((int(k), NF)).astype(np.float32)
                      for k in lens]


def replay(sched, clock, arrivals, reqs, decode_fn=None, tiers=None,
           on_arrival=None):
    """Replay seeded traffic on a manual clock: each request is
    submitted at its arrival time under rid ``q<i>`` (shed requests
    stay shed), ``on_arrival(i)`` runs (a fault, a controller tick) and
    the scheduler is pumped; then the queue drains past every deadline.
    Returns ``sched.results``."""
    from deepspeech_tpu.serving import OverloadRejected

    for i, (t, feat) in enumerate(zip(arrivals, reqs)):
        clock.t = float(t)
        try:
            sched.submit(feat, rid=f"q{i}",
                         **({"tier": tiers[i]} if tiers else {}))
        except OverloadRejected:
            pass
        if on_arrival is not None:
            on_arrival(i)
        sched.pump(decode_fn)
    clock.t += 10.0
    sched.drain(decode_fn)
    return sched.results


def counter_family(tel, family):
    """Sum of one counter family of a ``ServingTelemetry`` over all its
    label sets."""
    return int(sum(v for k, v in tel.counters.items()
                   if k.split("{", 1)[0] == family))


def burst_edges(schedule):
    """``(t, "traffic.burst" | "traffic.calm")`` at every step where a
    ``TrafficModel`` schedule's burst state flips (calm before t=0):
    the events a fault plan's ``on_event`` can arm on."""
    states = [0] + list(schedule.burst_states)
    return [(k * schedule.burst_step_s,
             "traffic.burst" if states[k + 1] else "traffic.calm")
            for k in range(len(states) - 1) if states[k + 1] != states[k]]


def solo_decode(inf, feat):
    """One request alone through ``inf``: the identity reference."""
    return inf.decode_batch_bucketed(
        {"features": feat[None],
         "feat_lens": np.full((1,), len(feat), np.int32)})[0]


class ChunkLogManager:
    """Duck-typed streaming session manager over a shared chunk log: a
    left session finalizes at once, so "no chunk lost" is an equality
    on ``final``; sessions can be handed to a peer (the snapshot
    surface ``MigrationController`` needs)."""

    def __init__(self, log):
        self.log, self.active, self.done = log, {}, {}

    def join(self, sid, raw_len=None):
        self.active[sid] = []

    def leave(self, sid, tail=None):
        self.done[sid] = " ".join(self.active.pop(sid))

    def step(self, chunks):
        for sid, c in chunks.items():
            self.active[sid].append(str(c))
            self.log.append((sid, str(c)))
        return {sid: " ".join(v) for sid, v in self.active.items()}

    def flush(self):
        pass

    def final(self, sid):
        return self.done[sid]

    def stats(self):
        return {"active": len(self.active), "draining": 0}

    def snapshot_fingerprint(self):
        return "chunklog-v1"

    def export_session(self, sid):
        return ("chunklog", sid, self.active.pop(sid))

    def import_session(self, snap, sid=None):
        _, orig, chunks = snap
        self.active[sid or orig] = chunks
