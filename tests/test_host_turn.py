"""The host's turn opened up (``obs/trace.py``): child spans inside
``train.step``, ``train.log``, ``infer.transcribe``, ``infer.prefill``
and ``infer.decode``, garbage collections as ``host.gc`` spans, and the
served call's always-on ``host_s`` phases.

Real loops at toy sizes on the CPU (``Trainer.fit`` of the ``ctc`` and
the ``lm`` objective, ``LMGreedy.transcribe``), the process-wide tracer
under an injected clock that ticks a millisecond a reading, so that
every span has its own start and the order of the records is the order
of the code. Nothing here reads a wall clock.
"""

import dataclasses
import gc
import io
import json
import os
import sys
import time
import types

import jax
import numpy as np
import pytest

from deepspeech_tpu import obs
from deepspeech_tpu.obs.metrics import MetricsRegistry
from deepspeech_tpu.obs.trace import _NOOP, Tracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TRAIN_CHILDREN = {
    "train.step": ["train.dispatch", "train.wait"],
    "train.log": ["train.sync", "train.lr", "train.fetch", "train.emit"],
}
INFER_CHILDREN = {
    "infer.transcribe": ["infer.cache", "infer.prefill", "infer.prefill",
                         "infer.decode"],
    "infer.prefill": ["infer.prefill.dispatch", "infer.prefill.wait"],
    "infer.decode": ["infer.decode.dispatch", "infer.decode.fetch"],
}
NEW_NAMES = sorted({c for kids in (*TRAIN_CHILDREN.values(),
                                   *INFER_CHILDREN.values())
                    for c in kids if c.count(".") > 1 or
                    c.startswith("train.") or c == "infer.cache"})
HOST_S = {"cache", "prefill_dispatch", "decode_dispatch", "fetch",
          "to_ids"}


class Ticking:
    """A clock that has moved on a millisecond whenever it is read."""

    def __init__(self):
        self.t = 1000.0

    def __call__(self) -> float:
        self.t += 1e-3
        return self.t


class Blocks:
    """Counts the loop's ``jax.block_until_ready`` calls."""

    def __init__(self):
        self.n = 0
        self.real = jax.block_until_ready

    def __call__(self, x):
        self.n += 1
        return self.real(x)


def run(fn, enabled: bool):
    """``fn()`` with the process-wide tracer on or off: the records it
    wrote, the registry it fed, the hooks ``gc`` holds before and
    after, how often the code blocked, and what ``fn`` returned."""
    clk, sink, reg, blocks = Ticking(), io.StringIO(), MetricsRegistry(), \
        Blocks()
    hooks = list(gc.callbacks)
    obs.configure(enabled=enabled, sink=sink, registry=reg, clock=clk,
                  wall=clk)
    jax.block_until_ready = blocks
    try:
        out = fn()
    finally:
        jax.block_until_ready = blocks.real
        obs.configure(enabled=False, registry=obs.registry(),
                      clock=time.perf_counter, wall=time.time)
    return types.SimpleNamespace(
        recs=[json.loads(line) for line in sink.getvalue().splitlines()],
        text=sink.getvalue(), registry=reg, out=out, blocks=blocks.n,
        hooks_before=hooks, hooks_after=list(gc.callbacks))


def ctc_trainer(hidden=16, layers=1, channels=(4, 4), frames=64,
                n_utts=24):
    """``dev_slice`` cut to one GRU-16 layer, three steps an epoch
    (or as wide and as long as asked)."""
    from deepspeech_tpu.config import get_config
    from deepspeech_tpu.data import CharTokenizer
    from deepspeech_tpu.train import Trainer, _SyntheticPipeline
    from deepspeech_tpu.utils.logging import JsonlLogger

    cfg = get_config("dev_slice")
    cfg = dataclasses.replace(
        cfg,
        model=dataclasses.replace(cfg.model, rnn_hidden=hidden,
                                  rnn_layers=layers,
                                  conv_channels=channels,
                                  dtype="float32"),
        data=dataclasses.replace(cfg.data, batch_size=8,
                                 bucket_frames=(frames,),
                                 max_label_len=16),
        train=dataclasses.replace(cfg.train, checkpoint_dir="",
                                  log_every=1, warmup_steps=10))
    pipe = _SyntheticPipeline(cfg, n_utts=n_utts, frames=frames,
                              label_len=4)
    return Trainer(cfg, pipe, CharTokenizer.english(),
                   logger=JsonlLogger(echo=False))


def lm_trainer():
    """The toy decoder-only trainer of ``tests/test_lfm2.py``: two steps
    an epoch, routing counters in the step's metrics."""
    from test_lfm2 import toy_trainer

    return toy_trainer(**{"train.epochs": 1})[1]


@pytest.fixture(scope="module", params=["ctc", "lm"])
def training(request):
    """One epoch traced, then the next with the tracer off, of the
    same ``Trainer`` (``fit`` skips what it has consumed)."""
    trainer = {"ctc": ctc_trainer, "lm": lm_trainer}[request.param]()
    steps = trainer.pipeline.batches_per_epoch(0)
    on = run(lambda: trainer.fit(1), enabled=True)
    off = run(lambda: trainer.fit(2), enabled=False)
    return types.SimpleNamespace(on=on, off=off, steps=steps,
                                 objective=request.param)


@pytest.fixture(scope="module")
def serving():
    """Two traced calls, then one with the tracer off, of one toy
    engine (``tests/test_axk1.py``: 4 streams, sub-batches of 2)."""
    from test_axk1 import batch, init, toy

    from deepspeech_tpu.decode.lm_greedy import LMGreedy

    cfg, b = toy(), batch()
    engine = LMGreedy(cfg, init(cfg, b), {})

    def call():
        return engine.transcribe(b[0], b[1])["stats"]

    on = run(lambda: [call(), call()], enabled=True)
    off = run(lambda: [call()], enabled=False)
    return types.SimpleNamespace(on=on, off=off, calls=2)


def program_children(recs, parent):
    """The records of ``parent``'s direct children the program opened
    (jax's own phases and collections land where they happen), by
    start."""
    kids = [r for r in recs if r["parent"] == parent["id"]
            and r["name"] != "host.gc" and not r["name"].startswith("jax.")]
    return sorted(kids, key=lambda r: r["ts"])


def check_family(recs, parent_name, want, key, units, whose=None):
    """``want``: the children's names, or a function of the parent's
    ordinal that gives them; ``whose``: the ``key`` a child carries
    (its parent's, if not given)."""
    whose = whose or (lambda parent, kid: parent[key])
    parents = [r for r in recs if r["name"] == parent_name]
    assert len(parents) == units
    for i, parent in enumerate(parents):
        kids = program_children(recs, parent)
        assert [k["name"] for k in kids] == (
            want(i) if callable(want) else want)
        assert all(k[key] == whose(parent, k) for k in kids)
        # In order, one after the other, inside the parent.
        ends = [k["ts"] + k["dur_ms"] / 1e3 for k in kids]
        assert parent["ts"] < kids[0]["ts"]
        assert all(e < k["ts"] for e, k in zip(ends, kids[1:]))
        assert ends[-1] < parent["ts"] + parent["dur_ms"] / 1e3
        assert sum(k["dur_ms"] for k in kids) < parent["dur_ms"]
    return parents


@pytest.mark.parametrize("parent", sorted(TRAIN_CHILDREN))
def test_training_children_in_order_under_their_parent(training, parent):
    want = TRAIN_CHILDREN[parent]
    # The loop hands step k+1 over before it reads step k: the traced
    # wait under train.step k+1 is on step k and carries k, and the
    # first step of a fit has nothing to wait on.
    parents = check_family(
        training.on.recs, parent,
        (lambda i: want[:1] if i == 0 else want)
        if parent == "train.step" else want,
        "step", training.steps,
        whose=lambda p, kid: p["step"] - (kid["name"] == "train.wait"))
    # train.step carries the step it runs, train.log the steps done.
    first = 0 if parent == "train.step" else 1
    assert [p["step"] for p in parents] == list(
        range(first, first + training.steps))
    assert all(p["parent"] is None for p in parents)


@pytest.mark.parametrize("parent", sorted(INFER_CHILDREN))
def test_served_children_in_order_under_their_parent(serving, parent):
    units = serving.calls * (2 if parent == "infer.prefill" else 1)
    parents = check_family(serving.on.recs, parent,
                           INFER_CHILDREN[parent], "call", units)
    assert sorted({p["call"] for p in parents}) == [1, 2]
    if parent != "infer.transcribe":
        calls = {r["id"]: r for r in serving.on.recs
                 if r["name"] == "infer.transcribe"}
        assert all(calls[p["parent"]]["call"] == p["call"]
                   for p in parents)


def test_the_fetch_span_counts_the_arrays_it_reads(training):
    fetches = [r for r in training.on.recs if r["name"] == "train.fetch"]
    assert len(fetches) == training.steps
    if training.objective == "ctc":
        assert {r["arrays"] for r in fetches} == {2}
    else:  # + the step's routing counters and its one dropped counter
        assert all(r["arrays"] > 3 for r in fetches)


def test_the_training_wait_exists_only_with_the_tracer_on(training):
    """The traced loop blocks inside ``train.step`` on the step before
    (the last step of a fit has none after it) and again, at once, in
    that step's ``train.sync``; with the tracer off the first call is
    not made at all."""
    t = training
    waits = [r for r in t.on.recs if r["name"] == "train.wait"]
    assert len(waits) == t.steps - 1
    assert (t.on.blocks, t.off.blocks) == (2 * t.steps - 1, t.steps)
    assert t.off.recs == []
    # A wait on step k ends before the line of step k (steps done:
    # k + 1) begins, and that line is written with step k + 1 handed
    # over, all but the last.
    logs = {r["step"]: r for r in t.on.recs if r["name"] == "train.log"}
    for w in waits:
        assert w["ts"] + w["dur_ms"] / 1e3 < logs[w["step"] + 1]["ts"]
    assert [logs[k]["ahead"] for k in sorted(logs)] \
        == [1] * (t.steps - 1) + [0]


def test_the_served_wait_exists_only_with_the_tracer_on(serving):
    """Once a prefill sub-batch traced; an untraced call never blocks
    before its ``device_get``."""
    s = serving
    assert sum(r["name"] == "infer.prefill.wait" for r in s.on.recs) \
        == 2 * s.calls
    assert (s.on.blocks, s.off.blocks) == (2 * s.calls, 0)
    assert s.off.recs == []


def check_off(off):
    assert off.text == ""
    assert off.registry.snapshot() == MetricsRegistry().snapshot()
    assert off.hooks_after == off.hooks_before
    for name in NEW_NAMES:
        assert obs.span(name, step=1, call=1) is _NOOP


def test_tracer_off_the_training_sites_record_and_hook_nothing(training):
    check_off(training.off)


def test_tracer_off_the_served_sites_record_and_hook_nothing(serving):
    check_off(serving.off)


@pytest.mark.parametrize("traced", ["on", "off"])
def test_a_served_call_times_five_host_phases(serving, traced):
    for stats in getattr(serving, traced).out:
        h = stats["host_s"]
        assert set(h) == HOST_S
        assert all(v >= 0 for v in h.values())
        assert h["cache"] <= h["prefill_dispatch"]
        assert h["decode_dispatch"] + h["fetch"] <= h["to_ids"]


def test_the_training_records_pass_the_schema_lint(training, obs_lint):
    assert training.on.recs and obs_lint(training.on.text) == []


def test_the_served_records_pass_the_schema_lint(serving, obs_lint):
    assert serving.on.recs and obs_lint(serving.on.text) == []


@pytest.mark.parametrize("record, key", [
    ({"name": "train.lr"}, "step"),
    ({"name": "train.fetch", "step": 3.5}, "step"),
    ({"name": "infer.decode.fetch", "step": 3}, "call"),
    ({"name": "infer.prefill.wait", "call": True}, "call"),
    ({"name": "host.gc", "generation": 2}, "collected"),
    ({"name": "host.gc", "collected": 0}, "generation")])
def test_the_schema_lint_flags_a_child_that_does_not_say_whose_it_is(
        obs_lint, record, key):
    rec = {"event": "span", "ts": 1.0, "dur_ms": 1.0, **record}
    problems = obs_lint(json.dumps(rec))
    assert len(problems) == 1 and repr(key) in str(problems[0])
    whole = {**rec, "step": 3, "call": 3, "generation": 2, "collected": 0}
    assert obs_lint(json.dumps(whole)) == []


def test_trace_report_gives_the_log_line_its_self_time(training):
    """An operator without the benchmark reads the same anatomy:
    ``train.log``'s self time is what its four children do not cover."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import trace_report
    finally:
        sys.path.remove(os.path.join(REPO, "tools"))
    agg = trace_report.aggregate(training.on.recs)["phases"]
    logs = [r for r in training.on.recs if r["name"] == "train.log"]
    own = sum(r["dur_ms"] for r in logs) - sum(
        r["dur_ms"] for r in training.on.recs
        if r["parent"] in {p["id"] for p in logs})
    assert agg["train.log"]["self_ms"] == pytest.approx(own, abs=1e-2)
    assert agg["train.log"]["self_ms"] < agg["train.log"]["cum_ms"]
    assert agg["train.lr"]["count"] == training.steps


def test_the_benchmarks_readers_still_read_a_traced_loop():
    """The readers of the host's turn (imported as they are) on a real
    traced loop under the real clock: ``train.step`` = {dispatch of
    k+1, wait on k} still spans a device step, so its median stays
    near the step-to-step interval; every unit of the window has its
    turn; and the schedule's span no longer holds a device round
    trip. A wider GRU than the other cases', so that a step outweighs
    the loop's own work on the CPU."""
    import statistics

    from benchmark.layer_metrics import (_host_turn, host_dispatch_ms,
                                         host_turn_ms, late_units,
                                         train_lr_ms, train_step_ms)

    trainer = ctc_trainer(hidden=96, layers=2, channels=(8, 8), frames=128,
                          n_utts=8 * 14)
    sink = io.StringIO()
    obs.configure(enabled=True, sink=sink, registry=MetricsRegistry(),
                  clock=time.perf_counter, wall=time.perf_counter)
    try:
        trainer.fit(1)
    finally:
        obs.configure(enabled=False, registry=obs.registry(),
                      wall=time.time)
    recs = [json.loads(line) for line in sink.getvalue().splitlines()]
    spans = [(r["name"], r["ts"], r["ts"] + r["dur_ms"] / 1e3)
             for r in recs if r["event"] == "span"]
    # The window opens, as a driver's does, inside the line of the last
    # warm-up step (the second: the first compiles), and closes with
    # the last line.
    logs = sorted((a, b) for n, a, b in spans if n == "train.log")
    record = {"driver": "train", "spans": spans,
              "t_window_start": logs[1][0] + 1e-6,
              "t_window_end": logs[-1][1]}
    units = _host_turn.units(record)
    assert len(units) >= 9
    assert all(0 < u.dispatch_s < u.turn_s for u in units)
    starts = [a for a, _ in _host_turn.in_window(record, "train.step")]
    interval = 1e3 * statistics.median(
        b - a for a, b in zip(starts, starts[1:]))
    assert train_step_ms.read(record) == pytest.approx(interval, rel=0.2)
    assert 0 < host_dispatch_ms.read(record) < host_turn_ms.read(record)
    assert train_lr_ms.read(record) < 0.1
    assert late_units.read(record) is not None


# -- host.gc ---------------------------------------------------------------

@pytest.fixture
def quiet_gc():
    """No collection but the ones the test asks for."""
    gc.collect()
    gc.disable()
    yield
    gc.enable()


def fresh(clock=None):
    clock = clock or Ticking()
    tr = Tracer(registry=MetricsRegistry(), clock=clock, wall=clock)
    sink = io.StringIO()
    return tr, sink


def records(sink):
    return [json.loads(line) for line in sink.getvalue().splitlines()]


def test_a_collection_inside_an_open_span_is_its_child(quiet_gc):
    tr, sink = fresh()
    tr.configure(enabled=True, sink=sink)
    with tr.span("train.step", step=7):
        gc.collect()
    gcs = [r for r in records(sink) if r["name"] == "host.gc"]
    step = records(sink)[-1]
    assert len(gcs) == 1 and step["name"] == "train.step"
    one, = gcs
    assert one["parent"] == step["id"] and one["generation"] == 2
    assert isinstance(one["collected"], int)
    # Start and duration come from the tracer's own clocks: one
    # reading of each as the collection starts, one as it stops.
    assert one["dur_ms"] == pytest.approx(1.0)
    assert step["ts"] < one["ts"] < step["ts"] + step["dur_ms"] / 1e3
    assert tr._registry.snapshot()["histograms"][
        'span_ms{name="host.gc"}']["count"] == 1


@pytest.mark.parametrize("generation", [0, 1, 2])
def test_a_collection_outside_any_span_has_no_parent(quiet_gc,
                                                     generation):
    tr, sink = fresh()
    tr.configure(enabled=True, sink=sink)
    gc.collect(generation)
    tr.configure(enabled=False, sink=sink)  # writes what the hook left
    one, = records(sink)
    assert (one["name"], one["parent"], one["generation"]) \
        == ("host.gc", None, generation)


def test_a_tracer_never_enabled_installs_no_hook(quiet_gc):
    hooks = list(gc.callbacks)
    tr, sink = fresh()
    tr.configure(enabled=False, sink=sink)
    with tr.span("train.step"):
        gc.collect()
    assert gc.callbacks == hooks and sink.getvalue() == ""


def test_a_tracer_enabled_twice_installs_one_hook(quiet_gc):
    hooks = len(gc.callbacks)
    tr, sink = fresh()
    for _ in range(2):
        tr.configure(enabled=True, sink=sink)
        tr.configure(enabled=False, sink=sink)
    assert len(gc.callbacks) == hooks + 1


def test_a_tracer_disabled_again_hears_no_collection(quiet_gc):
    clock = Ticking()
    tr, sink = fresh(clock)
    tr.configure(enabled=True, sink=sink)
    tr.configure(enabled=False, sink=sink)
    t = clock.t
    gc.collect()
    assert clock.t == t            # not even a clock reading
    tr.configure(enabled=True, sink=sink)
    with tr.span("after"):
        pass
    assert [r["name"] for r in records(sink)] == ["after"]


def test_a_dropped_tracer_takes_its_hook_with_it(quiet_gc):
    hooks = list(gc.callbacks)
    tr, sink = fresh()
    tr.configure(enabled=True, sink=sink)
    assert len(gc.callbacks) == len(hooks) + 1
    del tr
    gc.collect()
    assert gc.callbacks == hooks


def test_a_collection_while_the_tracer_writes_does_not_deadlock(quiet_gc):
    """The collector may run while this thread holds the tracer's lock
    (a sink's ``write`` is Python code): the hook takes no lock and
    writes nothing; the next span's record carries the collection."""
    tr, sink = fresh()
    tr.configure(enabled=True, sink=sink)
    with tr._lock:
        gc.collect()
    assert sink.getvalue() == ""
    with tr.span("next"):
        pass
    assert [r["name"] for r in records(sink)] == ["host.gc", "next"]


def test_collections_on_two_threads_go_to_their_own_parents(quiet_gc):
    import threading

    tr, sink = fresh()
    tr.configure(enabled=True, sink=sink)

    def other():
        with tr.span("worker"):
            gc.collect()

    with tr.span("main"):
        t = threading.Thread(target=other)
        t.start()
        t.join()
        gc.collect()
    recs = records(sink)
    ids = {r["name"]: r["id"] for r in recs if r["name"] != "host.gc"}
    assert sorted(r["parent"] for r in recs if r["name"] == "host.gc") \
        == sorted([ids["worker"], ids["main"]])


def test_the_traced_loops_collect_under_the_span_that_was_open(training):
    """Whatever the loop collected while traced is a ``host.gc`` record
    whose parent is a span of the run (or none, between spans)."""
    ids = {r["id"] for r in training.on.recs}
    for r in training.on.recs:
        if r["name"] == "host.gc":
            assert r["parent"] is None or r["parent"] in ids
            assert r["generation"] in (0, 1, 2) and r["dur_ms"] > 0
    assert np.isfinite([r["dur_ms"] for r in training.on.recs]).all()
