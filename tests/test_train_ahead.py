"""``Trainer.fit`` hands step k+1 over before it reads step k.

Real loops at toy sizes on the CPU (the ``ctc`` objective on
``dev_slice`` cut down, the ``lm`` objective of ``tests/test_lfm2.py``).
One list, ``seen``, takes what happens in the order it happens: a spy on
``trainer.train_step`` writes ``("dispatch", k)``, the logger writes
every event, and spies on ``save`` / ``evaluate`` write theirs. Nothing
here reads a clock.
"""

import dataclasses
import io
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeech_tpu import obs
from deepspeech_tpu.config import get_config
from deepspeech_tpu.obs.metrics import MetricsRegistry
from deepspeech_tpu.train import host_lr, make_lr_schedule


# -- (a) one formula, two faces ---------------------------------------------

@pytest.mark.parametrize("warmup, anneal, per_epoch", [
    (10, 1.1, 12), (7, 1.05, 9), (1, 1.2, 3), (0, 0.9, 5),
    (2000, 1.1, 7500)])
def test_the_host_schedule_is_the_device_schedule(warmup, anneal,
                                                  per_epoch):
    """Over the warm-up, past it, and across two epoch boundaries."""
    cfg = get_config("dev_slice")
    cfg = dataclasses.replace(cfg, train=dataclasses.replace(
        cfg.train, warmup_steps=warmup, lr_anneal=anneal,
        learning_rate=3e-4))
    device = jax.jit(jax.vmap(make_lr_schedule(cfg, per_epoch)))
    steps = sorted({*range(3 * max(warmup, 1) + 1),
                    *range(per_epoch - 2, per_epoch + 3),
                    *range(2 * per_epoch - 2, 2 * per_epoch + 3)})
    want = np.asarray(device(jnp.asarray(steps, jnp.int32)))
    got = [host_lr(cfg, per_epoch, s) for s in steps]
    assert all(isinstance(x, float) for x in got)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert len(set(got)) >= 3         # it moved: warm-up and anneal


# -- the loops ---------------------------------------------------------------

class Seen:
    """Logger, and the list the spies share."""

    def __init__(self):
        self.seen = []

    def log(self, event, **fields):
        self.seen.append((event, fields))

    def steps(self):
        return [f for e, f in self.seen if e == "train_step"]

    def dispatched(self):
        return sum(e == "dispatch" for e, _ in self.seen)


class After:
    """A preempt hook that asks once ``n`` steps were handed over."""

    def __init__(self, seen, n):
        self.seen, self.n = seen, n

    def requested(self):
        return self.seen.dispatched() >= self.n


def spy_on_dispatch(trainer, seen, fail_at=None):
    """Every call of ``trainer.train_step`` enters ``seen`` as
    ``("dispatch", {"k": ordinal})`` before the step is handed over
    (the ordinal is the spy's own: the state's counter is still on its
    way); the call of ordinal ``fail_at`` raises instead."""
    real = trainer.train_step

    def train_step(state, *rest):
        k = seen.dispatched()
        if k == fail_at:
            raise RuntimeError(f"step {k} cannot be handed over")
        seen.seen.append(("dispatch", {"k": k}))
        return real(state, *rest)

    trainer.train_step = train_step
    return real


def ds2_trainer(seen, tmp_path=None, preempt=None, evaluate=False,
                **train):
    """``dev_slice`` cut to one GRU-16 layer, three steps an epoch."""
    from deepspeech_tpu.data import CharTokenizer
    from deepspeech_tpu.train import Trainer, _SyntheticPipeline

    cfg = get_config("dev_slice")
    cfg = dataclasses.replace(
        cfg,
        model=dataclasses.replace(cfg.model, rnn_hidden=16, rnn_layers=1,
                                  conv_channels=(4, 4), dtype="float32"),
        data=dataclasses.replace(cfg.data, batch_size=8,
                                 bucket_frames=(64,), max_label_len=16),
        train=dataclasses.replace(
            cfg.train, **{
                "checkpoint_dir": str(tmp_path) if tmp_path else "",
                "log_every": 1, "warmup_steps": 4, "lr_anneal": 1.1,
                "epochs": 2, **train}))
    pipe = _SyntheticPipeline(cfg, n_utts=24, frames=64, label_len=4)
    return Trainer(cfg, pipe, CharTokenizer.english(),
                   eval_pipeline=pipe if evaluate else None,
                   logger=seen, preempt=preempt)


def lm_trainer(seen, **overrides):
    """The toy decoder-only trainer: two steps an epoch, three epochs,
    routing counters in the step's outputs."""
    from test_lfm2 import toy_trainer

    trainer = toy_trainer(**{"train.epochs": 3, **overrides})[1]
    trainer.logger = seen
    return trainer


def counters():
    c = obs.registry().snapshot()["counters"]
    return (c.get("train_log_ahead_total", 0),
            c.get("train_logged_steps_total", 0))


@pytest.fixture(scope="module", params=["ctc", "lm"])
def ran(request):
    """One whole ``fit`` (6 steps) of each objective, spied on."""
    seen = Seen()
    trainer = {"ctc": ds2_trainer, "lm": lm_trainer}[request.param](seen)
    real = spy_on_dispatch(trainer, seen)
    before = counters()
    out = trainer.fit()
    seen.seen.append(("returned", out))
    ahead, logged = (b - a for a, b in zip(before, counters()))
    return dict(seen=seen, trainer=trainer, real=real, out=out,
                ahead=ahead, logged=logged, objective=request.param)


def index(seen, event, **match):
    """Where in the list the first such entry stands."""
    for i, (e, f) in enumerate(seen.seen):
        if e == event and all(f.get(k) == v for k, v in match.items()):
            return i
    raise AssertionError(f"no {event} {match} in {seen.seen}")


# -- (b) the order -----------------------------------------------------------

def test_step_k_plus_1_is_handed_over_before_step_k_is_logged(ran):
    seen = ran["seen"]
    steps = seen.steps()
    # In step order, none missing, the last before fit returns.
    assert [f["step"] for f in steps] == [1, 2, 3, 4, 5, 6]
    assert index(seen, "train_step", step=6) < index(seen, "returned")
    for k in range(6):              # the line of step k: "step": k + 1
        line = index(seen, "train_step", step=k + 1)
        assert index(seen, "dispatch", k=k) < line
        nxt = k + 1
        per_epoch = ran["trainer"].steps_per_epoch
        if nxt < 6 and nxt % per_epoch:
            assert index(seen, "dispatch", k=nxt) < line
            # ... and never two ahead.
            if nxt + 1 < 6:
                assert line < index(seen, "dispatch", k=nxt + 1)
        elif nxt < 6:
            # An epoch ends between them: the line is written first.
            assert line < index(seen, "epoch_end", epoch=k // per_epoch) \
                < index(seen, "dispatch", k=nxt)
    assert ran["out"]["loss"] == steps[-1]["loss"]


def test_the_ahead_counter_reads_every_step_but_an_epochs_last(ran):
    """(f): n - 1 of n in a fit of one epoch, as a benchmark window
    is; here every epoch's last step is drained."""
    epochs = 6 // ran["trainer"].steps_per_epoch
    assert (ran["ahead"], ran["logged"]) == (6 - epochs, 6)


def test_a_line_carries_its_own_epoch_and_learning_rate(ran):
    t = ran["trainer"]
    for f in ran["seen"].steps():
        k = f["step"] - 1
        assert f["epoch"] == k // t.steps_per_epoch
        assert f["lr"] == round(host_lr(t.cfg, t.steps_per_epoch, k), 8)
        assert f["lr"] == pytest.approx(
            float(t.lr_schedule(jnp.asarray(k))), rel=1e-5)


def test_unlogged_steps_run_as_before_and_logged_ones_ahead():
    """``log_every`` 2 over three steps an epoch: lines at 2, 4, 6."""
    seen = Seen()
    trainer = ds2_trainer(seen, log_every=2)
    spy_on_dispatch(trainer, seen)
    before = counters()
    trainer.fit()
    assert [f["step"] for f in seen.steps()] == [2, 4, 6]
    # Step 1 (line "2") has step 2 behind it in its epoch; step 3
    # (line "4") as well; step 5 ends the run.
    assert index(seen, "dispatch", k=2) \
        < index(seen, "train_step", step=2) \
        < index(seen, "epoch_end", epoch=0)
    assert index(seen, "dispatch", k=4) \
        < index(seen, "train_step", step=4) \
        < index(seen, "dispatch", k=5)
    assert [b - a for a, b in zip(before, counters())] == [2, 3]


def test_the_last_line_is_written_before_preempted(tmp_path):
    seen = Seen()
    trainer = ds2_trainer(seen, tmp_path, preempt=After(seen, 2),
                          checkpoint_every_steps=0)
    spy_on_dispatch(trainer, seen)
    saved = trainer.save
    trainer.save = lambda epoch: (seen.seen.append(("save", {})),
                                  saved(epoch))[1]
    out = trainer.fit()
    assert out["preempted"] is True
    assert [e for e, _ in seen.seen] == [
        "dispatch", "dispatch", "train_step", "train_step", "save",
        "preempted"]
    assert [f["step"] for f in seen.steps()] == [1, 2]
    assert seen.seen[-1][1]["step"] == 2


def test_a_checkpoint_is_saved_after_its_steps_line(tmp_path):
    seen = Seen()
    trainer = ds2_trainer(seen, tmp_path, checkpoint_every_steps=2,
                          epochs=1)
    spy_on_dispatch(trainer, seen)
    saved = trainer.save
    trainer.save = lambda epoch: (seen.seen.append(
        ("save", {"step": int(trainer.state.step)})), saved(epoch))[1]
    trainer.fit()
    # Mid-epoch at step 2 (its line first, step 3 not yet handed
    # over), then the epoch's.
    assert [e for e, _ in seen.seen] == [
        "dispatch", "dispatch", "train_step", "train_step", "save",
        "dispatch", "train_step", "epoch_end", "save"]
    assert [f["step"] for e, f in seen.seen if e == "save"] == [2, 3]


def test_the_epochs_last_line_is_written_before_its_eval():
    seen = Seen()
    trainer = ds2_trainer(seen, evaluate=True, epochs=1)
    spy_on_dispatch(trainer, seen)
    evaluate = trainer.evaluate
    trainer.evaluate = lambda: (seen.seen.append(("evaluate", {})),
                                evaluate())[1]
    out = trainer.fit()
    assert [e for e, _ in seen.seen][-5:] == [
        "train_step", "train_step", "epoch_end", "evaluate", "eval"]
    assert "wer" in out and out["loss"] == seen.steps()[-1]["loss"]


def test_an_exception_leaving_the_loop_still_writes_the_owed_line():
    seen = Seen()
    trainer = ds2_trainer(seen, epochs=1)
    spy_on_dispatch(trainer, seen, fail_at=2)
    with pytest.raises(RuntimeError, match="step 2 cannot"):
        trainer.fit()
    assert [e for e, _ in seen.seen] == [
        "dispatch", "dispatch", "train_step", "train_step"]
    assert [f["step"] for f in seen.steps()] == [1, 2]


# -- (c) event k is step k ---------------------------------------------------

def test_a_lines_loss_and_gradient_norm_are_its_own_steps(ran):
    """Against the same steps run one at a time, each read back before
    the next is handed over (the order of the loop before): bit-equal
    over the six steps."""
    from deepspeech_tpu.parallel import shard_batch

    fresh = {"ctc": ds2_trainer, "lm": lm_trainer}[ran["objective"]](
        Seen())
    state, want = fresh.state, []
    for epoch in range(6 // fresh.steps_per_epoch):
        for batch in fresh.pipeline.epoch(epoch):
            state, m = ran["real"](state, shard_batch(fresh.mesh, batch))
            want.append((float(m["loss"]), float(m["grad_norm"])))
    got = [(f["loss"], f["grad_norm"]) for f in ran["seen"].steps()]
    assert got == want and len({w[0] for w in want}) == 6


# -- (e) the guardian --------------------------------------------------------

def test_under_the_guardian_the_loop_is_synchronous():
    seen = Seen()
    trainer = ds2_trainer(seen, guardian=True)
    assert trainer.guardian is not None
    spy_on_dispatch(trainer, seen)
    before = counters()
    trainer.fit()
    order = [(e, f["k"] if e == "dispatch" else f["step"])
             for e, f in seen.seen if e in ("dispatch", "train_step")]
    assert order == [x for k in range(6)
                     for x in (("dispatch", k), ("train_step", k + 1))]
    assert [b - a for a, b in zip(before, counters())] == [0, 6]


# -- (d) nothing for the device in the host's turn ---------------------------

def traced(fn):
    """``fn()`` under the process-wide tracer; its records."""
    sink = io.StringIO()
    obs.configure(enabled=True, sink=sink, registry=MetricsRegistry())
    try:
        fn()
    finally:
        obs.configure(enabled=False, registry=obs.registry(),
                      clock=time.perf_counter, wall=time.time)
    return [json.loads(line) for line in sink.getvalue().splitlines()]


@pytest.mark.parametrize("objective", ["ctc", "lm"])
def test_the_log_line_issues_no_device_computation(objective):
    """Whatever ``jax`` runs for the first time it traces, and the
    tracer hears it (``jax.trace`` spans, children of the span open
    then). With every cache of the process dropped first, a fit's
    traced spans hold the step's own tracing under ``train.dispatch``
    and nothing under ``train.log``; and the schedule the optimizer
    traces is not called for the line at all."""
    seen = Seen()
    trainer = {"ctc": ds2_trainer, "lm": lm_trainer}[objective](seen)
    calls = []
    schedule = trainer.lr_schedule
    trainer.lr_schedule = lambda step: (calls.append(step),
                                        schedule(step))[1]
    jax.clear_caches()
    recs = traced(trainer.fit)
    assert calls == [] and len(seen.steps()) == 6
    by_id = {r["id"]: r for r in recs if "id" in r}

    def under(rec, name):
        while rec is not None:
            if rec["name"] == name:
                return True
            rec = by_id.get(rec.get("parent"))
        return False

    heard = [r for r in recs if r["name"].startswith("jax.")]
    assert any(under(r, "train.dispatch") for r in heard)
    assert [r for r in heard if under(r, "train.log")] == []
    logs = [r for r in recs if r["name"] == "train.log"]
    assert [r["ahead"] for r in logs].count(0) \
        == 6 // trainer.steps_per_epoch
