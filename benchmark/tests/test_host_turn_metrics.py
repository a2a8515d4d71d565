"""The six readers of the host's turn on hand-made records of each
driver: flat (name, start, end) spans as ``drivers/train.SpanSink``
keeps them, laid out as the program nests them
(``deepspeech_tpu/obs/trace.py``), times in seconds."""

import pytest

import conftest  # noqa: F401  (puts the checkout on sys.path)
from benchmark import harness
from benchmark.layer_metrics import (_host_turn, host_dispatch_ms,
                                     host_gc_pct, host_turn_ms,
                                     late_units, train_fetch_ms,
                                     train_lr_ms)

READERS = {m.__name__.rsplit(".", 1)[1]: m for m in (
    host_turn_ms, host_dispatch_ms, train_lr_ms, train_fetch_ms,
    late_units, host_gc_pct)}
MS = 1e-3


def training(driver, steps=6, device=0.5, late=None, children=True,
             gc_at=()):
    """``steps`` traced steps after one warm-up step: dispatch 2 ms,
    the device's step, then the log (sync 0.05, lr 3, fetch 1, emit
    0.5 ms) and 1.5 ms of prefetch. The window opens inside the warm-up
    step's emit. Step ``late`` waits one second more."""
    spans, t, lo = [], 10.0, None
    for k in range(steps + 1):
        step0 = t
        wait = device + (1.0 if k == late else 0.0)
        if children:
            spans.append(("train.dispatch", t, t + 2 * MS))
            spans.append(("train.wait", t + 2 * MS, t + 2 * MS + wait))
        t += 2 * MS + wait
        spans.append(("train.step", step0, t))
        log0 = t
        for name, ms in (("train.sync", 0.05), ("train.lr", 3.0),
                         ("train.fetch", 1.0), ("train.emit", 0.5)):
            if name == "train.emit" and k == 0:
                lo = t + 0.2 * MS
            if children:
                spans.append((name, t, t + ms * MS))
            t += ms * MS
        spans.append(("train.log", log0, t))
        spans.append(("pipeline.data_wait", t, t + 0.01 * MS))
        spans.append(("pipeline.device_prefetch", t + 0.01 * MS,
                      t + 1.5 * MS))
        t += 1.5 * MS
    hi = log0 + 4.55 * MS       # the last step's log line
    for at in gc_at:
        spans.append(("host.gc", lo + at, lo + at + 2 * MS))
    return {"driver": driver, "spans": spans, "t_window_start": lo,
            "t_window_end": hi, "counters": {}}


def served(calls=3, prefills=8, stall=None, children=True):
    """``calls`` traced calls after a warm-up call: cache 0.1 ms, then
    per sub-batch dispatch 1.5 ms + wait 0.286 s, decode dispatch 4 ms
    + fetch 1.3 s, 2 ms of prefetch between calls. Call ``stall``'s
    first sub-batch waits two seconds more."""
    spans, t, lo = [], 50.0, None
    for k in range(calls + 1):
        call0 = t
        if children:
            spans.append(("infer.cache", t, t + 0.1 * MS))
        t += 0.1 * MS
        for i in range(prefills):
            p0 = t
            wait = 0.286 + (2.0 if k == stall and i == 0 else 0.0)
            if children:
                spans.append(("infer.prefill.dispatch", t, t + 1.5 * MS))
                spans.append(("infer.prefill.wait", t + 1.5 * MS,
                              t + 1.5 * MS + wait))
            t += 1.5 * MS + wait
            spans.append(("infer.prefill", p0, t))
            t += 0.2 * MS       # a_lens.append, pre.append
        d0 = t
        if children:
            spans.append(("infer.decode.dispatch", t, t + 4 * MS))
            spans.append(("infer.decode.fetch", t + 4 * MS,
                          t + 4 * MS + 1.3))
        t += 4 * MS + 1.3
        spans.append(("infer.decode", d0, t))
        spans.append(("infer.transcribe", call0, t))
        t += 0.5 * MS           # observe_lm_call, the texts
        if k == 0:
            lo = t + 0.1 * MS
        hi = t
        spans.append(("pipeline.device_prefetch", t + 0.2 * MS,
                      t + 2 * MS))
        t += 2 * MS
    return {"driver": "transcribe_lm", "spans": spans,
            "t_window_start": lo, "t_window_end": hi,
            "counters": {"calls": []}}


def value(name, record):
    return harness.metric_value({"name": name}, record, traced=True)


@pytest.mark.parametrize("driver", _host_turn.TRAIN)
def test_training_drivers_read_one_name_each(driver):
    """The same six names for ``train``, ``train_rnnt`` and
    ``train_lfm2``: the step's turn is wait-end to dispatch-end, i.e.
    the log (4.55 ms), the prefetch (1.5) and the dispatch (2)."""
    rec = training(driver, gc_at=(1.0, 2.0))
    assert value("host_turn_ms", rec) == pytest.approx(8.05)
    assert value("host_dispatch_ms", rec) == pytest.approx(2.0)
    assert value("train_lr_ms", rec) == pytest.approx(3.0)
    assert value("train_fetch_ms", rec) == pytest.approx(1.0)
    assert value("late_units", rec) == 0.0
    window = rec["t_window_end"] - rec["t_window_start"]
    assert value("host_gc_pct", rec) == pytest.approx(
        100 * 4 * MS / window)
    # The first step's turn began before the window: five units of six.
    assert len(_host_turn.units(rec)) == 5


def test_served_call_sums_its_nine_programs():
    """Eight hand-overs inside the call and the one from the call
    before: 0.5 + 2 + 0.1 ms between calls, 0.2 ms between sub-batches
    and before the decode program, plus every dispatch."""
    rec = served()
    assert value("host_dispatch_ms", rec) == pytest.approx(
        8 * 1.5 + 4.0)
    assert value("host_turn_ms", rec) == pytest.approx(
        (0.5 + 2.0 + 0.1) + 8 * 0.2 + 8 * 1.5 + 4.0)
    assert value("late_units", rec) == 0.0
    assert value("host_gc_pct", rec) == 0.0
    assert value("train_lr_ms", rec) is None      # not this driver's
    assert value("train_fetch_ms", rec) is None
    assert len(_host_turn.units(rec)) == 2        # of three calls


@pytest.mark.parametrize("record, late", [
    (served(stall=2), 1), (served(calls=2, stall=2), 1),
    (training("train_rnnt", late=3), 1), (training("train"), 0)],
    ids=["call-2-of-3", "call-2-of-2", "step-3-of-6", "none"])
def test_a_stalled_unit_is_late(record, late):
    """Ledger PR 34's ``ax_k1`` run: 2.0 s more in one prefill
    sub-batch of one call of three."""
    assert value("late_units", record) == late
    # The stall is a wait, not the host's turn: the median turn holds.
    quiet = (served() if record["driver"] == "transcribe_lm"
             else training(record["driver"]))
    assert value("host_turn_ms", record) == pytest.approx(
        value("host_turn_ms", quiet))


@pytest.mark.parametrize("record", [
    training("train", children=False),
    training("train_lfm2", children=False),
    served(children=False),
    dict(training("train"), driver="stream")],
    ids=["train-parent", "train_lfm2-parent", "served-parent",
         "another-driver"])
@pytest.mark.parametrize("name", sorted(READERS))
def test_a_record_without_the_child_spans_reads_none(record, name):
    """The parent's program under these readers (and a record of a
    driver they do not serve): nothing to read, nothing raised."""
    assert value(name, record) is None


def test_a_turn_with_no_program_seen_done_before_it_is_left_out():
    rec = served(calls=1)
    rec["spans"] = [s for s in rec["spans"]
                    if s[1] >= rec["t_window_start"]]
    assert _host_turn.units(rec) == []
    assert value("host_turn_ms", rec) is None
