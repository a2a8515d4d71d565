"""The readers that find kernels and jax's compile phases by name, on a
hand-made record: instruction texts as a device trace names its events
(``kernel_metadata`` over several lines), two chips, two steps, one
slow and one fast backward call site; spans before, across and after
the window's start."""

import importlib

import pytest

import conftest  # noqa: F401  (puts the checkout on sys.path)
from benchmark.layer_metrics import _kernel_id

CALL = ('custom-call(bf16[850,32,5280]{2,1,0:T(8,128)(2,1)} %fusion.4, '
        'bf16[1760,5632]{1,0:T(8,128)(2,1)WHERE} %pad.26), '
        'custom_call_target="tpu_custom_call", '
        'operand_layout_constraints={bf16[850,32,5280]{2,1,0}, '
        'bf16[1760,5632]{1,0}}, frontend_attributes={kernel_metadata=')


def event(name, shape, facts, weights="S(1)"):
    inner = ",\n".join(f'"{k}":"{v}"' for k, v in sorted(facts.items()))
    meta = "{\n" + inner + "\n}" if facts else "{}"
    return (f"%{name} = {shape} {CALL}{meta}}}"
            ).replace("WHERE", weights)


def scan(kernel, reverse):
    return {"kernel": kernel, "variant": "blocked", "reverse": reverse,
            "t": 850, "b": 32, "h": 1760, "gates": 3}


FWD = "f32[850,32,1760]{2,1,0:T(8,128)}"
BWD = "(f32[850,32,5280]{2,1,0}, f32[850,32,5280]{2,1,0})"


def one_step(named=True):
    """One chip's Mosaic events of one step, in time order."""
    def facts(f):
        return f if named else {}
    return [
        (event("gru_scan_fwd.4", FWD, facts(scan("gru_scan_fwd", 0))),
         0.008),
        (event("gru_scan_fwd.5", FWD, facts(scan("gru_scan_fwd", 1))),
         0.008),
        (event("jvp_ctc_alpha_.1", "(f32[850,32,640]{2,1,0}, f32[32,1])",
               facts({"kernel": "ctc_alpha", "t": 850, "b": 32,
                      "s": 640})), 0.002),
        (event("jvp_ctc_gamma_.1", "f32[850,32,640]{2,1,0}",
               facts({"kernel": "ctc_gamma", "t": 850, "b": 32,
                      "s": 640})), 0.003),
        (event("gru_scan_bwd.7", BWD, facts(scan("gru_scan_bwd", 1)),
               weights=""), 0.025),  # its weights stayed in HBM
        (event("gru_scan_bwd.6", BWD, facts(scan("gru_scan_bwd", 1))),
         0.0147),
        (event("gru_scan_bwd.8", BWD, facts(scan("gru_scan_bwd", 0))),
         0.0149),
    ]


def record(kernels, chips=2, units=2, spans=()):
    return {"driver": "train", "chips": chips, "units": units,
            "t_window_start": 100.0, "t_window_end": 102.0,
            "spans": list(spans), "counters": {},
            "trace": None if kernels is None else {"kernels": kernels}}


def read(name, rec):
    reader = importlib.import_module(f"benchmark.layer_metrics.{name}")
    assert reader.DRIVERS == ("train",)
    return reader.read(rec)


def test_kernel_facts_reads_the_trace_form():
    text = one_step()[4][0]
    assert "\n" in text
    assert _kernel_id.kernel_facts(text) == {
        "kernel": "gru_scan_bwd", "variant": "blocked", "reverse": "1",
        "t": "850", "b": "32", "h": "1760", "gates": "3"}
    assert _kernel_id.kernel_facts(one_step(named=False)[0][0]) == {}
    assert _kernel_id.kernel_facts("%fusion.2 = f32[4] fusion(...)") == {}
    assert _kernel_id.kernel_facts("x kernel_metadata={\n\"kernel\":") == {}
    assert _kernel_id.kernel_facts('kernel_metadata="text"') == {}


@pytest.mark.parametrize("name, want", [
    ("rnn_scan_fwd_ms", 16.0),
    ("rnn_scan_bwd_ms", 25.0 + 14.7 + 14.9),
    ("ctc_kernel_ms", 5.0),
    ("rnn_scan_bwd_slow_calls", 1.0),
    ("unnamed_kernel_calls", 0.0),
])
def test_named_kernels_per_step_and_chip(name, want):
    rec = record(one_step() * 4)  # two chips, two steps
    assert read(name, rec) == pytest.approx(want)


def test_slow_backward_calls_are_told_by_site_and_direction():
    # On the second chip every call is fast: half a slow call a step
    # and chip.
    fast = [(t, 0.0147 if "gru_scan_bwd.7" in t else s)
            for t, s in one_step()]
    rec = record(one_step() * 2 + fast * 2)
    assert read("rnn_scan_bwd_slow_calls", rec) == pytest.approx(0.5)
    sites = rec["counters"]["rnn_scan_bwd_call_sites"]
    assert [(s["instruction"], s["reverse"], s["calls"], s["slow"])
            for s in sites] == [("%gru_scan_bwd.7", "1", 4, True),
                                ("%gru_scan_bwd.6", "1", 4, False),
                                ("%gru_scan_bwd.8", "0", 4, False)]
    assert [s["weights_in_vmem"] for s in sites] == [False, True, True]
    assert sites[1]["median_ms"] == pytest.approx(14.7)
    # A call is compared with the same work only: reverse=0's 14.9 ms
    # is its own fastest, whatever reverse=1 takes.
    assert sites[2]["median_ms"] == pytest.approx(14.9)


@pytest.mark.parametrize("name", [
    "rnn_scan_fwd_ms", "rnn_scan_bwd_ms", "ctc_kernel_ms",
    "rnn_scan_bwd_slow_calls"])
def test_a_program_that_names_no_kernel_reads_as_nothing(name):
    assert read(name, record(one_step(named=False) * 4)) is None
    assert read(name, record(None)) is None  # untraced, or a CPU trace


def test_unnamed_kernels_are_counted():
    assert read("unnamed_kernel_calls",
                record(one_step(named=False) * 4)) == 28
    mixed = one_step() + one_step(named=False)[:2]
    assert read("unnamed_kernel_calls", record(mixed)) == 2
    assert read("unnamed_kernel_calls", record(None)) is None


def test_setup_trace_lower_is_the_union_before_the_window():
    spans = [
        ("train.step", 90.0, 99.0),
        ("jax.trace", 91.0, 94.0),    # train_step ...
        ("jax.trace", 92.0, 93.0),    # ... and a function traced in it
        ("jax.lower", 94.0, 96.0),
        ("jax.compile", 96.0, 98.5),  # a cache read: not this metric's
        ("jax.trace", 99.5, 100.5),   # ends inside the window
        ("jax.compile", 100.5, 101.0),
        ("jax.lower", 103.0, 104.0),  # the HLO checks after the window
    ]
    rec = record(None, spans=spans)
    assert read("setup_trace_lower_s", rec) == pytest.approx(5.0)
    assert rec["counters"]["compiled_in_window"] == {
        "jax.trace": 1, "jax.lower": 0, "jax.compile": 1}
    # The parent's tracer has no such span, an untraced run no span.
    assert read("setup_trace_lower_s",
                record(None, spans=spans[:1])) is None
    assert read("setup_trace_lower_s", record(None)) is None
