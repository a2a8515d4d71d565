"""``ops/scan_pallas.scan_route`` is the one place that says which
recurrent kernel runs a layer and in which build. Its answer has to be
what is then lowered: every case here asks the route, sends the same
call through the dispatch of ``models/rnn.py`` (or, with a carried
state, to the kernel function), lowers it for the TPU (lowering only,
as tests/test_kernel_identity.py does) and reads the ``kernel`` and
``variant`` facts off the program text. The last cases hold the
route's other askers (``streaming.py``, ``utils/quantize.py``,
``chip_smoke.py``) to it for the presets the cells run."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest

from deepspeech_tpu.config import get_config
from deepspeech_tpu.models import rnn as rnn_model
from deepspeech_tpu.ops import rnn_pallas
from deepspeech_tpu.ops.scan_pallas import scan_route
from test_kernel_identity import lowered_facts

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
S = jax.ShapeDtypeStruct
_T = 16
# either side of each budget: 10 MiB at 4 / 2 / 1 bytes by 3 or 4 (or,
# both directions fused, 6) gates; 1760 is ds2_full's width
_WIDTHS = (800, 1536, 1760, 1869, 1870)


def _cases():
    for cell in ("gru", "lstm"):
        for stored in ("float32", "bfloat16", "int8"):
            for hidden in _WIDTHS:
                for carry in (False, True):
                    for directions in (1, 2):
                        if not (carry and directions == 2):
                            yield cell, stored, hidden, 0, 8, carry, directions
    # rnnt_he2019's widths: its batch, rows off the sublane tile, and a
    # float32 batch past the kernels' limit; the decoders' carried step
    for stored, rows in (("bfloat16", 64), ("bfloat16", 60),
                         ("float32", 64), ("float32", 256), ("int8", 64)):
        for carry in (False, True):
            yield "lstmp", stored, 2048, 640, rows, carry, 1


def _facts_of(fn, args):
    return {(f["kernel"], f["variant"]) for f in lowered_facts(fn, args)}


def _weights(cell, stored, hidden):
    wide = (3 if cell == "gru" else 4) * hidden
    bias = S((wide,), jnp.float32)
    if stored == "int8":
        return {"q": S((hidden, wide), jnp.int8),
                "scale": S((wide,), jnp.float32)}, bias
    return S((hidden, wide), jnp.float32), bias


@pytest.mark.parametrize(
    "cell, stored, hidden, proj, rows, carry, directions", list(_cases()))
def test_the_route_names_what_is_lowered(monkeypatch, cell, stored, hidden,
                                         proj, rows, carry, directions):
    monkeypatch.setenv("DS2N_ASSUME_TPU", "1")  # compiled kernels, as there
    int8 = stored == "int8"
    dtype = "bfloat16" if int8 else stored
    cfg = dataclasses.replace(
        get_config("rnnt_he2019" if cell == "lstmp" else "ds2_small").model,
        rnn_type=cell, rnn_hidden=hidden, rnn_proj=proj, dtype=dtype,
        rnn_impl="pallas", bidirectional=directions == 2)
    facts = dict(rows=rows, hidden=hidden, proj=proj,
                 dot_bytes=jnp.dtype(dtype).itemsize, int8=int8, carry=carry,
                 directions=directions)
    route = scan_route(cell, "pallas", **facts)
    assert route == rnn_model.layer_scan_route(
        cfg, rows, int8=int8, carry=carry, directions=directions)
    wide = (3 if cell == "gru" else 4) * hidden
    xproj, mask = S((rows, _T, wide), jnp.dtype(dtype)), S((rows, _T),
                                                           jnp.float32)

    if carry:
        # the chunked engine's call (GRU) and the decoders' one-step
        # path (lstmp, which runs the scan): no dispatch in between
        h0 = S((rows, hidden), jnp.float32)
        if cell != "gru":
            assert route.kernel is None
            return
        w, bias = _weights(cell, stored, hidden)
        if int8:
            call = lambda xp, m, w_, b_, h0_: rnn_pallas.gru_scan_pallas_q(
                xp, m, w_["q"], w_["scale"], b_, dot_dtype=dtype, h0=h0_)
        else:
            call = lambda xp, m, w_, b_, h0_: \
                rnn_pallas.gru_scan_pallas_stream(xp, m, w_, b_, h0_,
                                                  dot_dtype=dtype)
        if route.kernel is None:
            with pytest.raises(ValueError, match="resident-only"):
                lowered_facts(call, (xproj, mask, w, bias, h0))
        else:
            assert _facts_of(call, (xproj, mask, w, bias, h0)) == {
                (route.kernel, route.variant)}
        return

    if cell == "lstmp":
        args = (xproj, mask, S((proj, wide), jnp.float32),
                S((hidden, proj), jnp.float32), S((wide,), jnp.float32),
                S((wide,), jnp.float32))
        if int8:  # quantised trees are dequantised at entry: float weights
            assert route.kernel is None
            return
        run = lambda *a: rnn_model._run_lstmp(cfg, *a)
    else:
        params = {rev: _weights(cell, stored, hidden)
                  for rev in (False, True)[:directions]}
        args = (xproj, mask, params)
        run = lambda xp, m, p: rnn_model._run_stack_dirs(
            cfg, xp, jnp.zeros(xp.shape[-1:], jnp.float32), m, p)

    def train(*a):
        ys, vjp = jax.vjp(lambda xp: run(xp, *a[1:]), a[0])
        return vjp(ys)

    want = {(route.kernel, route.variant)}
    if not int8:
        back = scan_route(cell, "pallas", backward=True, **facts)
        want.add((back.kernel, back.variant))
    got = _facts_of(run if int8 else train, args)
    assert got == (want - {(None, None)})


@pytest.mark.parametrize("preset", ["ds2_full", "ds2_streaming",
                                    "rnnt_he2019"])
def test_the_other_askers_agree_with_the_route(preset):
    """What ``chip_smoke.kernel_route`` reports, what the int8 serving
    plumbing keeps and records and what the chunked engine runs are the
    route's answers for the preset, under the impl the chip resolves."""
    from deepspeech_tpu.streaming import StreamingTranscriber
    from deepspeech_tpu.utils.quantize import keep_recurrent_q, kernel_regime

    spec = importlib.util.spec_from_file_location(
        "chip_smoke_for_route", os.path.join(REPO, "chip_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)

    cfg = get_config(preset)
    model = dataclasses.replace(cfg.model, rnn_impl="pallas")
    facts = dict(hidden=model.rnn_hidden, proj=model.rnn_proj,
                 dot_bytes=jnp.dtype(model.dtype).itemsize)
    route = scan_route(model.rnn_type, "pallas", rows=cfg.data.batch_size,
                       directions=2 if model.bidirectional else 1, **facts)
    assert smoke.kernel_route(preset)["rnn_route"] == {
        "ds2_full": "pinned", "ds2_streaming": "resident",
        "rnnt_he2019": "resident"}[preset] == route.variant

    for streaming in (False, True):
        q = scan_route(model.rnn_type, "pallas", int8=True, carry=streaming,
                       **facts)
        assert (keep_recurrent_q(model, streaming) is not None) == (
            q.kernel is not None)
        assert kernel_regime(model, True, streaming) == (
            q.variant.replace("_", "-") if q.kernel else "fp")

    if preset == "ds2_streaming":
        engine = StreamingTranscriber(dataclasses.replace(cfg, model=model),
                                      None, None)
        assert engine._use_pallas == (scan_route(
            "gru", "pallas", carry=True, **facts).kernel is not None)
        assert engine._use_pallas  # H=800 in bf16 is resident
    else:  # not streamable: the engine refuses before any routing
        with pytest.raises(ValueError):
            StreamingTranscriber(dataclasses.replace(cfg, model=model),
                                 None, None)
