"""Span tracing: nested monotonic-clock spans + compile events, JSONL.

The tracer answers "where did this step's time go?" for the hot paths
— data wait, host→device transfer, jitted compute, decode, checkpoint
I/O — with a per-record schema shared by every pipeline::

    {"event": "span", "name": "train.step", "ts": <wall s>,
     "dur_ms": <float>, "id": 7, "parent": 3, ...attrs}
    {"event": "compile", "name": "compile", "ts": ..., "dur_ms": 0.0,
     "rung": "4x64", "site": "infer.py:267"}
    {"event": "span", "name": "jax.lower", "ts": <wall s at its start>,
     "dur_ms": ..., "id": 9, "parent": 7, "fun": "train_step"}

jax's own compile phases arrive as spans too, from one
``jax.monitoring`` listener: ``jax.trace`` (the function traced to a
jaxpr), ``jax.lower`` (jaxpr to StableHLO module) and ``jax.compile``
(the backend compile request, a persistent-cache read included), each
with ``fun`` = the function's name and ``parent`` = the span open on the
calling thread, so a recompile inside ``train.step`` is that span's
child. jax reports a phase when it ends: ``ts`` is the wall clock then
minus the duration.

The host's turn between two device programs is opened up by child
spans (the parent's name and extent stay what they were; ``parent``
comes from the stack, and the children of one unit share its ``step``
or ``call``, but for ``train.wait``, which carries the step it blocks
on)::

    train.step      train.dispatch  the jitted step's call to its return
                    train.wait      the traced loop's block on the loss
                                    of the step BEFORE, whose line is
                                    owed (``Trainer.fit`` hands step
                                    k+1 over before it reads step k);
                                    on its own step's where the line is
                                    not put off (tracer on only)
    train.log       train.sync      the logged step's block on the loss
      (``ahead``: 1 if the next     (at once after a ``train.wait``)
      step was handed over first)
                    train.lr        the schedule in host floats
                                    (``train.host_lr``)
                    train.fetch     loss, grad norm and the routing
                                    counters to the host (``arrays``):
                                    transfers only
                    train.emit      the logger and the TensorBoard writer
    infer.transcribe  infer.cache   the call's cache handed out or made
    infer.prefill   infer.prefill.dispatch, infer.prefill.wait (tracer
                                    on only): one sub-batch
    infer.decode    infer.decode.dispatch  argument conversion + the call
                    infer.decode.fetch     ids and counters to the host

A garbage collection is a span as well: ``host.gc`` with ``generation``
and ``collected``, ``parent`` = the span open on the collecting thread,
from one ``gc.callbacks`` hook installed when a tracer is first enabled
(never enabled: no hook; disabled again: the hook returns at once). The
hook writes nothing itself (the collector may run while this tracer's
lock is held): it leaves the finished collection for the next span's
record, or for ``configure(enabled=False)``, to write.

The device's side of the same units is not here: a device trace names
its events by HLO instruction, and ``obs/layers.py`` maps the
instructions of the programs these loops dispatch (``watch`` beside
``train.dispatch``, ``infer.prefill.dispatch`` and
``infer.decode.dispatch``, reached with the tracer on only) to the
model's layers by the scopes the code opens.

Durations come from a monotonic clock (injectable for tests — wall
time only stamps ``ts``); nesting is tracked per thread, so gateway
dispatch spans on a worker thread never adopt a train-loop parent.

DISABLED BY DEFAULT. ``span()`` on a disabled tracer returns a shared
no-op context manager — one attribute read, no allocation, nothing
recorded (``tests/test_obs.py``
``test_scenario_disabled_hooks_hand_out_noops_and_record_nothing``).
Enable with ``configure(jsonl_path=...)`` or by exporting
``DS2_TRACE=/path``; read the output with ``tools/trace_report.py``.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import threading
import time
import weakref
from typing import Callable, IO, Optional

from .metrics import MetricsRegistry, registry as _default_registry


class _NoopSpan:
    """Shared do-nothing span for the disabled tracer."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


_NOOP = _NoopSpan()

_JAX_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
}


class _Span:
    __slots__ = ("_tracer", "name", "attrs", "id", "parent",
                 "ts", "_t0")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self.id = tracer._new_id()
        self.parent = None
        self.ts = 0.0
        self._t0 = 0.0

    def set(self, **attrs) -> None:
        """Attach attributes discovered mid-span (e.g. cache hit)."""
        self.attrs.update(attrs)

    def __enter__(self):
        stack = self._tracer._stack()
        self.parent = stack[-1].id if stack else None
        stack.append(self)
        self.ts = self._tracer._wall()
        self._t0 = self._tracer._clock()
        return self

    def __exit__(self, *exc):
        dur_ms = (self._tracer._clock() - self._t0) * 1e3
        stack = self._tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        self._tracer._record(self, dur_ms)
        return False


def _callsite(skip_substrings=(os.sep + "obs" + os.sep,
                               "utils" + os.sep + "cache.py")) -> str:
    """First stack frame outside obs/ and the cache ledger —
    "file.py:lineno", the attribution for a compile event."""
    f = sys._getframe(1)
    while f is not None:
        fn = f.f_code.co_filename
        if not any(s in fn for s in skip_substrings):
            return f"{os.path.basename(fn)}:{f.f_lineno}"
        f = f.f_back
    return "?"


def _forget_gc_hook(hook) -> None:
    if hook in gc.callbacks:
        gc.callbacks.remove(hook)


class Tracer:
    """Span recorder with an injectable monotonic clock and JSONL sink.

    ``registry`` (default: the process-wide one) additionally receives
    every span duration as a ``span_ms{name=...}`` histogram sample and
    every compile event as a ``compiles{rung=...}`` counter — so
    ``obs.render_text()`` exposes the same breakdown the trace file
    records, without parsing JSONL.
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 clock: Optional[Callable[[], float]] = None,
                 wall: Optional[Callable[[], float]] = None):
        self.enabled = False
        self._clock = clock or time.perf_counter
        self._wall = wall or time.time
        self._registry = (registry if registry is not None
                          else _default_registry())
        self._sink: Optional[IO[str]] = None
        self._owns_sink = False
        self._lock = threading.Lock()
        self._tl = threading.local()
        self._id = 0
        self._hears_jax = False
        self._gc_t0: Optional[tuple] = None
        self._gc_done: list = []

    # -- configuration --------------------------------------------------
    def configure(self, enabled: bool = True,
                  jsonl_path: Optional[str] = None,
                  sink: Optional[IO[str]] = None,
                  registry: Optional[MetricsRegistry] = None,
                  clock: Optional[Callable[[], float]] = None,
                  wall: Optional[Callable[[], float]] = None) -> None:
        """(Re)configure in place: pass ``jsonl_path`` to append span
        records to a file, or ``sink`` for an open stream (tests use
        ``io.StringIO``). Disabling closes an owned file sink."""
        if not enabled:
            self._flush_gc()  # before the lock: writing takes it
        with self._lock:
            if clock is not None:
                self._clock = clock
            if wall is not None:
                self._wall = wall
            if registry is not None:
                self._registry = registry
            if sink is not None:
                self._close_sink()
                self._sink, self._owns_sink = sink, False
            elif jsonl_path:
                self._close_sink()
                self._sink = open(jsonl_path, "a")
                self._owns_sink = True
                # Buffered writes (a flush per span would dominate the
                # span itself); make sure the tail reaches disk even
                # when nobody calls configure(enabled=False).
                import atexit

                atexit.register(self._close_sink)
            if not enabled:
                self._close_sink()
                self._gc_t0 = None
            elif not self._hears_jax:
                self._listen_to_jax()
                self._listen_to_gc()
            self.enabled = enabled

    def _listen_to_jax(self) -> None:
        """Once per tracer, on its first enabling: hear jax's duration
        events for as long as the tracer lives (jax keeps listeners for
        the life of the process, so the listener holds it weakly)."""
        import jax.monitoring

        ref = weakref.ref(self)

        def on_duration(event, duration_secs, **kwargs):
            tr = ref()
            if tr is not None and tr.enabled and event in _JAX_PHASES:
                tr._jax_phase(_JAX_PHASES[event], duration_secs,
                              kwargs.get("fun_name"))

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        self._hears_jax = True

    def _listen_to_gc(self) -> None:
        """Beside ``_listen_to_jax``: one ``gc.callbacks`` hook for the
        tracer's life, which holds it weakly and goes with it."""
        ref = weakref.ref(self)

        def on_gc(phase, info):
            tr = ref()
            if tr is None or not tr.enabled:
                return
            if phase == "start":
                tr._gc_t0 = (tr._wall(), tr._clock())
            elif tr._gc_t0 is not None:
                (ts, t0), tr._gc_t0 = tr._gc_t0, None
                stack = tr._stack()
                tr._gc_done.append(
                    (ts, (tr._clock() - t0) * 1e3,
                     stack[-1].id if stack else None,
                     {"generation": info["generation"],
                      "collected": info["collected"]}))

        gc.callbacks.append(on_gc)
        weakref.finalize(self, _forget_gc_hook, on_gc).atexit = False

    def _flush_gc(self) -> None:
        """Write the collections the hook has left (see the module
        docstring for why it does not write them itself)."""
        while self._gc_done:
            ts, dur_ms, parent, attrs = self._gc_done.pop(0)
            self._write_span("host.gc", ts, dur_ms, self._new_id(),
                             parent, attrs)

    def _close_sink(self) -> None:
        if self._sink is not None and self._owns_sink:
            try:
                self._sink.close()
            except Exception:
                pass
        self._sink, self._owns_sink = None, False

    # -- recording ------------------------------------------------------
    def span(self, name: str, **attrs):
        """``with tracer.span("train.step", step=i): ...`` — returns the
        shared no-op when disabled (the fast path)."""
        if not self.enabled:
            return _NOOP
        return _Span(self, name, attrs)

    def compile_event(self, batch: int, frames: int,
                      site: Optional[str] = None,
                      labels: Optional[dict] = None) -> None:
        """One fresh (B, T) XLA compile: always counted per rung in the
        registry; with tracing on, also emitted as a zero-duration
        record attributing the compile to its call site (the stack walk
        only happens when a trace is being written). Extra ``labels``
        (e.g. ``{"replica": "r0"}`` from a pooled inferencer's shape
        cache) merge into the counter's label set and the record."""
        rung = f"{int(batch)}x{int(frames)}"
        self._registry.count("compiles", 1,
                             labels={"rung": rung, **(labels or {})})
        if not self.enabled:
            return
        if site is None:
            site = _callsite()
        self._write({"event": "compile", "name": "compile",
                     "ts": round(self._wall(), 6), "dur_ms": 0.0,
                     "id": self._new_id(), "parent": None,
                     "rung": rung, "site": site, **(labels or {})})

    def emit(self, rec: dict) -> None:
        """Write one caller-built record through the JSONL sink — the
        request-trace summaries (``obs/context.py``) ride here so span
        and trace records share one stream, one lock, one schema.
        No-op when disabled (and free: one attribute read)."""
        if not self.enabled:
            return
        self._write(rec)

    # -- internals ------------------------------------------------------
    def _new_id(self) -> int:
        with self._lock:
            self._id += 1
            return self._id

    def _stack(self) -> list:
        stack = getattr(self._tl, "stack", None)
        if stack is None:
            stack = self._tl.stack = []
        return stack

    def _record(self, span: _Span, dur_ms: float) -> None:
        if self._gc_done:
            self._flush_gc()
        self._write_span(span.name, span.ts, dur_ms, span.id, span.parent,
                         span.attrs)

    def _jax_phase(self, name: str, duration_secs: float, fun) -> None:
        stack = self._stack()
        self._write_span(name, self._wall() - duration_secs,
                         duration_secs * 1e3, self._new_id(),
                         stack[-1].id if stack else None, {"fun": fun})

    def _write_span(self, name: str, ts: float, dur_ms: float, id_: int,
                    parent: Optional[int], attrs: dict) -> None:
        self._registry.observe("span_ms", dur_ms, labels={"name": name})
        self._write({"event": "span", "name": name, "ts": round(ts, 6),
                     "dur_ms": round(dur_ms, 6), "id": id_,
                     "parent": parent, **attrs})

    def _write(self, rec: dict) -> None:
        # Interleaving audit (threaded per-replica fan-out): the line
        # is serialized OUTSIDE the lock, and the single sink.write of
        # a complete line happens INSIDE it. io.TextIOWrapper/StringIO
        # writes are not atomic across threads without this — two
        # workers' records would tear mid-line. The concurrent-writer
        # regression test in tests/test_obs.py pins this down.
        sink = self._sink
        if sink is None:
            return
        line = json.dumps(rec, ensure_ascii=False, default=str) + "\n"
        with self._lock:
            sink.write(line)


tracer = Tracer()

_env_path = os.environ.get("DS2_TRACE", "")
if _env_path:
    tracer.configure(enabled=True, jsonl_path=_env_path)
