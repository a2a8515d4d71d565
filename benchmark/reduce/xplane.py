"""From a profiler trace (``.xplane.pb``) to device numbers.

Read with ``jax.profiler.ProfileData`` and nothing else. What a TPU
trace holds (looked at by hand, PR 22): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` has one event per executed
HLO instruction (the event's name is the instruction's text, so a
Mosaic kernel is an event whose name holds
``custom_call_target="tpu_custom_call"``), whose line ``Async XLA
Ops`` has the start-to-done spans of asynchronous copies and
collectives, and whose line ``XLA Modules`` has one event per executed
program. ``jax.profiler.TraceAnnotation`` events land on the ``python``
line of ``/host:CPU``. All start times are nanoseconds on one clock.

The benchmark's own spans are taken on ``time.perf_counter``; one
annotation, ``bench.anchor``, entered at a known ``perf_counter``
reading, ties the two clocks together.

Definitions:

  busy       union of the ``XLA Ops`` intervals inside the window, per
             chip. Asynchronous spans are not "an operation ran": they
             last from issue to completion whatever the chip does.
  idle       window minus busy. Each idle gap is shared out among the
             host spans that overlap it, by overlap; what no span
             covers goes to ``(no span)``.
  kernels    events whose name holds the Mosaic custom-call target.
  collective events (either line) whose instruction is an all-reduce,
             all-gather, reduce-scatter, collective-permute or
             all-to-all (their ``-start``/``-done`` halves included);
             exposed = their union minus the union of every other
             ``XLA Ops`` event.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Sequence, Tuple

ANCHOR = "bench.anchor"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
_COLLECTIVE = re.compile(
    r"(?<![-\w])(all-reduce|all-gather|reduce-scatter|"
    r"collective-permute|all-to-all)(-start|-done)?\(")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")

Interval = Tuple[float, float]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]
             ) -> List[Interval]:
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    b = list(b)
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def short_name(name: str, limit: int = 96) -> str:
    """An HLO instruction's text cut to what identifies it: its name,
    its opcode and its result shape; kernels are marked."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:limit]
    shape, _, tail = rest.partition(" ")
    if rest.startswith("("):  # tuple result
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                shape, tail = rest[:i + 1], rest[i + 2:]
                break
    opcode = tail.split("(", 1)[0]
    shape = re.sub(r"\{[^}]*\}", "", shape)
    mark = " [mosaic]" if KERNEL_MARK in name else ""
    return f"{head} {opcode}{mark} {shape}"[:limit]


class DeviceTrace:
    def __init__(self, index: int):
        self.index = index
        self.ops: List[Tuple[float, float, str]] = []      # start,end,name
        self.async_ops: List[Tuple[float, float, str]] = []
        self.modules: List[Tuple[float, float, str]] = []


class Trace:
    def __init__(self):
        self.devices: Dict[int, DeviceTrace] = {}
        self.annotations: List[Tuple[float, float, str]] = []

    def anchor_ns(self) -> Optional[float]:
        for a, _, name in self.annotations:
            if name == ANCHOR:
                return a
        return None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    tr = Trace()
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = tr.devices.setdefault(int(m.group(1)),
                                        DeviceTrace(int(m.group(1))))
            for line in plane.lines:
                dest = {OPS_LINE: dev.ops, ASYNC_LINE: dev.async_ops,
                        MODULES_LINE: dev.modules}.get(line.name)
                if dest is None:
                    continue
                for e in line.events:
                    dest.append((e.start_ns, e.start_ns + e.duration_ns,
                                 e.name))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        tr.annotations.append(
                            (e.start_ns, e.start_ns + e.duration_ns,
                             e.name))
    return tr


def reduce_trace(tr: Trace, window_ns: Interval,
                 spans_ns: Sequence[Tuple[str, float, float]] = (),
                 top: int = 10) -> dict:
    """The numbers of one traced window. ``spans_ns`` are flat host
    spans (name, start, end) on the trace's clock."""
    lo, hi = window_ns
    window = hi - lo
    if window <= 0 or not tr.devices:
        raise ValueError("empty window or no device plane in the trace")
    spans_ns = sorted(spans_ns, key=lambda s: s[1])
    span_ends = [e for _, _, e in spans_ns]
    per_chip = []
    op_time: Dict[str, float] = {}
    gap_by_span: Dict[str, float] = {}
    longest: List[Tuple[float, float, float]] = []  # (len, a, b)
    kernel_ns = exposed_ns = collective_ns = 0.0
    n_modules = 0
    for dev in tr.devices.values():
        ops = [(max(a, lo), min(b, hi), n) for a, b, n in dev.ops
               if min(b, hi) > max(a, lo)]
        busy = union([(a, b) for a, b, _ in ops])
        busy_ns = total(busy)
        for a, b, n in ops:
            key = short_name(n)
            op_time[key] = op_time.get(key, 0.0) + (b - a)
            if KERNEL_MARK in n:
                kernel_ns += b - a
        coll = [(a, b) for a, b, n in ops if _COLLECTIVE.search(n)]
        coll += [(max(a, lo), min(b, hi)) for a, b, n in dev.async_ops
                 if _COLLECTIVE.search(n) and min(b, hi) > max(a, lo)]
        compute = union([(a, b) for a, b, n in ops
                         if not _COLLECTIVE.search(n)])
        coll_u = union(coll)
        collective_ns += total(coll_u)
        exposed_ns += total(subtract(coll_u, compute))
        n_modules += sum(1 for a, b, _ in dev.modules
                         if lo <= a < hi)
        for a, b in subtract([(lo, hi)], busy):
            longest.append((b - a, a, b))
            covered = 0.0
            # Flat spans sorted by start have sorted ends too: skip to
            # the first one that ends after the gap begins.
            i = bisect.bisect_right(span_ends, a)
            while i < len(spans_ns) and spans_ns[i][1] < b:
                name, s, e = spans_ns[i]
                ov = min(b, e) - max(a, s)
                if ov > 0:
                    gap_by_span[name] = gap_by_span.get(name, 0.0) + ov
                    covered += ov
                i += 1
            if b - a - covered > 0:
                gap_by_span["(no span)"] = (
                    gap_by_span.get("(no span)", 0.0) + b - a - covered)
        per_chip.append({"chip": dev.index, "busy_s": busy_ns / 1e9,
                         "idle_pct": 100.0 * (1 - busy_ns / window)})
    n = len(per_chip)
    longest.sort(reverse=True)

    def span_at(a, b):
        best, best_ov = "(no span)", 0.0
        for name, s, e in spans_ns:
            ov = min(b, e) - max(a, s)
            if ov > best_ov:
                best, best_ov = name, ov
        return best

    return {
        "window_s": window / 1e9,
        "busy_s": sum(c["busy_s"] for c in per_chip) / n,
        "idle_pct": sum(c["idle_pct"] for c in per_chip) / n,
        "per_chip": per_chip,
        "kernel_s": kernel_ns / 1e9 / n,
        "collective_s": collective_ns / 1e9 / n,
        "collective_exposed_s": exposed_ns / 1e9 / n,
        "programs": n_modules / n,
        "op_seconds": {k: v / 1e9 / n for k, v in op_time.items()},
        "longest_gaps": [[span_at(a, b), g / 1e9]
                         for g, a, b in longest[:top]],
        "breakdown": {
            "device_ops": [[k, v / 1e9 / n] for k, v in sorted(
                op_time.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[k, v / 1e9 / n] for k, v in sorted(
                gap_by_span.items(), key=lambda kv: -kv[1])[:top]],
        },
    }


def kernel_events(tr: Trace, window_ns: Interval
                  ) -> List[Tuple[str, float]]:
    """(instruction text, seconds) of every Mosaic kernel event inside
    the window, all chips."""
    lo, hi = window_ns
    out = []
    for dev in tr.devices.values():
        for a, b, n in dev.ops:
            if KERNEL_MARK in n and a >= lo and b <= hi:
                out.append((n, (b - a) / 1e9))
    return out
