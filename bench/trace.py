"""Reduction of a profiler trace to the intervals the per-layer metrics
read.

A :class:`Trace` holds three kinds of interval, all on the profiler's
one clock (nanoseconds from the start of the trace):

* ``device``: per device plane (``/device:TPU:<n>``), the events of its
  ``XLA Ops`` line -- one per operation that ran on that chip;
* ``python``: the Python tracer's function events on the host, properly
  nested per thread;
* ``spans``: the benchmark's own ``bench_request`` annotations, one per
  request, each carrying the request's index ``i``.

Busy time is the union of a device's operation intervals; a share or a
mean over several chips is taken per chip and averaged.  Idle time is
attributed to the innermost host function running at each instant.
"""

from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field

SPAN = "bench_request"


@dataclass(frozen=True)
class Interval:
    start: float
    end: float
    name: str = ""
    stats: tuple = ()

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Trace:
    device: dict[str, list[Interval]] = field(default_factory=dict)
    python: list[list[Interval]] = field(default_factory=list)
    spans: list[Interval] = field(default_factory=list)

    def span_index(self, span: Interval) -> int:
        return int(dict(span.stats)["i"])


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` file written by ``jax.profiler``."""
    from jax.profiler import ProfileData

    tr = Trace()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            ops = [Interval(e.start_ns, e.end_ns, e.name)
                   for line in plane.lines if line.name == "XLA Ops"
                   for e in line.events]
            if ops:
                tr.device[plane.name] = sorted(ops, key=lambda x: x.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = []
                for e in line.events:
                    if e.name == SPAN:
                        tr.spans.append(Interval(
                            e.start_ns, e.end_ns, e.name,
                            tuple((k, str(v)) for k, v in e.stats)))
                    elif line.name == "python":
                        evs.append(Interval(e.start_ns, e.end_ns,
                                            e.name.lstrip("$")))
                if evs:
                    tr.python.append(evs)
    tr.spans.sort(key=lambda x: x.start)
    return tr


def union(intervals: list[Interval], lo: float, hi: float
          ) -> list[tuple[float, float]]:
    """Merged ``[start, end)`` pairs of ``intervals`` clipped to
    ``[lo, hi)``."""
    out: list[list[float]] = []
    for iv in sorted(intervals, key=lambda x: x.start):
        s, e = max(iv.start, lo), min(iv.end, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(merged: list[tuple[float, float]], lo: float,
            hi: float) -> float:
    """Length of ``[lo, hi)`` covered by sorted, disjoint ``merged``."""
    k = max(bisect.bisect_right(merged, (lo, float("inf"))) - 1, 0)
    total = 0.0
    for s, e in merged[k:]:
        if s >= hi:
            break
        total += max(0.0, min(e, hi) - max(s, lo))
    return total


def window(tr: Trace) -> tuple[float, float] | None:
    """From the first request span's start to the last one's end."""
    if not tr.spans:
        return None
    return tr.spans[0].start, max(s.end for s in tr.spans)


def busy_ns(tr: Trace, lo: float, hi: float) -> float | None:
    """Busy time in ``[lo, hi)`` averaged over the device planes; None
    when the trace holds no device."""
    if not tr.device:
        return None
    return sum(sum(e - s for s, e in union(ops, lo, hi))
               for ops in tr.device.values()) / len(tr.device)


def kernel_ns(tr: Trace, pattern: re.Pattern, lo: float,
              hi: float) -> float | None:
    """Device time of the operations whose :func:`op_label` matches
    ``pattern`` and that start in ``[lo, hi)``, summed over the chips
    and divided by their number; None when no such operation ran."""
    n, total = 0, 0.0
    for ops in tr.device.values():
        for op in ops:
            if lo <= op.start < hi and pattern.search(op_label(op.name)):
                n += 1
                total += op.dur
    return total / len(tr.device) if n else None


def innermost(events: list[Interval], lo: float, hi: float
              ) -> list[tuple[float, float, str | None]]:
    """Split ``[lo, hi)`` into segments, each named by the innermost of
    the properly nested ``events`` that covers it (None where none
    does)."""
    segs: list[tuple[float, float, str | None]] = []
    stack: list[tuple[float, str]] = []
    cur = lo

    def emit(upto: float) -> None:
        nonlocal cur
        upto = min(upto, hi)
        if upto > cur:
            segs.append((cur, upto, stack[-1][1] if stack else None))
            cur = upto

    for ev in sorted(events, key=lambda x: (x.start, -x.end)):
        if ev.start >= hi:
            break
        while stack and stack[-1][0] <= ev.start:
            emit(stack[-1][0])
            stack.pop()
        emit(ev.start)
        # a child never outlives its parent, whatever the clock's rounding
        end = min(ev.end, stack[-1][0]) if stack else ev.end
        stack.append((end, ev.name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    emit(hi)
    return segs


def idle_by_host(tr: Trace, lo: float, hi: float) -> dict[str, float]:
    """Device-idle nanoseconds in ``[lo, hi)`` by the innermost host
    function running meanwhile (averaged over the device planes)."""
    out: dict[str, float] = {}
    if not tr.device:
        return out
    segs = [s for evs in tr.python for s in innermost(evs, lo, hi)]
    if not tr.python:
        segs = [(lo, hi, None)]
    for ops in tr.device.values():
        busy = union(ops, lo, hi)
        for s, e, name in segs:
            idle = (e - s) - overlap(busy, s, e)
            if idle > 0:
                key = name or "no host function"
                out[key] = out.get(key, 0.0) + idle / len(tr.device)
    return out


_LAYOUT = re.compile(r"\{[^{}]*\}")
_HLO = re.compile(r"^%\S+ = (.*?) ([\w-]+)\(([^)]*)\)")
_TARGET = re.compile(r'custom_call_target="(\w+)"')


def op_label(name: str) -> str:
    """A short label for an HLO operation: its kind (a custom call's
    target), its result shape and its operands' shapes, without
    layouts."""
    plain = name
    while True:
        stripped = _LAYOUT.sub("", plain)
        if stripped == plain:
            break
        plain = stripped
    m = _HLO.match(plain)
    if not m:
        return name[:160]
    out, kind, args = m.groups()
    target = _TARGET.search(plain)
    shapes = ", ".join(a.split()[0] for a in args.split(", ") if a)
    return f"{target.group(1) if target else kind} {out} <- ({shapes})"[:160]


def breakdown(tr: Trace, lo: float, hi: float, top: int = 10) -> dict:
    """The device operations that took most time and the device-idle
    time by what the host was doing, in seconds, ``top`` of each."""
    ops: dict[str, float] = {}
    for plane in tr.device.values():
        for op in plane:
            if lo <= op.start < hi:
                key = op_label(op.name)
                ops[key] = ops.get(key, 0.0) + op.dur / len(tr.device)
    idle = idle_by_host(tr, lo, hi)

    def ranked(d: dict[str, float]) -> list:
        return [[k, v * 1e-9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"device_ops": ranked(ops), "idle_gaps": ranked(idle)}
