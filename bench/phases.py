#!/usr/bin/env python3
"""Split the device-idle time of a profiler trace into the program's
host phases.

    python3 bench/phases.py TRACE.xplane.pb

The fused path opens ``clutch.*`` spans around the host phases of each
request (``repro.kernels.fused_session`` ``SPAN_*``): ``clutch.session``
around the job and, inside it, ``clutch.resolve``, ``clutch.dispatch``,
``clutch.readback``, ``clutch.unpack`` and ``clutch.finish``.  Every
instant of a request in which no operation runs on the device is put
down to the innermost program span open at that instant, so a request's
phases sum to its span's length less the device's busy time inside it:
what ``bench/metrics/host_critical_ms.py`` reads.

The requests are the benchmark's ``bench_request`` spans where the
trace has them (``bench/run.py --trace 1``), else the program's own
``clutch.session`` spans.  Prints one JSON object: the requests, and
the mean milliseconds per request of each phase the trace holds, with
``host_unspanned_ms`` for time inside no phase span.  On a TPU the
trace's device timeline can sit a millisecond or two off the host's,
which moves time between adjacent phases (PERF.md); the totals hold.
"""

from __future__ import annotations

import argparse
import bisect
import json
import sys
from pathlib import Path

if not __package__:    # run as a script: make ``bench`` importable
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace  # noqa: E402
from bench.trace import Interval  # noqa: E402

PROGRAM = "clutch."
SESSION = "clutch.session"
#: Each phase's name in the output and the span it reads.
PHASES = {"index_resolve_ms": "clutch.resolve",
          "dispatch_ms": "clutch.dispatch",
          "readback_ms": "clutch.readback",
          "unpack_ms": "clutch.unpack",
          "host_finish_ms": "clutch.finish"}


def load_program(path: str) -> list[Interval]:
    """The program's spans in an ``.xplane.pb`` file, in order: the
    events named ``clutch.*`` on every host line (each line is named
    after its thread, and the main thread after the process)."""
    from jax.profiler import ProfileData

    out = [Interval(e.start_ns, e.end_ns, e.name)
           for plane in ProfileData.from_file(path).planes
           if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events
           if e.name.startswith(PROGRAM)]
    return sorted(out, key=lambda x: (x.start, -x.end))


def idle_by_program_span(device: dict[str, list[Interval]],
                         program: list[Interval], spans: list[Interval]
                         ) -> dict[str | None, float]:
    """Device-idle nanoseconds inside the request ``spans`` by the
    innermost program span open meanwhile (None where none is), summed
    over the spans and averaged over the device planes; with no device
    plane (a CPU trace) no operation ran, so every instant is idle.
    Each span is split into segments that cover it once."""
    out: dict[str | None, float] = {}
    if not spans:
        return out
    lo, hi = min(s.start for s in spans), max(s.end for s in spans)
    busy = [trace.union(ops, lo, hi) for ops in device.values()] or [[]]
    prog = sorted(program, key=lambda x: (x.start, -x.end))
    starts = [e.start for e in prog]
    for span in spans:
        inside = prog[bisect.bisect_left(starts, span.start):
                      bisect.bisect_left(starts, span.end)]
        for s, e, name in trace.innermost(inside, span.start, span.end):
            for merged in busy:
                idle = (e - s) - trace.overlap(merged, s, e)
                out[name] = out.get(name, 0.0) + idle / len(busy)
    return out


def split(device: dict[str, list[Interval]], program: list[Interval],
          spans: list[Interval]) -> dict[str, float]:
    """Mean milliseconds per request span of device-idle time in each
    phase whose span the trace holds, and ``host_unspanned_ms`` (inside
    ``clutch.session`` alone, or no program span); empty when the trace
    holds no program span or no request."""
    if not program or not spans:
        return {}
    idle = idle_by_program_span(device, program, spans)
    names = {e.name for e in program}
    per_request = 1e-6 / len(spans)
    out = {phase: idle.get(span, 0.0) * per_request
           for phase, span in PHASES.items() if span in names}
    out["host_unspanned_ms"] = (idle.get(SESSION, 0.0)
                                + idle.get(None, 0.0)) * per_request
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="an .xplane.pb file from jax.profiler")
    args = ap.parse_args(argv)
    tr = trace.load(args.trace)
    program = load_program(args.trace)
    if not program:
        print(f"phases: no {PROGRAM}* span in {args.trace}", file=sys.stderr)
        return 1
    spans = tr.spans or [e for e in program if e.name == SESSION]
    print(json.dumps({"requests": len(spans),
                      "device_planes": len(tr.device),
                      **split(tr.device, program, spans)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
