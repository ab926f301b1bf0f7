"""Session layer (``pud/session.py`` ``PudSession.query``/``predict``):
mean milliseconds per request between the benchmark's host clock around
the call and the program's own ``JobResult.wallclock_ns`` around its
executor -- the planner lookup, query objects and job wrapping."""


def read(w):
    gaps = [r.latency_s * 1e3 - r.wallclock_ns * 1e-6 for r in w.requests
            if r.wallclock_ns is not None and not r.failed]
    return sum(gaps) / len(gaps) if gaps else None
