"""Executor host layer (``kernels/fused_session.py``
``FusedTableExec``/``FusedGbdtExec``): mean milliseconds per request
during which the request's span is open and no operation runs on the
device -- index resolution, dispatch, readback, unpacking and the host
finish, all on the request's critical path."""

from bench import trace


def read(w):
    if not w.trace.device:
        return None
    busy = {name: trace.union(ops, w.lo, w.hi)
            for name, ops in w.trace.device.items()}
    pairs = w.spans()
    if not pairs:
        return None
    total = 0.0
    for _, span in pairs:
        covered = sum(trace.overlap(b, span.start, span.end)
                      for b in busy.values()) / len(busy)
        total += span.dur - covered
    return total / len(pairs) * 1e-6
