"""Device (the TPU): percent of the traced window -- first request's
start to last request's end -- in which no operation runs on the chip
(averaged over the chips)."""

from bench import trace


def read(w):
    busy = trace.busy_ns(w.trace, w.lo, w.hi)
    if busy is None or w.hi <= w.lo:
        return None
    return 100.0 * (1.0 - busy / (w.hi - w.lo))
