"""Predicate kernel: percent of its HBM roofline.  The least time is
the bytes Algorithm 1 needs for the traced requests
(``bench.kernels.predicate_bytes`` of each request's tuple, its
``desc``) over the chip's HBM bandwidth; the share is that over the
kernel's device time.  Bound by bytes: the kernel does a few logical
operations per word it loads."""

from bench import kernels, trace


def read(w):
    ns = trace.kernel_ns(w.trace, kernels.PREDICATE, w.lo, w.hi)
    if ns is None:
        return None
    need = sum(kernels.predicate_bytes(w.cell.config, r.desc)
               for r, _ in w.spans())
    return 100.0 * need / w.peak("hbm_bytes_per_s") / (ns * 1e-9)
