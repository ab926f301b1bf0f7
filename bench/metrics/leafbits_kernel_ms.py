"""Leaf-bits kernel (``kernels/fused_query.py``
``gbdt_leafbits_banked``): device milliseconds of its launches per
request over the traced window."""

from bench import kernels, trace


def read(w):
    ns = trace.kernel_ns(w.trace, kernels.LEAFBITS, w.lo, w.hi)
    n = len(w.spans())
    return ns * 1e-6 / n if ns is not None and n else None
