"""Leaf-bits kernel: percent of its HBM roofline.  The least time is
the bytes its work needs (``bench.kernels.leafbits_bytes`` of each
traced request's batch size, its ``desc``) over the chip's HBM
bandwidth; the share is that over the kernel's device time.
Bytes only: no peak of the vector unit's logical operations is
published, so a share bound by operations would read too high here."""

from bench import kernels, trace


def read(w):
    ns = trace.kernel_ns(w.trace, kernels.LEAFBITS, w.lo, w.hi)
    if ns is None:
        return None
    need = sum(kernels.leafbits_bytes(w.cell.config, r.desc)
               for r, _ in w.spans())
    return 100.0 * need / w.peak("hbm_bytes_per_s") / (ns * 1e-9)
