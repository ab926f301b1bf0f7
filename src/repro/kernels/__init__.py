"""TPU-native Clutch kernels (pallas_call + BlockSpec), jit wrappers in
ops.py, pure-jnp oracles in ref.py.

Kernels exist for the compute hot-spots the paper optimizes -- comparison
and its surrounding data path -- not for the generic transformer stack:
  clutch_merge     Algorithm 1 chunk merge over packed bit-planes
  temporal_encode  binary -> temporal-coding LUT construction
  bitserial_cmp    bit-serial borrow-chain baseline (paper's comparison)
  fused_query      fused range predicate + popcount (beyond-paper fusion);
                   also the resource-batched fused_predicate_banked /
                   gbdt_leafbits_banked grids behind the fused backend
  leaf_gather      GBDT leaf aggregation as MXU one-hot contraction
  minp_mask        serving sampler threshold mask via chunked comparator
  fused_session    the JAX-native session backend: one jitted program
                   per query kind sweeps every shard of a resource and
                   joins counts with a psum over a shard_map mesh

Two-backend contract: ``PudSession(backend="machine")`` runs the NumPy
machine simulator and its scheduled Timeline -- the DRAM-side cost
oracle; ``backend="fused"`` runs these kernels end-to-end under jit --
the wall-clock path -- with bit-exact results (integer/boolean work on
device, the few float aggregates finished host-side with the machine
path's exact NumPy expressions).  Fused executables are compile-cached
per (plan, table shape, query kind); scalars/features are traced
operands, so repeated jobs re-trace zero times.

Memory placement: the host-resolved row indices sit in SMEM, where a
scalar read may feed a dynamic sublane offset or a DMA's source; LUT
tiles sit in VMEM, except the predicate kernel's stacked LUT, which
stays in HBM and is gathered row by row (``fused_query.row_slabs``).
On a TPU the kernels compile to Mosaic; on the CPU backend (the tests)
they run in Pallas interpret mode; any other backend is refused
(:func:`~repro.kernels.common.use_interpret`).
"""

from . import ops, ref  # noqa: F401
