"""Quickstart: the `repro.pud` session API on all three substrates.

Runs the same range predicate (x0 < f < x1 over 100K records) through:
  1. a PudSession over the functional PuD machine model (Unmodified
     DRAM, traced + bus-scheduled commands),
  2. the Pallas kernel path (compiled on a TPU; interpreted when
     JAX's backend is the CPU, e.g. with JAX_PLATFORMS=cpu),
  3. the analytical DRAM cost model (throughput/energy projection),
and checks them against NumPy.

    PYTHONPATH=src python examples/quickstart.py
"""

import sys
import os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax.numpy as jnp
import numpy as np

from repro.core import cost
from repro.core.clutch import clutch_op_count
from repro.core.encoding import make_plan
from repro.core.machine import PuDArch
from repro.kernels import ops
from repro.pud import PudSession, Q1


def main() -> None:
    n_bits, chunks, n = 32, 12, 100_000
    rng = np.random.default_rng(0)
    values = rng.integers(0, 1 << n_bits, n, dtype=np.uint64)
    x0 = int(rng.integers(0, 1 << (n_bits - 1)))
    x1 = int(rng.integers(x0 + 1, 1 << n_bits))
    plan = make_plan(n_bits, chunks)
    print(f"range predicate {x0} < f < {x1} over {n} x {n_bits}-bit "
          f"values, {chunks} chunks -> {plan.rows_required} LUT rows")

    # 1. The session API over the PuD machine model: declare the table,
    #    submit the query as a job, read the result + scheduled stats.
    session = PudSession(sys_cfg=cost.DESKTOP, num_devices=1,
                         arch=PuDArch.UNMODIFIED)
    table = session.create_table(values[:, None], n_bits=n_bits,
                                 name="quickstart", cols_per_bank=65536)
    job = session.query(table, Q1(fi=0, x0=x0, x1=x1))
    bitmap_machine = job.result

    # 2. Pallas kernel path (Mosaic on a TPU, interpreted on the CPU
    #    backend): one predicate of the pair, checked element-wise.
    bitmap_kernel = np.asarray(ops.clutch_compare(
        jnp.asarray(values.astype(np.uint32)), x0,
        make_plan(n_bits, 5)))

    # 3. ground truth + cost model
    want = (values > x0) & (values < x1)
    assert (bitmap_machine == want).all()
    assert (bitmap_kernel == (values > x0)).all()
    print("bitmaps match NumPy on both substrates")
    print(f"session job: {len(job.timeline.waves)} scheduled waves, "
          f"makespan {job.stats.makespan_ns / 1e3:.2f} us "
          f"(per-op count closed form: "
          f"{clutch_op_count(5, PuDArch.UNMODIFIED)} PuD ops "
          f"for a 5-chunk compare)")

    for name, method in [("clutch", "clutch"), ("bit-serial", "bitserial")]:
        c = cost.pud_compare_cost(method, n_bits, PuDArch.UNMODIFIED,
                                  cost.DESKTOP, chunks=5)
        print(f"{name:11s}: {c.time_ns / 1e3:8.2f} us/batch "
              f"{c.throughput_geps:8.1f} Gelem/s "
              f"{c.elems_per_uj:10.0f} elem/uJ   (DDR4-2666 desktop)")
    cpu = cost.cpu_scan_cost(n_bits, cost.DESKTOP.parallel_cols,
                             cost.DESKTOP)
    print(f"{'cpu-scan':11s}: {cpu.time_ns / 1e3:8.2f} us/batch "
          f"{cpu.throughput_geps:8.2f} Gelem/s "
          f"{cpu.elems_per_uj:10.0f} elem/uJ   (BitWeaving-V)")


if __name__ == "__main__":
    main()
