"""Unstructured SpMV benchmark: ELL kernel throughput + partition-plan
structure on a random FEM mesh (DESIGN.md §12/§13).  Emits
``BENCH_spmv.json`` for the perf trajectory; CI gates the STRUCTURAL
metrics (``scripts/check_bench.py``), which a partitioner/ordering
regression moves and container timing noise cannot:

* ``ell_occupancy``        — useful fraction of stored ELL slots in the
                             production SLICED-ELL layout (degree-sorted
                             row buckets, per-slice padding;
                             ``sparse.sliced_ell_reorder``).  The
                             uniform padded-row number rides along as
                             ``ell_occupancy_padded``.
* ``plan_halo_fraction``   — halo rows shipped per shard / rows owned
                             (RCM quality: a worse ordering inflates the
                             send sets).
* ``plan_hops``            — neighbour-hop count (1 == the structured-
                             stencil regime; more means the ordering
                             failed to localize the band).

The Pallas kernel is timed COMPILED when a real accelerator backend is
present (``kernel_mode: "compiled"``); on CPU CI it falls back to
interpret mode (``"interpret"`` — a correctness vehicle, not a speed
number).  Modeled HBM bytes per SpMV ride alongside the wall clocks so
the trajectory has a machine-independent roofline column.

    PYTHONPATH=src python -m benchmarks.spmv_bench [--n 4096] [--out PATH]
"""

import argparse
import os
import time

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402  (after XLA_FLAGS)
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

jax.config.update("jax_enable_x64", True)

from jax.sharding import PartitionSpec as P  # noqa: E402

from benchmarks.lane import (  # noqa: E402
    compiled_out,
    resolve_kernel_mode,
    write_payload,
)
from repro.kernels import ops as kops  # noqa: E402
from repro.linalg import plan_for, random_fem_mesh  # noqa: E402
from repro.linalg.sparse import sliced_ell_reorder  # noqa: E402
from repro.parallel.distributed import (  # noqa: E402
    make_solver_mesh,
    partitioned_solver_ops,
)


def time_best(fn, repeats=5):
    fn()                                     # warmup / compile
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def spmv_hbm_bytes(nnz: int, n: int, occupancy: float = 1.0,
                   dsize: int = 8) -> int:
    """Modeled HBM traffic of one ELL SpMV: every STORED slot streams a
    value (dsize) + column index (4B); x is gathered (~n reads) and y
    written once.  ``occupancy`` < 1 inflates the stored slots over nnz
    — the padding-waste term sliced ELL removes (DESIGN.md §13)."""
    slots = int(round(nnz / max(occupancy, 1e-9)))
    return slots * (dsize + 4) + 2 * n * dsize


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=4096, help="mesh nodes")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--slice-rows", type=int, default=64)
    ap.add_argument("--out", type=str, default=None)
    ap.add_argument("--kernel-mode", choices=("auto", "compiled"),
                    default="auto",
                    help="'compiled' demands a real accelerator and "
                         "writes a machine-readable skip payload to "
                         "--out when there is none (benchmarks.lane)")
    args = ap.parse_args()

    out = compiled_out(args.kernel_mode, args.out, "BENCH_spmv.json")
    mode, skip = resolve_kernel_mode(args.kernel_mode)
    if skip is not None:
        write_payload(out, skip)
        return

    n_dev = len(jax.devices())
    op = random_fem_mesh(args.seed, args.n)
    # plan_for populates the memo partitioned_solver_ops reads below —
    # RCM + send-set construction runs once, not twice.
    plan = plan_for(op, n_dev)
    x = jnp.asarray(np.random.default_rng(1).standard_normal(op.n))

    # --- single-device applies -------------------------------------------
    # x always passes as a real argument — a zero-arg jitted closure
    # would constant-fold the whole SpMV and time a cached fetch.
    apply_jnp = jax.jit(op.apply)
    t_jnp = time_best(lambda: apply_jnp(x))
    # Time the COMPILED kernel on a real backend; interpret on CPU CI.
    interpret = mode == "interpret"
    kern = jax.jit(lambda xx: kops.ell_spmv_apply(
        xx, op.cols, op.vals, interpret=interpret))
    t_kern = time_best(lambda: kern(x))

    # --- sliced ELL (degree-sorted buckets, per-slice padding) -----------
    sliced, sperm = sliced_ell_reorder(op, args.slice_rows)
    xs = x[jnp.asarray(sperm)]
    sliced_apply = jax.jit(sliced.apply)
    t_sliced = time_best(lambda: sliced_apply(xs))

    # --- distributed halo SpMV on the simulated mesh ---------------------
    mesh = make_solver_mesh(n_dev)
    arrays, build, _perm = partitioned_solver_ops(op, None, n_dev, "shards")
    arr_specs = jax.tree.map(lambda _: P("shards"), arrays)
    fn = jax.shard_map(
        lambda xl, loc: build(loc).apply_a(xl), mesh=mesh,
        in_specs=(P("shards"), arr_specs), out_specs=P("shards"),
        check_vma=False)
    xp = x[jnp.asarray(plan.perm)]
    dist = jax.jit(fn)
    t_dist = time_best(lambda: dist(xp, arrays))

    nnz = op.nnz
    occ_padded = float(nnz / (op.n * op.w))
    occ_sliced = sliced.occupancy()
    payload = {
        "mesh_devices": n_dev,
        "problem": {"n": op.n, "nnz": nnz, "ell_width": op.w},
        # structural metrics (gated — deterministic given the seed):
        "ell_occupancy": occ_sliced,
        "ell_occupancy_padded": occ_padded,
        "sliced_padding_waste": sliced.padding_waste(),
        "sliced_rows_per_slice": args.slice_rows,
        "sliced_n_slices": len(sliced.slice_cols),
        "plan_halo_fraction": plan.halo_rows_fraction(),
        "plan_hops": plan.hops,
        "plan_bandwidth": plan.band,
        "plan_neighbor_bytes": plan.neighbor_bytes(),
        # modeled HBM traffic (machine-independent roofline column):
        "spmv_hbm_bytes_padded": spmv_hbm_bytes(nnz, op.n, occ_padded),
        "spmv_hbm_bytes_sliced": spmv_hbm_bytes(nnz, op.n, occ_sliced),
        # informational wall-clock (not gated — container noise):
        "kernel_mode": mode,
        "jax_backend": jax.default_backend(),
        "jnp_spmv_s": t_jnp,
        "kernel_spmv_s": t_kern,
        "sliced_spmv_s": t_sliced,
        "distributed_spmv_s": t_dist,
        "jnp_spmv_gnnz_per_s": nnz / t_jnp / 1e9,
    }
    write_payload(out, payload)


if __name__ == "__main__":
    main()
