"""Staged ring-reduction benchmark: ladder structure and wire accounting
on the simulated 8-device mesh (DESIGN.md §14).  Emits
``BENCH_reduce.json``; CI gates the STRUCTURAL metrics
(``scripts/check_bench.py``), all deterministic given shapes — container
timing noise cannot move any of them:

* ``staged_dotblock_allreduces``   — all-reduce count in the compiled
                                     staged p(l)-CG trace window.  MUST
                                     be 0: the dot block is tagged
                                     ppermute hops, nothing else (the
                                     tentpole's HLO acceptance).
* ``hops_per_window_min``          — ladder hops in the thinnest traced
                                     iteration window; >= l means the
                                     hop-per-iteration schedule really
                                     spreads the reduction across the
                                     in-flight window.
* ``staged_starts_per_window_max`` — hop-0 permutes per window (the
                                     logical-reduction count); 1 means
                                     one handle enters the wire per
                                     iteration, batching widens the
                                     payload, never the handle count.
* ``fp32_hop_payload_over_monolithic`` — per-hop wire bytes of the fp32
                                     payload ladder vs the fp64
                                     monolithic reduction payload: the
                                     mixed-precision option halves the
                                     latency-bound message size, gated
                                     at <= 0.55x.
* parity columns                   — staged-vs-monolithic residual
                                     histories on a stencil solve
                                     (bitwise: max |dh| == 0.0) and the
                                     fp32-payload bounded tail.

Honest accounting rides alongside: ``staged_total_wire_bytes`` is the
(P-1)-hop ring allgather's TOTAL per-shard traffic, which exceeds a
bandwidth-optimal tree all-reduce's — the ladder targets the
latency-bound small-payload regime (K = 2l+1 entries), where per-hop
message size and hop count dominate and aggregate bytes do not.

Measured primitive wall clocks (``measured_hop_time_s`` /
``measured_allreduce_time_s``) ride along too: one ring hop vs one
monolithic psum of the same K-entry payload on the live mesh.  On the
opt-in compiled lane (``--kernel-mode compiled``, accelerator required —
CPU containers get a machine-readable skip payload instead, see
``benchmarks.lane``) these time the real interconnect and feed
``launch.autotune.recalibrate_profile`` (alpha / alpha_hop).

    PYTHONPATH=src python -m benchmarks.reduce_bench [--l 2] [--out PATH]
        [--kernel-mode auto|compiled]
"""

import argparse
import os
import time

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402  (after XLA_FLAGS)
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

jax.config.update("jax_enable_x64", True)

from benchmarks.lane import (  # noqa: E402
    compiled_out,
    resolve_kernel_mode,
    write_payload,
)
from repro.core.chebyshev import shifts_for_operator  # noqa: E402
from repro.linalg import Stencil2D5  # noqa: E402
from repro.parallel import get_backend  # noqa: E402
from repro.parallel.distributed import make_solver_mesh  # noqa: E402
from repro.parallel.reduction import (  # noqa: E402
    hop_payload_bytes,
    reduction_wire_bytes,
)
from repro.utils.trace import plcg_overlap_report  # noqa: E402


def _time_best(fn, repeats=7):
    jax.block_until_ready(fn())              # warmup / compile
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def measured_collective_times(n_dev: int, l: int) -> dict:
    """Wall clock of the two reduction primitives on THIS mesh: one ring
    hop (``lax.ppermute`` of the K-entry dot-block payload — the ladder's
    unit cost, ``timing_model.ring_hop_time``) and one monolithic
    ``lax.psum`` of the same payload.  These feed
    ``launch.autotune.recalibrate_profile`` (alpha_hop / alpha): on a
    real accelerator mesh they time the interconnect; on the simulated
    CPU mesh they time XLA's intra-process collectives — the
    ``collective_timing_basis`` key says which one a reader is holding.
    """
    mesh = make_solver_mesh(n_dev)
    k = 2 * l + 1
    x = jnp.asarray(np.arange(n_dev * k, dtype=np.float64))
    ring = [(i, (i + 1) % n_dev) for i in range(n_dev)]
    hop = jax.jit(jax.shard_map(
        lambda v: lax.ppermute(v, "shards", ring), mesh=mesh,
        in_specs=P("shards"), out_specs=P("shards"), check_vma=False))
    allred = jax.jit(jax.shard_map(
        lambda v: lax.psum(v, "shards"), mesh=mesh,
        in_specs=P("shards"), out_specs=P(), check_vma=False))
    return {
        "measured_hop_time_s": _time_best(lambda: hop(x)),
        "measured_allreduce_time_s": _time_best(lambda: allred(x)),
        "collective_timing_basis": (
            "accelerator interconnect"
            if jax.default_backend() in ("tpu", "gpu")
            else "XLA CPU intra-process collectives (simulated mesh)"),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nx", type=int, default=32)
    ap.add_argument("--ny", type=int, default=24)
    ap.add_argument("--l", type=int, default=2)
    ap.add_argument("--stages", type=int, default=2)
    ap.add_argument("--out", type=str, default=None)
    ap.add_argument("--kernel-mode", choices=("auto", "compiled"),
                    default="auto",
                    help="'compiled' demands a real accelerator mesh "
                         "and writes a machine-readable skip payload "
                         "to --out when there is none (benchmarks.lane)")
    args = ap.parse_args()

    out = compiled_out(args.kernel_mode, args.out, "BENCH_reduce.json")
    mode, skip = resolve_kernel_mode(args.kernel_mode)
    if skip is not None:
        write_payload(out, skip)
        return

    n_dev = len(jax.devices())
    op = Stencil2D5(args.nx, args.ny)
    l = args.l
    sig = shifts_for_operator(op, l)
    b = jnp.asarray(np.random.default_rng(0).standard_normal(op.n))
    bspec = jax.ShapeDtypeStruct((op.n,), jnp.float64)

    be_staged = get_backend("shard_map", n_shards=n_dev, reduction="staged",
                            reduction_stages=args.stages)
    be_mono = get_backend("shard_map", n_shards=n_dev)

    # --- traced schedule structure (compiled HLO, deterministic) ---------
    rep = plcg_overlap_report(be_staged, op, bspec, l=l, window=l + 2,
                              sigmas=sig)
    hops_min = min(rep.reduce_hops_per_window.values())
    starts_max = max(rep.staged_starts_per_window.values())

    # --- solve parity (bitwise on stencils; deterministic) ---------------
    kw = dict(method="plcg", l=l, sigmas=sig, tol=1e-10, maxit=2000)
    r_mono = be_mono.solve(op, b, **kw)
    r_staged = be_staged.solve(op, b, **kw)
    hm = np.asarray(r_mono.res_history)
    hs = np.asarray(r_staged.res_history)
    parity_max_abs = float(np.abs(hm - hs).max())

    be_fp32 = get_backend("shard_map", n_shards=n_dev, reduction="staged",
                          reduction_stages=args.stages,
                          reduction_dtype=jnp.float32)
    r_fp32 = be_fp32.solve(op, b, **kw)
    h32 = np.asarray(r_fp32.res_history)
    m = (hm >= 0) & (h32 >= 0)
    fp32_tail = float((np.abs(hm[m] - h32[m]) / float(r_mono.norm0)).max())

    # --- wire accounting (analytic, shape-determined) --------------------
    mono_payload = hop_payload_bytes(l, dsize=8)        # (2l+1) f64 entries
    hop64 = hop_payload_bytes(l, dsize=8)
    hop32 = hop_payload_bytes(l, dsize=4)

    payload = {
        "mesh_devices": n_dev,
        "kernel_mode": mode,
        "jax_backend": jax.default_backend(),
        "problem": {"n": op.n, "nx": args.nx, "ny": args.ny, "l": l,
                    "stages": args.stages},
        # structural gates (deterministic):
        "staged_dotblock_allreduces": rep.n_collectives,
        "hops_per_window_min": hops_min,
        "staged_starts_per_window_max": starts_max,
        "max_in_flight": rep.max_in_flight,
        "hops_in_flight": rep.hops_in_flight,
        "halos_in_flight": rep.halos_in_flight,
        # wire bytes (analytic; the fp32 ratio is gated <= 0.55):
        "monolithic_payload_bytes_fp64": mono_payload,
        "staged_hop_payload_bytes_fp64": hop64,
        "staged_hop_payload_bytes_fp32": hop32,
        "fp32_hop_payload_over_monolithic": hop32 / mono_payload,
        "staged_total_wire_bytes_fp64": reduction_wire_bytes(n_dev, l,
                                                             dsize=8),
        "staged_total_wire_bytes_fp32": reduction_wire_bytes(n_dev, l,
                                                             dsize=4),
        # parity (deterministic given seed/mesh):
        "staged_vs_monolithic_hist_max_abs": parity_max_abs,
        "staged_bitwise_parity": parity_max_abs == 0.0,
        "fp32_payload_tail_rel": fp32_tail,
        "fp32_converged": bool(r_fp32.converged),
        "iters_monolithic": int(r_mono.iters),
        "iters_staged": int(r_staged.iters),
        "iters_fp32": int(r_fp32.iters),
    }
    # Measured primitive wall clocks (informational here, the
    # recalibration inputs on the compiled lane):
    payload.update(measured_collective_times(n_dev, l))
    write_payload(out, payload)


if __name__ == "__main__":
    main()
