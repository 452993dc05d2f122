"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b \
        --shape train_4k [--multipod] [--kv-seq-shard] [--out results.json]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multipod]
    PYTHONPATH=src python -m repro.launch.dryrun --cg   # solver-path cells

Emits per cell: memory_analysis, cost_analysis FLOPs/bytes, collective
byte/count breakdown parsed from the optimized HLO, and the §Roofline
terms (TPU v5e constants).  Success of .lower().compile() for every cell
on the 16x16 and 2x16x16 meshes is deliverable (e).

The ``--cg`` cells run the solver path through ``distributed_solve`` (the
shard_map reduction backend, DESIGN.md §3).  Pick the pipeline depth for
a cell with the autotuner before dry-running it::

    from repro.launch.autotune import autotune_depth
    from benchmarks.timing_model import V5E
    best = autotune_depth(n=4_000_000, p=256, hw=V5E).best
    # -> run_cg_cell(mesh, l=best.l, unroll=best.unroll)

(DESIGN.md §5/§6.)
"""

# The 512 placeholder devices MUST be claimed before jax initializes —
# keep these two lines first (system prompt, MULTI-POD DRY-RUN §0).
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse
import json
import time
import traceback

import jax
import numpy as np

from repro.launch.cells import SHAPES, all_cells, build_cell
from repro.launch.mesh import make_production_mesh, n_chips
from repro.utils.hlo import summarize_collectives
from repro.utils.roofline import HW_V5E, cost_analysis_dict, roofline_terms


def run_cell(arch: str, shape_name: str, mesh, kv_seq_shard=False,
             verbose=True, pure_dp=False, split_kv=False,
             pipeline_l=0, decode_bf16=False) -> dict:
    from repro.models import attention as attn_mod
    t0 = time.time()
    cell = build_cell(arch, shape_name, mesh, kv_seq_shard=kv_seq_shard,
                      pure_dp=pure_dp, pipeline_l=pipeline_l)
    attn_mod.SPLIT_KV_AXIS = "model" if split_kv else None
    attn_mod.DECODE_UPCAST = not decode_bf16
    with jax.set_mesh(mesh):
        jitted = jax.jit(
            cell.fn,
            in_shardings=cell.in_shardings,
            out_shardings=cell.out_shardings,
        )
        lowered = jitted.lower(*cell.args)
        compiled = lowered.compile()
    attn_mod.SPLIT_KV_AXIS = None
    attn_mod.DECODE_UPCAST = True
    t1 = time.time()

    try:
        mem = compiled.memory_analysis()
        mem_info = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # CPU backend may not implement it
        mem_info = {"error": str(e)}

    cost = cost_analysis_dict(compiled)
    hlo = compiled.as_text()
    colls = summarize_collectives(hlo)
    chips = n_chips(mesh)
    terms = roofline_terms(cost, hlo, chips, HW_V5E)

    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "chips": chips,
        "compile_s": round(t1 - t0, 1),
        "flops": terms.flops,
        "hbm_bytes": terms.hbm_bytes,
        "coll_bytes": terms.coll_bytes,
        "coll_per_kind": colls.per_kind,
        "t_compute": terms.t_compute,
        "t_memory": terms.t_memory,
        "t_collective": terms.t_collective,
        "dominant": terms.dominant,
        "model_flops": cell.model_flops,
        "tokens": cell.tokens_per_step,
        "useful_fraction": terms.useful_fraction(cell.model_flops),
        "mfu": terms.mfu(cell.model_flops),
        "memory": mem_info,
        "kv_seq_shard": kv_seq_shard,
        "split_kv": split_kv,
        "pure_dp": pure_dp,
        "pipeline_l": pipeline_l,
        "decode_bf16": decode_bf16,
    }
    if verbose:
        print(f"[{arch} × {shape_name} × {rec['mesh']}] "
              f"compile {rec['compile_s']}s | {terms.row()} | "
              f"useful {rec['useful_fraction']:.3f} | MFU-bound {rec['mfu']:.3f}")
        print("  collectives:\n" + str(colls))
        print(f"  memory: {mem_info}")
    return rec


def _compile_costs(arch, shape_name, mesh, depth_units, kv_seq_shard,
                   pure_dp=False, split_kv=False, decode_bf16=False,
                   moe_constrain=False):
    """Compile a reduced-depth FULL-WIDTH cell with the layer scan
    unrolled, so cost_analysis counts every layer."""
    from repro.models import model as model_mod
    from repro.models import attention as attn_mod
    from repro.models import moe as moe_mod
    from repro.launch.cells import build_cell as _bc
    old = model_mod.SCAN_UNROLL
    model_mod.SCAN_UNROLL = True
    attn_mod.SPLIT_KV_AXIS = "model" if split_kv else None
    attn_mod.DECODE_UPCAST = not decode_bf16
    moe_mod.CONSTRAIN_EP = moe_constrain
    try:
        cell = _bc(arch, shape_name, mesh, kv_seq_shard=kv_seq_shard,
                   depth_units=depth_units, pure_dp=pure_dp)
        with jax.set_mesh(mesh):
            lowered = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                              out_shardings=cell.out_shardings).lower(*cell.args)
            compiled = lowered.compile()
    finally:
        model_mod.SCAN_UNROLL = old
        attn_mod.SPLIT_KV_AXIS = None
        attn_mod.DECODE_UPCAST = True
        moe_mod.CONSTRAIN_EP = False
    cost = cost_analysis_dict(compiled)
    per_kind = summarize_collectives(compiled.as_text()).per_kind
    return (float(cost.get("flops", 0.0) or 0.0),
            float(cost.get("bytes accessed", 0.0) or 0.0),
            per_kind)


def run_cell_roofline(arch: str, shape_name: str, mesh, kv_seq_shard=False,
                      verbose=True, units=(2, 4), pure_dp=False,
                      split_kv=False, decode_bf16=False,
                      moe_constrain=False) -> dict:
    """Roofline terms via per-layer extrapolation: XLA counts a rolled scan
    body once, so the full-depth compile undercounts FLOPs.  We compile the
    model at ``units`` repeat-units UNROLLED (full width, full batch) and
    extrapolate linearly in depth:  X(L) = fixed + L·per_unit.  The time
    scans inside Mamba2/RWKV6 stay rolled: their recurrence FLOPs are <1%
    of the projection FLOPs (noted in EXPERIMENTS.md)."""
    from repro.configs import get_config
    from repro.launch.cells import build_cell as _bc, layer_unit

    cfg_full = get_config(arch)
    n_units_full = cfg_full.n_layers // layer_unit(cfg_full)
    a, b = units
    t0 = time.time()
    fa, ba, ca = _compile_costs(arch, shape_name, mesh, a, kv_seq_shard,
                                pure_dp, split_kv, decode_bf16, moe_constrain)
    fb, bb, cb = _compile_costs(arch, shape_name, mesh, b, kv_seq_shard,
                                pure_dp, split_kv, decode_bf16, moe_constrain)
    t1 = time.time()

    def extrap(xa, xb):
        per = (xb - xa) / (b - a)
        fixed = xa - a * per
        return fixed + n_units_full * per

    flops = extrap(fa, fb)
    hbm = extrap(ba, bb)
    kinds = sorted(set(ca) | set(cb))
    per_kind = {}
    for k in kinds:
        va = ca.get(k, {"count": 0, "bytes": 0})
        vb = cb.get(k, {"count": 0, "bytes": 0})
        per_kind[k] = {"count": extrap(va["count"], vb["count"]),
                       "bytes": extrap(va["bytes"], vb["bytes"])}

    # synthesize roofline terms from the extrapolated numbers
    from repro.utils.roofline import _RING_FACTOR
    chips = n_chips(mesh)
    hw = HW_V5E
    t_coll = sum(_RING_FACTOR[k](chips) * v["bytes"] / hw.link_bw
                 for k, v in per_kind.items())
    coll_bytes = sum(v["bytes"] for v in per_kind.values())
    # model flops of the FULL cell; HLO numbers are PER-DEVICE
    cell_full = _bc(arch, shape_name, mesh, kv_seq_shard=kv_seq_shard,
                    pure_dp=pure_dp)
    t_compute = flops / hw.peak_flops
    t_memory = hbm / hw.hbm_bw
    t_bound = max(t_compute, t_memory, t_coll)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "chips": chips, "compile_s": round(t1 - t0, 1),
        "flops": flops, "hbm_bytes": hbm, "coll_bytes": coll_bytes,
        "coll_per_kind": per_kind,
        "t_compute": t_compute, "t_memory": t_memory, "t_collective": t_coll,
        "dominant": max(
            [("compute", t_compute), ("memory", t_memory),
             ("collective", t_coll)], key=lambda kv: kv[1])[0],
        "model_flops": cell_full.model_flops,
        "tokens": cell_full.tokens_per_step,
        "useful_fraction": (cell_full.model_flops / (flops * chips)
                            if flops else None),
        "mfu": (cell_full.model_flops / (t_bound * chips * hw.peak_flops)
                if t_bound else None),
        "kv_seq_shard": kv_seq_shard,
        "pure_dp": pure_dp,
        "split_kv": split_kv,
        "decode_bf16": decode_bf16,
        "moe_constrain": moe_constrain,
        "extrapolated_from_units": list(units),
    }
    if verbose:
        print(f"[ROOFLINE {arch} × {shape_name} × {rec['mesh']}] "
              f"compile {rec['compile_s']}s | compute {t_compute:.3e}s | "
              f"memory {t_memory:.3e}s | collective {t_coll:.3e}s | "
              f"dominant={rec['dominant']} | useful "
              f"{rec['useful_fraction']:.3f} | MFU-bound {rec['mfu']:.3f}")
    return rec


def run_cg_cell(mesh, problem="laplace2d", l=2, verbose=True,
                method="plcg", unroll=1) -> dict:
    """Dry-run of the paper's own solver path on the production mesh
    (flattened to 1-D domain decomposition)."""
    from repro.configs import get_config
    from repro.configs.problems import build_operator
    from repro.core.chebyshev import chebyshev_shifts
    from repro.parallel.distributed import (
        distributed_solve, make_solver_mesh)
    import jax.numpy as jnp

    prob = get_config(problem)
    n_dev = mesh.devices.size
    smesh = make_solver_mesh(n_dev)
    op = build_operator(prob)
    lmin, lmax = op.eig_bounds()
    kw = {}
    if method == "plcg":
        kw = dict(l=l, sigmas=chebyshev_shifts(lmin, lmax, l,
                                               dtype=jnp.float32),
                  unroll=unroll)
    b = jax.ShapeDtypeStruct((op.n,), jnp.float32)
    fn, arrays = distributed_solve(
        smesh, op, b, method=method,
        maxit=prob.maxit, tol=prob.tol, jit=False, **kw)
    t0 = time.time()
    from jax.sharding import NamedSharding, PartitionSpec as P
    bsh = NamedSharding(smesh, P("shards"))
    ash = jax.tree.map(lambda _: NamedSharding(smesh, P("shards")), arrays)
    lowered = jax.jit(fn, in_shardings=(bsh, ash)).lower(b, arrays)
    compiled = lowered.compile()
    t1 = time.time()
    cost = cost_analysis_dict(compiled)
    hlo = compiled.as_text()
    colls = summarize_collectives(hlo)
    terms = roofline_terms(cost, hlo, n_dev, HW_V5E)
    name = {"cg": f"cg-{problem}", "pcg": f"pcg-{problem}"}.get(
        method, f"plcg-{problem}-l{l}" + (f"-u{unroll}" if unroll > 1 else ""))
    rec = {
        "arch": name, "shape": f"n={op.n}",
        "mesh": str(n_dev), "chips": n_dev,
        "compile_s": round(t1 - t0, 1),
        "flops": terms.flops, "hbm_bytes": terms.hbm_bytes,
        "coll_bytes": terms.coll_bytes, "coll_per_kind": colls.per_kind,
        "t_compute": terms.t_compute, "t_memory": terms.t_memory,
        "t_collective": terms.t_collective, "dominant": terms.dominant,
    }
    if verbose:
        print(f"[{name} × {n_dev} shards] compile "
              f"{rec['compile_s']}s | {terms.row()}")
        print("  collectives:\n" + str(colls))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None, choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--cg", action="store_true")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--kv-seq-shard", action="store_true")
    ap.add_argument("--pure-dp", action="store_true",
                    help="replicate weights; batch over every mesh axis")
    ap.add_argument("--split-kv", action="store_true",
                    help="explicit split-KV decode merge (manual shard_map)")
    ap.add_argument("--pipeline-l", type=int, default=0,
                    help="train cells: delayed-gradient ring depth l")
    ap.add_argument("--decode-bf16", action="store_true",
                    help="decode: bf16 operands + f32 accumulation")
    ap.add_argument("--moe-constrain", action="store_true",
                    help="MoE: explicit EP sharding constraints")
    ap.add_argument("--moe-tp", action="store_true",
                    help="MoE: TP inside experts instead of EP")
    ap.add_argument("--roofline", action="store_true",
                    help="reduced-depth unrolled compiles + extrapolation")
    ap.add_argument("--out", type=str, default=None)
    args = ap.parse_args()

    meshes = []
    if args.both_meshes:
        meshes = [make_production_mesh(), make_production_mesh(multi_pod=True)]
    else:
        meshes = [make_production_mesh(multi_pod=args.multipod)]

    records, failures = [], []
    for mesh in meshes:
        if args.cg:
            # The dry-run matrix sticks to the stencil ice-sheet variant:
            # the unstructured `icesheet3d` partitions 500k FEM nodes
            # (setup-time RCM) — meaningful for a real launch, noise for
            # a compile-only sweep.
            for prob in ("laplace2d", "icesheet3d-stencil"):
                records.append(run_cg_cell(mesh, prob, method="cg"))
                records.append(run_cg_cell(mesh, prob, method="pcg"))
                for l in (1, 2, 3):
                    records.append(run_cg_cell(mesh, prob, l))
                records.append(run_cg_cell(mesh, prob, l=2, unroll=3))
            continue
        cells = all_cells() if args.all else [(args.arch, args.shape)]
        runner = run_cell_roofline if args.roofline else run_cell
        for arch, shape_name in cells:
            try:
                kw = dict(kv_seq_shard=args.kv_seq_shard,
                          pure_dp=args.pure_dp)
                if runner is run_cell:
                    kw["pipeline_l"] = args.pipeline_l
                kw["split_kv"] = args.split_kv
                kw["decode_bf16"] = args.decode_bf16
                if runner is run_cell_roofline:
                    kw["moe_constrain"] = args.moe_constrain
                from repro.launch import sharding as shd_mod
                shd_mod.MOE_TP = args.moe_tp
                records.append(runner(arch, shape_name, mesh, **kw))
            except Exception as e:
                traceback.print_exc()
                failures.append((arch, shape_name,
                                 "x".join(map(str, mesh.devices.shape)),
                                 repr(e)[:200]))

    if args.out:
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1, default=float)
        print(f"wrote {len(records)} records -> {args.out}")
    if failures:
        print(f"\nFAILURES ({len(failures)}):")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print(f"\nDRY-RUN OK: {len(records)} cells compiled")


if __name__ == "__main__":
    main()
