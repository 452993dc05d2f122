"""Chip smoke test: the p(l)-CG main path on TPU at the paper's size.

    python chip_smoke.py             # one chip: single solve + served stream
    python chip_smoke.py --chips 4   # four chips: the 4-shard mesh only

Default phase (one chip), on ``laplace2d`` (2048 x 2048, 4,194,304
unknowns, the paper's Fig. 3 problem):

* a single p(l)-CG solve through ``get_backend("local").make_solver``;
* a served stream: ``SolverService`` with slab width 4 retires 8
  right-hand sides.

``--chips 4`` runs the reduction that spans chips and nothing else: the
same problem on ``get_backend("shard_map", n_shards=4)`` with the
monolithic ``psum`` and with the staged ring ladder, against the
one-chip oracle ``get_backend("local", reduction="staged",
virtual_shards=4)``, plus the overlap report of the compiled 4-shard
program.

Every solution is checked on the host: the true relative residual
||b - A x|| / ||b|| is recomputed in numpy float64 with a numpy 5-point
apply (not the device operator) and must meet the tolerance, and the
solver must report ``converged``.  The served stream must shed nothing,
lose no worker and retry nothing.  The chip path runs in float32 with
``jax_enable_x64`` off: TPU v5e has no native float64 and no Pallas
kernel lowers it.

The script refuses to run anywhere but on a TPU.  Its last line is one
JSON object, ``{"ok": true, "device": {...}}``, printed only when every
check passed; any failure exits non-zero without it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.problems import build_operator  # noqa: E402
from repro.core.chebyshev import shifts_for_operator  # noqa: E402
from repro.parallel import get_backend  # noqa: E402
from repro.serve import SolverService  # noqa: E402
from repro.stability import GovernorConfig  # noqa: E402
from repro.utils.compile_cache import use_compile_cache  # noqa: E402

PROBLEM = "laplace2d"
SEED = 0
N_REQUESTS = 8
SLAB_WIDTH = 4
CHUNK_ITERS = 64
MESH_SHARDS = 4
# 4-shard runs against the oracle.  On CPU the residual histories are
# bitwise equal.  On a TPU they are not: the ICI all-reduce of psum sums
# the four partials in its own order, and the staged mesh and the oracle
# also part in the last bit before the first replacement (PERF.md §6-7).
# The histories part at the last bit within a few entries,
# and float32 CG amplifies that until the certified solves stop a few
# percent apart: at tol 2e-4 the psum, staged, oracle and plain one-chip
# solves took 4692-4799 updates (PERF.md §6).  So the first HIST_CHECK
# history entries must agree to HIST_RTOL, which a wrong combine (a lost
# or doubled partial) misses by orders of magnitude already at entry 0,
# and the update counts must agree within ITER_SLACK, or ITER_FRAC of the
# oracle's where that is more: about twice the spread rounding alone
# caused.
HIST_CHECK = 20
HIST_RTOL = 1e-3
ITER_SLACK = 2
ITER_FRAC = 0.05

# Float32 solve policy.  Without replacement, p(2)-CG on a v5e stalls at
# a recursive residual near 8e-2 on laplace2d; a residual replacement
# every REPLACE_EVERY updates keeps it converging (every 1000 took fewer
# updates than every 500 at each tol tried, PERF.md §6).  The governor's
# verification arm certifies convergence against the true residual: a
# column converges only on a replacement whose freshly computed b - A x
# meets tol.
REPLACE_EVERY = 1000
GOVERNOR = GovernorConfig(verify_only=True)


class SmokeFailure(AssertionError):
    """A check of the smoke failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(*parts) -> None:
    print(*parts, flush=True)


# ------------------------------------------------------------- host side --

def lap5_np(x: np.ndarray, nx: int, ny: int) -> np.ndarray:
    """The 5-point Dirichlet Laplacian in numpy float64 — the host's own
    operator, independent of the device code under test."""
    g = x.reshape(nx, ny)
    p = np.pad(g, 1)
    return (4.0 * g - p[:-2, 1:-1] - p[2:, 1:-1]
            - p[1:-1, :-2] - p[1:-1, 2:]).reshape(-1)


def true_rel_residual(cfg, b: np.ndarray, x) -> float:
    """||b - A x|| / ||b|| in float64 on the host."""
    b = np.asarray(b, np.float64)
    x = np.asarray(x, np.float64)
    r = b - lap5_np(x, cfg.nx, cfg.ny)
    return float(np.linalg.norm(r) / np.linalg.norm(b))


def rhs(n: int, count: int, seed: int = SEED) -> np.ndarray:
    """``count`` float32 right-hand sides from a fixed seed, (count, n)."""
    rng = np.random.default_rng(seed)
    return rng.standard_normal((count, n)).astype(np.float32)


def solver_kwargs(cfg, op) -> dict:
    return dict(l=cfg.l, tol=cfg.tol, maxit=cfg.maxit,
                sigmas=shifts_for_operator(op, cfg.l, dtype=jnp.float32),
                replace_every=REPLACE_EVERY, governor=GOVERNOR,
                max_restarts=10 + cfg.maxit // REPLACE_EVERY)


def compare_histories(a: np.ndarray, c: np.ndarray) -> dict:
    """Where two residual histories first differ, and their largest
    relative difference over the first HIST_CHECK entries."""
    a = np.asarray(a)
    c = np.asarray(c)
    bits = f"u{a.itemsize}"
    differ = np.nonzero(a.view(bits) != c.view(bits))[0]
    head_a = a[:HIST_CHECK].astype(np.float64)
    head_c = c[:HIST_CHECK].astype(np.float64)
    early = float(np.max(np.abs(head_a - head_c) / np.abs(head_c)))
    return dict(bitwise=not len(differ),
                first_diff=int(differ[0]) if len(differ) else None,
                early_rel=early)


def counter_total(snapshot: dict, name: str) -> float:
    series = snapshot["metrics"].get(name, {}).get("series", {})
    return float(sum(series.values()))


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------- phases --

def solve_phase(cfg) -> dict:
    """One p(l)-CG solve of ``cfg`` on the local backend, checked on the
    host."""
    op = build_operator(cfg)
    b = jnp.asarray(rhs(op.n, 1)[0])
    solver = get_backend("local").make_solver(
        op, "plcg", **solver_kwargs(cfg, op))
    t0 = time.perf_counter()
    compiled = solver.lower(b).compile()
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = jax.block_until_ready(compiled(b))
    solve_s = time.perf_counter() - t0
    rel = true_rel_residual(cfg, b, res.x)
    out = dict(iters=int(res.iters), restarts=int(res.restarts),
               converged=bool(res.converged), true_rel=rel,
               compile_s=compile_s, solve_s=solve_s)
    log(f"[solve] {cfg.name} n={op.n} l={cfg.l} tol={cfg.tol:g}: "
        f"iters={out['iters']} restarts={out['restarts']} "
        f"converged={out['converged']} true_rel={rel:.3e} "
        f"compile_s={compile_s:.2f} "
        f"solve_s={solve_s:.3f} (smoke timing, not a benchmark)")
    check(out["converged"], f"solve did not converge: {out}")
    check(rel <= cfg.tol, f"true residual {rel:.3e} > tol {cfg.tol:g}")
    return out


def serve_phase(cfg) -> dict:
    """``N_REQUESTS`` right-hand sides through ``SolverService``; every
    retired request is checked on the host."""
    op = build_operator(cfg)
    svc = SolverService(get_backend("local"), s=SLAB_WIDTH, method="plcg",
                        l=cfg.l, maxit=cfg.maxit, chunk_iters=CHUNK_ITERS,
                        replace_every=REPLACE_EVERY, governor=GOVERNOR)
    svc.register_operator(cfg.name, op)
    B = rhs(op.n, N_REQUESTS, seed=SEED + 1)
    ids = [svc.submit(cfg.name, b, tol=cfg.tol) for b in B]
    t0 = time.perf_counter()
    results = svc.drain()
    drain_s = time.perf_counter() - t0
    snap = svc.metrics_snapshot()
    counts = {name: counter_total(snap, f"serve_{name}_total")
              for name in ("requests_retired", "requests_shed",
                           "requests_retried", "requests_resubmitted",
                           "worker_deaths")}
    log(f"[serve] {cfg.name} s={SLAB_WIDTH} requests={N_REQUESTS} "
        f"drain_s={drain_s:.2f} (smoke timing, includes compile) {counts}")
    check(sorted(results) == sorted(ids),
          f"retired {sorted(results)} != submitted {sorted(ids)}")
    check(counts["requests_retired"] == N_REQUESTS, f"retired: {counts}")
    for name in ("requests_shed", "requests_retried",
                 "requests_resubmitted", "worker_deaths"):
        check(counts[name] == 0, f"serve {name} = {counts[name]:g}")
    worst = 0.0
    for rid, b in zip(ids, B):
        r = results[rid]
        check(not r.shed and r.x is not None, f"request {rid} shed")
        rel = true_rel_residual(cfg, b, r.x)
        worst = max(worst, rel)
        log(f"[serve]   request {rid}: iters={r.iters} "
            f"converged={r.converged} true_rel={rel:.3e}")
        check(r.converged, f"request {rid} did not converge")
        check(rel <= cfg.tol,
              f"request {rid}: true residual {rel:.3e} > tol {cfg.tol:g}")
    return dict(counts, worst_true_rel=worst, drain_s=drain_s)


def mesh_phase(cfg, n_shards: int = MESH_SHARDS) -> dict:
    """``cfg`` on an ``n_shards`` shard_map mesh, psum and staged,
    against the one-device staged oracle with as many virtual shards."""
    from repro.utils.trace import plcg_overlap_report

    op = build_operator(cfg)
    b = jnp.asarray(rhs(op.n, 1)[0])
    kw = solver_kwargs(cfg, op)
    runs = {
        "psum": get_backend("shard_map", n_shards=n_shards),
        "staged": get_backend("shard_map", n_shards=n_shards,
                              reduction="staged"),
        "oracle": get_backend("local", reduction="staged",
                              virtual_shards=n_shards),
    }
    mesh_devs = [str(d) for d in runs["psum"].mesh.devices.flat]
    log(f"[mesh] make_solver_mesh({n_shards}): {mesh_devs}")
    check(len(set(mesh_devs)) == n_shards,
          f"mesh holds {len(set(mesh_devs))} distinct devices, "
          f"want {n_shards}")
    out, hists = {}, {}
    for name, be in runs.items():
        t0 = time.perf_counter()
        res = jax.block_until_ready(be.solve(op, b, method="plcg", **kw))
        solve_s = time.perf_counter() - t0
        rel = true_rel_residual(cfg, b, res.x)
        placed = sorted(str(d) for d in res.x.sharding.device_set) \
            if name != "oracle" else None
        out[name] = dict(iters=int(res.iters), converged=bool(res.converged),
                         true_rel=rel)
        hists[name] = np.asarray(res.res_history)
        log(f"[mesh] {name:6s} ({be.describe()}): iters={out[name]['iters']}"
            f" converged={out[name]['converged']} true_rel={rel:.3e}"
            f" solve_s={solve_s:.2f} (smoke timing, includes compile)"
            + (f" x on {placed}" if placed else ""))
        if placed is not None:
            check(len(placed) == n_shards,
                  f"{name}: solution lives on {placed}, want "
                  f"{n_shards} devices")
    pairs = {f"{a}=={c}": compare_histories(hists[a], hists[c])
             for a, c in (("psum", "staged"), ("staged", "oracle"),
                          ("psum", "oracle"))}
    bitwise = {k: v["bitwise"] for k, v in pairs.items()}
    log(f"[mesh] residual histories bitwise equal: {bitwise}")
    for k, v in pairs.items():
        log(f"[mesh]   {k}: first differing entry {v['first_diff']}, "
            f"max relative difference over the first {HIST_CHECK} "
            f"entries {v['early_rel']:.3e}")
    rep = plcg_overlap_report(
        runs["psum"], op, jax.ShapeDtypeStruct((op.n,), jnp.float32),
        l=cfg.l, sigmas=kw["sigmas"])
    log(f"[mesh] overlap report (psum, compiled {n_shards}-shard "
        f"program):\n{rep}")
    oracle_iters = out["oracle"]["iters"]
    slack = max(ITER_SLACK, int(np.ceil(ITER_FRAC * oracle_iters)))
    for name in ("psum", "staged"):
        check(out[name]["converged"], f"{name} did not converge")
        check(out[name]["true_rel"] <= cfg.tol,
              f"{name}: true residual {out[name]['true_rel']:.3e} "
              f"> tol {cfg.tol:g}")
        early = pairs[f"{name}==oracle"]["early_rel"]
        check(early <= HIST_RTOL,
              f"{name}: first {HIST_CHECK} history entries differ from "
              f"the oracle's by {early:.3e} > {HIST_RTOL:g}")
        check(abs(out[name]["iters"] - oracle_iters) <= slack,
              f"{name}: {out[name]['iters']} iterations vs oracle "
              f"{oracle_iters} (slack {slack})")
    return dict(out, bitwise=bitwise, max_in_flight=rep.max_in_flight)


# ------------------------------------------------------------------ main --

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, MESH_SHARDS), default=1,
                    help="1: single solve + served stream; 4: the "
                         "4-shard mesh against its one-chip oracle")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r}",
              file=sys.stderr)
        return 2
    if jax.config.jax_enable_x64:
        print("chip_smoke: jax_enable_x64 is on; the chip path runs "
              "float32", file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(jax.devices())}", file=sys.stderr)
        return 2
    cache = use_compile_cache(ROOT)
    log(f"[setup] device={dev.device_kind} count={len(jax.devices())} "
        f"jax={jax.__version__} compile_cache={cache}")

    cfg = get_config(PROBLEM)
    if args.chips == 1:
        solve_phase(cfg)
        serve_phase(cfg)
    else:
        mesh_phase(cfg, n_shards=args.chips)
    log(f"[setup] peak_bytes_in_use={peak_bytes()}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
