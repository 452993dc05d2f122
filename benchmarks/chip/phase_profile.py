"""Where a cell's device time goes, by p(l)-CG phase.

    python3 benchmarks/chip/phase_profile.py --workload laplace2d.solve \\
        --seed 7 --seconds 10 [--root CHECKOUT] [--hlo-out FILE]

Runs the cell once as ``run.py --trace 0`` does (its result line first on
standard output), then compiles the same solver program again (a hit of
the persistent compilation cache), solves back to back for another
``--seconds`` under the profiler, and joins the trace to the compiled
program's text with ``phase_reduce``.  The last stdout line is a JSON
object: the traced window's phase times and per-layer readings, the
existing per-layer metrics of the cell read from the same window, the
checks that tie them together, and what tracing costs (traced against
untraced ``solve_s``, and the seconds the join takes after the window).

``--root`` runs the program of another checkout (a parent commit, say)
under this benchmark; ``--hlo-out`` writes the compiled program's text.
Needs the chips the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))

import harness  # noqa: E402
import run  # noqa: E402


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--root", type=Path, default=run.ROOT)
    ap.add_argument("--hlo-out", type=Path)
    return ap.parse_args(argv)


def traced_window(workload: str, seed: int, seconds: float,
                  root: Path) -> tuple[dict, str]:
    """Solve back to back for ``seconds`` under the profiler: the window's
    phase reduction with the cell's per-layer metrics read from the same
    window, and the compiled program's text."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    import phase_reduce
    import trace_reduce
    from peaks import peaks_for
    from traffic import rhs_pool

    spec = harness.load_json(root / "BENCHMARK.json")
    cell, entry = harness.find_cell(spec, workload)
    config = harness.load_json(harness.config_path(root, entry))
    mix = harness.load_json(harness.traffic_path(cell["traffic"], BENCH_DIR))
    chips = int(cell["chips"])
    dev = jax.devices()[0]
    op, backend, kw = harness.build_program(config, root)
    reference = harness.load_module(
        harness.reference_path(config["reference"], BENCH_DIR))
    prob = config["problem"]
    dtype = jnp.dtype(config["dtype"])
    pool = rhs_pool(mix, seed, op.n, dtype,
                    lambda u: reference.apply_jnp(u, prob),
                    harness.rhs_sharding(backend, dev))
    solver = backend.make_solver(op, config["solver"]["method"], **kw)
    program = jax.jit(solver).lower(pool[0]).compile()
    jax.block_until_ready(program(pool[0]))

    tracer = trace_reduce.Tracer(tempfile.mkdtemp(prefix="chipbench_phase_"))
    tracer.start()
    solves = []
    gc.collect()
    gc.disable()
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            k = 0
            while time.perf_counter() - t0 < seconds:
                ts = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.solve"):
                    res = jax.block_until_ready(program(pool[k % len(pool)]))
                    iters, restarts, converged = jax.device_get(
                        (res.iters, res.restarts, res.converged))
                solves.append(harness.Solve(int(iters), int(restarts),
                                            bool(converged),
                                            time.perf_counter() - ts))
                k += 1
        window_s = time.perf_counter() - t0
    finally:
        gc.enable()
    t = time.perf_counter()
    jax.profiler.stop_trace()
    pd = ProfileData.from_file(trace_reduce.find_xplane(tracer.logdir))
    summary = trace_reduce.summarize(pd, chips)
    read_s = time.perf_counter() - t
    t = time.perf_counter()
    text = program.as_text()
    phases = phase_reduce.summarize(pd, chips, text)
    join_s = time.perf_counter() - t
    shutil.rmtree(tracer.logdir, ignore_errors=True)

    rec = harness.RunRecord(
        chips=chips, n=op.n, l=int(kw["l"]), itemsize=dtype.itemsize,
        tol=float(config["solver"]["tol"]),
        peaks=peaks_for(dev.device_kind),
        setup_s=0.0, window_s=window_s, solves=solves, trace=summary)
    existing = {}
    for m in harness.cell_metrics(spec, workload, True):
        v = harness.load_module(
            harness.metric_path(m["name"], BENCH_DIR)).read(rec)
        if v is not None:
            existing[m["name"]] = float(v)
    idle = summary.window_s - summary.busy_s
    return {
        "workload": workload,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": chips},
        "solves": len(solves), "updates": rec.updates,
        "converged": sum(s.converged for s in solves),
        "solve_s_traced": window_s / len(solves),
        "trace_read_s": read_s, "join_s": join_s,
        "readings": phase_reduce.readings(phases, rec.updates, len(solves)),
        "existing": existing,
        "window_s": summary.window_s, "busy_s": summary.busy_s,
        "checks": {
            "phase_sum_over_busy": sum(phases.phase_s.values())
            / summary.busy_s,
            "unscoped_over_busy": phases.phase_s.get("unscoped", 0.0)
            / summary.busy_s,
            "idle_split_minus_idle_s": phases.loop_idle_s
            + phases.host_idle_s - idle,
        },
        "halo_s": phases.halo_s, "loop_idle_s": phases.loop_idle_s,
        "host_idle_s": phases.host_idle_s,
        "unscoped_ops": phases.unscoped_ops,
        **phases.breakdown(), **summary.breakdown(),
    }, text


def main(argv=None) -> int:
    args = parse_args(argv)
    root = args.root.resolve()
    rc = run.main(["--workload", args.workload, "--seed", str(args.seed),
                   "--seconds", str(args.seconds), "--trace", "0"],
                  root=root, bench_dir=BENCH_DIR)
    if rc:
        return rc
    try:
        out, text = traced_window(args.workload, args.seed + 1,
                                  args.seconds, root)
    except (harness.BenchError, FileNotFoundError, KeyError,
            ValueError) as e:
        print(f"phase_profile: {e}", file=sys.stderr, flush=True)
        return 2
    if args.hlo_out:
        args.hlo_out.parent.mkdir(parents=True, exist_ok=True)
        args.hlo_out.write_text(text)
    out["total_s"] = time.perf_counter() - T_START
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
