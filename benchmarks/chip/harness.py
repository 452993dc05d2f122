"""The chip benchmark's harness: one cell of ``BENCHMARK.json``, one run.

Everything particular to a configuration, a traffic mix or a metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``configs/<config>.json``    the deployment as it is run: problem sizes,
                               solver policy, backend, precision, and the
                               name of its plain reference;
* ``references/<name>.py``     that plain reference (``apply_np``,
                               ``apply_jnp``, ``true_rel_residual``);
* ``traffic/<mix>.json``       the mix's parameters, read by ``traffic.py``;
* ``metrics/<metric>.py``      a reader ``read(run) -> float | None`` of one
                               metric from a :class:`RunRecord`.

A run builds the operator and the solver through the program's public
entry points, makes the right-hand-side pool on the device from the seed,
compiles the cell's one solver program ahead of time (from the persistent
compilation cache after the first run), then solves back to back for the
window.  A solve started inside the window is finished, so the window
holds whole solves.  Once it has closed, a sample of the window's
solutions drawn from the seed (all of them, where the window holds no more
than ``CHECKED``) is checked on the host against the plain reference in
float64.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
CACHE_DIR = ".jax_cache"
# Solutions checked on the host after the window, at most: each check is
# a float64 residual of the whole grid, and all of them have to take less
# time than the window.
CHECKED = 8


class BenchError(Exception):
    """A run that cannot produce a result (no chip, a malformed entry)."""


# ------------------------------------------------------------- discovery --

def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import the Python file ``path`` as a fresh module."""
    if not path.is_file():
        raise BenchError(f"missing benchmark file {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(spec: dict, workload: str) -> tuple[dict, dict]:
    """(cell, configuration entry) of ``workload`` in ``spec``."""
    cells = {c["name"]: c for c in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    return cell, configs[cell["config"]]


def cell_metrics(spec: dict, workload: str, trace: bool) -> list[dict]:
    """The metric entries a run of ``workload`` reports: the end-to-end ones
    with ``trace`` off, the per-layer ones with it on; an entry with a
    ``workloads`` list applies to those cells only."""
    entries = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in entries
            if "workloads" not in m or workload in m["workloads"]]


def config_path(root: Path, entry: dict) -> Path:
    return root / entry["file"]


def traffic_path(mix: str, bench_dir: Path = BENCH_DIR) -> Path:
    return bench_dir / "traffic" / f"{mix}.json"


def reference_path(name: str, bench_dir: Path = BENCH_DIR) -> Path:
    return bench_dir / "references" / f"{name}.py"


def metric_path(name: str, bench_dir: Path = BENCH_DIR) -> Path:
    return bench_dir / "metrics" / f"{name}.py"


# ----------------------------------------------------------------- record --

@dataclasses.dataclass
class Solve:
    iters: int
    restarts: int
    converged: bool
    seconds: float
    true_rel: float | None = None      # host check; None: not sampled

    def certified(self, tol: float) -> bool:
        return self.converged and (self.true_rel is None
                                   or self.true_rel <= tol)


@dataclasses.dataclass
class RunRecord:
    """What a metric reader sees of one run."""

    chips: int
    n: int
    l: int
    itemsize: int
    tol: float
    peaks: dict | None
    setup_s: float
    window_s: float
    solves: list[Solve]
    trace: object | None = None        # trace_reduce.TraceSummary

    @property
    def certified(self) -> int:
        return sum(s.certified(self.tol) for s in self.solves)

    @property
    def updates(self) -> int:
        return sum(s.iters for s in self.solves)


# ---------------------------------------------------------------- program --

def program_path(root: Path) -> Path:
    return root / "src"


def build_program(config: dict, root: Path):
    """(op, backend, solver kwargs) through the program's entry points."""
    src = program_path(root)
    if not (src / "repro").is_dir():
        raise BenchError(f"the program is not in this checkout ({src})")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    import jax.numpy as jnp

    from repro.configs.laplace2d import CGProblem
    from repro.configs.problems import build_operator
    from repro.core.chebyshev import shifts_for_operator
    from repro.parallel import get_backend
    from repro.stability import GovernorConfig

    prob = config["problem"]
    sol = config["solver"]
    if prob.get("prec", "none") != "none":
        raise BenchError(f"{config['name']}: the harness builds no "
                         f"preconditioner yet (prec={prob['prec']!r})")
    op = build_operator(CGProblem(name=config["name"], **prob))
    dtype = jnp.dtype(config["dtype"])
    be_cfg = dict(config["backend"])
    backend = get_backend(be_cfg.pop("name"), **be_cfg)
    kw = dict(l=sol["l"], tol=sol["tol"], maxit=sol["maxit"],
              sigmas=shifts_for_operator(op, sol["l"], dtype=dtype),
              replace_every=sol["replace_every"],
              max_restarts=sol["max_restarts"],
              governor=GovernorConfig(**sol["governor"]))
    return op, backend, kw


def rhs_sharding(backend, device):
    """Where a right-hand side lives: split over the backend's mesh axis
    when it has one, else on ``device``."""
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    mesh = getattr(backend, "mesh", None)
    if mesh is None:
        return SingleDeviceSharding(device)
    return NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))


def cache_entries(path: str) -> int:
    return sum(len(files) for _, _, files in os.walk(path)) \
        if os.path.isdir(path) else 0


# -------------------------------------------------------------------- run --

def log(*parts) -> None:
    print(*parts, flush=True)


def run_cell(*, workload: str, cell: dict, config: dict, mix: dict,
             metrics: list[dict], seed: int, seconds: float, trace: bool,
             root: Path, t_start: float, bench_dir: Path = BENCH_DIR,
             require_chip: bool = True) -> dict:
    """One run of ``cell``; returns the result object the last stdout line
    carries.  ``t_start`` is the host clock at process start, so set-up
    counts the imports too.  ``require_chip=False`` lets a test drive the
    same run on the CPU."""
    import jax

    setup = {}
    devices = jax.devices()
    dev = devices[0]
    if require_chip and dev.platform != "tpu":
        raise BenchError(f"needs a TPU, JAX found platform {dev.platform!r}")
    if jax.config.jax_enable_x64:
        raise BenchError("jax_enable_x64 is on; the chip path runs float32")
    chips = int(cell["chips"])
    if len(devices) < chips:
        raise BenchError(f"{workload} needs {chips} chips, JAX found "
                         f"{len(devices)}")
    from peaks import peaks_for

    peaks = peaks_for(dev.device_kind) if require_chip else None
    # The persistent compilation cache lives at a fixed path inside the
    # checkout, whatever the environment names, so two checkouts never
    # share one; every program is cached, however short its compile.
    cache_dir = str(Path(root).resolve() / CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    setup["init_s"] = time.perf_counter() - t_start

    t = time.perf_counter()
    op, backend, kw = build_program(config, root)
    reference = load_module(reference_path(config["reference"], bench_dir))
    setup["operator_s"] = time.perf_counter() - t

    import jax.numpy as jnp
    from traffic import rhs_pool

    t = time.perf_counter()
    prob = config["problem"]
    dtype = jnp.dtype(config["dtype"])
    pool = rhs_pool(mix, seed, op.n, dtype,
                    lambda u: reference.apply_jnp(u, prob),
                    rhs_sharding(backend, dev))
    setup["rhs_pool_s"] = time.perf_counter() - t

    t = time.perf_counter()
    before = cache_entries(cache_dir)
    solver = backend.make_solver(op, config["solver"]["method"], **kw)
    program = jax.jit(solver).lower(pool[0]).compile()
    setup["compile_s"] = time.perf_counter() - t
    written = cache_entries(cache_dir) - before
    setup_s = time.perf_counter() - t_start
    log("[setup] " + " ".join(f"{k}={v:.3f}" for k, v in setup.items())
        + f" setup_s={setup_s:.3f} compile_cache="
        + (f"miss ({written} entries written)" if written else "hit")
        + f" dir={cache_dir}")

    tracer = None
    if trace:
        from trace_reduce import Tracer
        tracer = Tracer(tempfile.mkdtemp(prefix="chipbench_trace_"))
        tracer.start()
    outs, solves = [], []
    # No collection inside the window: a full one takes tens of
    # milliseconds here, which would land inside a solve's timing.
    gc.collect()
    gc.disable()
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            k = 0
            while time.perf_counter() - t0 < seconds:
                ts = time.perf_counter()
                j = k % len(pool)
                with jax.profiler.TraceAnnotation("bench.solve"):
                    res = jax.block_until_ready(program(pool[j]))
                    iters, restarts, converged = jax.device_get(
                        (res.iters, res.restarts, res.converged))
                solves.append(Solve(int(iters), int(restarts),
                                    bool(converged),
                                    time.perf_counter() - ts))
                outs.append((j, res.x))
                k += 1
        window_s = time.perf_counter() - t0
    finally:
        gc.enable()
    summary = tracer.stop(chips) if tracer is not None else None
    if tracer is not None:
        shutil.rmtree(tracer.logdir, ignore_errors=True)
    used = devices[:chips]
    peak_mem = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in used]
    peak_mem = max((p for p in peak_mem if p is not None), default=None)

    # The host check runs once the window has closed, on a sample of the
    # solutions drawn from the seed.
    tol = float(config["solver"]["tol"])
    checked = sorted(np.random.default_rng(seed).choice(
        len(solves), min(CHECKED, len(solves)), replace=False).tolist())
    host_b = {}
    for i in checked:
        j, x = outs[i]
        if j not in host_b:
            host_b[j] = np.asarray(pool[j])
        solves[i].true_rel = reference.true_rel_residual(
            host_b[j], np.asarray(x), prob)
    del outs, pool, program, host_b
    for i, s in enumerate(solves):
        rel = "unchecked" if s.true_rel is None else f"{s.true_rel:.6e}"
        log(f"[window] solve {i}: iters={s.iters} restarts={s.restarts} "
            f"converged={s.converged} true_rel={rel} "
            f"seconds={s.seconds:.6f}")

    rec = RunRecord(chips=chips, n=op.n, l=int(kw["l"]),
                    itemsize=dtype.itemsize, tol=tol, peaks=peaks,
                    setup_s=setup_s, window_s=window_s, solves=solves,
                    trace=summary)
    values = {}
    for m in metrics:
        v = load_module(metric_path(m["name"], bench_dir)).read(rec)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # A residual that is not a number (a solve that blew up) is the worst.
    worst = max((s.true_rel if np.isfinite(s.true_rel) else float("inf")
                 for s in solves if s.true_rel is not None),
                default=float("inf"))
    uncertified = sum(not s.certified(tol) for s in solves)
    checks = {
        "worst_true_rel": {"value": worst, "limit": tol},
        "uncertified": {"value": uncertified, "limit": 0},
        "solves": {"value": len(solves), "limit": 1},
    }
    correct = bool(solves) and worst <= tol and uncertified == 0
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(used), "memory_peak_bytes": peak_mem}
    result = {"correct": correct, "attempted": len(solves),
              "failed": uncertified, "metrics": values, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    result["checks"] = checks
    return result


def print_checks(result: dict) -> None:
    """Each compared number beside its limit, last on standard error."""
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
