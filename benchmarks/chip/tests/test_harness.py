"""The harness finds cells, configurations, mixes and metrics by name, and
refuses to run where it cannot measure."""

import json
import os
import re
import subprocess
import sys

import pytest

from conftest import BENCH_DIR, ROOT
from helpers import make_checkout, run, small_config


def _fake_stop(self, chips, hlo_text):
    """What ``Tracer.stop`` returns for a window whose block solves took
    2 us of device time: the trace read nowhere, the phases given."""
    from phase_reduce import PhaseSummary
    from trace_reduce import TraceSummary

    assert "HloModule" in hlo_text
    return (TraceSummary(window_s=1.0, busy_s=0.5, allreduce_s=0.0,
                         op_s={"fusion.1": 0.5}, idle_gaps=[], chips=chips),
            PhaseSummary(window_s=1.0, busy_s=0.5,
                         phase_s={"plcg_vector": 0.5},
                         sub_s={"plcg_vector/prec": 2e-6,
                                "plcg_vector/other": 0.5 - 2e-6},
                         halo_s=0.0, loop_idle_s=0.25, host_idle_s=0.25,
                         loop_gaps=[], unscoped_ops=[], chips=chips))


def test_new_files_are_found_by_name(tmp_path, capsys, monkeypatch):
    """A configuration that names a preconditioner, a mix and metrics added
    as new files, with entries in BENCHMARK.json, run without any existing
    file being edited; a per-layer metric reads the configuration as run
    and the traced window's phases."""
    import trace_reduce

    before = {p: p.read_bytes() for p in BENCH_DIR.rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}
    root = make_checkout(tmp_path, {})
    bench = root / "benchmarks" / "chip"
    cfg = small_config("laplace2d", name="tiny2d")
    cfg["problem"] = {"kind": "stencil2d", "nx": 16, "ny": 16,
                      "prec": "blockjacobi", "block_size": 16}
    (bench / "configs" / "tiny2d.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "single_solve.json").read_text())
    mix["pool"] = 3
    (bench / "traffic" / "three_rhs.json").write_text(json.dumps(mix))
    (bench / "metrics" / "solves_in_window.py").write_text(
        "def read(run):\n    return len(run.solves)\n")
    # A per-layer metric of the block solve's device time per block, from
    # the configuration's block size and a vector sub-phase.
    (bench / "metrics" / "prec_us_per_block.py").write_text(
        "def read(run):\n"
        "    p = run.phases\n"
        "    if p is None or 'plcg_vector/prec' not in p.sub_s:\n"
        "        return None\n"
        "    blocks = run.n // run.config['problem']['block_size']\n"
        "    return 1e6 * p.sub_s['plcg_vector/prec'] / blocks\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny2d", "source": "test",
                            "file": "benchmarks/chip/configs/tiny2d.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny2d.three", "config": "tiny2d",
                              "traffic": "three_rhs", "chips": 1,
                              "why": "test"})
    spec["end_to_end"].append({"name": "solves_in_window", "unit": "solves",
                               "better": "higher", "bound": 0.25,
                               "source": "host_clock",
                               "workloads": ["tiny2d.three"]})
    spec["per_layer"].append({"name": "prec_us_per_block", "unit": "us",
                              "better": "lower", "source": "device_trace",
                              "layer": "preconditioner", "moves": "solve_s",
                              "workloads": ["tiny2d.three"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    rc, result = run(root, "tiny2d.three", capsys)
    assert rc == 0
    assert result["correct"] is True
    assert set(result["metrics"]) == {"solve_s", "setup_s",
                                      "solves_in_window"}
    assert result["metrics"]["solves_in_window"]["value"] == \
        result["attempted"] >= 3
    assert list(result)[-1] == "checks"

    # The traced run, with the trace's reductions standing in for a chip's.
    monkeypatch.setattr(trace_reduce.Tracer, "start", lambda self: None)
    monkeypatch.setattr(trace_reduce.Tracer, "stop", _fake_stop)
    rc, result = run(root, "tiny2d.three", capsys, trace=True)
    assert rc == 0 and result["correct"] is True
    # 2 us of block solve over 256 / 16 blocks.
    assert result["metrics"]["prec_us_per_block"]["value"] == \
        pytest.approx(2.0 / 16)
    assert result["breakdown"]["phases"]["plcg_vector/prec"] == 2e-6
    after = {p: p.read_bytes() for p in BENCH_DIR.rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert after == before


def test_metrics_follow_their_workloads(tmp_path, capsys):
    root = make_checkout(tmp_path, {
        "laplace2d": small_config("laplace2d"),
        "laplace2d-4chip": small_config("laplace2d-4chip")})
    rc, one = run(root, "laplace2d.solve", capsys)
    assert rc == 0 and one["correct"] and one["device"]["count"] == 1
    assert set(one["metrics"]) == {"solve_s", "setup_s"}
    rc, four = run(root, "laplace2d-4chip.solve", capsys)
    assert rc == 0 and four["correct"] and four["device"]["count"] == 4


def test_unknown_workload_exits_nonzero(tmp_path, capsys):
    root = make_checkout(tmp_path, {})
    rc, result = run(root, "nonesuch.solve", capsys)
    assert rc != 0 and result is None


def test_checkout_without_program_exits_nonzero(tmp_path, capsys):
    root = make_checkout(tmp_path, {"laplace2d": small_config("laplace2d")})
    os.remove(root / "src")
    rc, result = run(root, "laplace2d.solve", capsys)
    assert rc != 0 and result is None


def test_more_chips_than_present_exits_nonzero(tmp_path, capsys):
    root = make_checkout(tmp_path, {"laplace2d": small_config("laplace2d")})
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"][0]["chips"] = 64
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    rc, result = run(root, spec["workloads"][0]["name"], capsys)
    assert rc != 0 and result is None


def test_entry_point_refuses_cpu():
    """The command as the benchmark states it, on a machine with no TPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = json.loads((ROOT / "BENCHMARK.json").read_text())["command"]
    p = subprocess.run(
        [sys.executable] + cmd[1:] + ["--workload", "laplace2d.solve",
                                      "--seed", str(2**31 + 11),
                                      "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert '"correct"' not in p.stdout


def test_host_check_samples_the_window(tmp_path, capsys, monkeypatch):
    """A window with more solves than ``CHECKED`` checks that many of them
    on the host, drawn from the seed; the others count by the solver's own
    verdict."""
    import harness
    import run as entry

    monkeypatch.setattr(harness, "CHECKED", 2)
    root = make_checkout(tmp_path, {"laplace2d": small_config("laplace2d")})
    rc = entry.main(["--workload", "laplace2d.solve", "--seed",
                     str(2**31 + 5), "--seconds", "0.5", "--trace", "0"],
                    root=root, bench_dir=root / "benchmarks" / "chip",
                    require_chip=False)
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    window = [ln for ln in lines if ln.startswith("[window]")]
    assert rc == 0 and result["correct"] is True
    assert len(window) == result["attempted"] > 2
    assert sum("true_rel=unchecked" not in ln for ln in window) == 2


# The source locations a compiled program's text carries: the tables of
# file names (absolute paths of the checkout), functions, lines and stack
# frames, and each instruction's reference into them.
_LOCATION_TABLES = re.compile(
    r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n(?:.+\n)*\n?",
    re.M)
_FRAME_ID = re.compile(r" stack_frame_id=\d+")


def without_locations(text: str) -> str:
    """A compiled program's text less where in the Python source each
    instruction was traced from."""
    return _FRAME_ID.sub("", _LOCATION_TABLES.sub("", text))


@pytest.mark.parametrize("config", ["laplace2d", "laplace2d-4chip"])
def test_unpreconditioned_program_is_unchanged(config):
    """With ``prec`` ``none`` the harness compiles the very program it
    compiled before it built preconditioners: the operator's own
    Chebyshev shifts and no ``prec`` argument.  The texts differ only in
    the source lines they were traced from."""
    import jax
    import jax.numpy as jnp

    import harness
    from repro.configs.laplace2d import CGProblem
    from repro.configs.problems import build_operator
    from repro.core.chebyshev import shifts_for_operator
    from repro.parallel import get_backend
    from repro.stability import GovernorConfig

    cfg = small_config(config)
    op, backend, kw = harness.build_program(cfg, ROOT)
    assert kw["prec"] is None
    sol = cfg["solver"]
    be_cfg = dict(cfg["backend"])
    plain_op = build_operator(CGProblem(name=cfg["name"], **cfg["problem"]))
    plain_be = get_backend(be_cfg.pop("name"), **be_cfg)
    plain_kw = dict(l=sol["l"], tol=sol["tol"], maxit=sol["maxit"],
                    sigmas=shifts_for_operator(plain_op, sol["l"],
                                               dtype=jnp.float32),
                    replace_every=sol["replace_every"],
                    max_restarts=sol["max_restarts"],
                    governor=GovernorConfig(**sol["governor"]))

    def text(be, o, solver_kw):
        b = jax.ShapeDtypeStruct(
            (o.n,), jnp.float32,
            sharding=harness.rhs_sharding(be, jax.devices()[0]))
        solver = be.make_solver(o, sol["method"], **solver_kw)
        return without_locations(
            jax.jit(solver).lower(b).compile().as_text())

    ours = text(backend, op, kw)
    assert "stack_frame_id" not in ours and "plcg_vector" in ours
    assert ours == text(plain_be, plain_op, plain_kw)


def test_four_chips_trace_the_last_quarter(tmp_path, capsys, monkeypatch):
    """A traced run on four chips profiles the last quarter of the window,
    and the trace's readings are per traced update and solve."""
    import run as entry
    import trace_reduce

    monkeypatch.setattr(trace_reduce.Tracer, "start", lambda self: None)
    monkeypatch.setattr(trace_reduce.Tracer, "stop", _fake_stop)
    root = make_checkout(tmp_path, {
        "laplace2d-4chip": small_config("laplace2d-4chip")})
    rc = entry.main(["--workload", "laplace2d-4chip.solve", "--seed",
                     str(2**31 + 9), "--seconds", "2", "--trace", "1"],
                    root=root, bench_dir=root / "benchmarks" / "chip",
                    require_chip=False)
    lines = capsys.readouterr().out.strip().splitlines()
    result = json.loads(lines[-1])
    traced = int(re.search(r"traced_solves=(\d+)",
                           next(ln for ln in lines
                                if ln.startswith("[trace]"))).group(1))
    assert rc == 0 and result["correct"] is True
    assert 0 < traced < result["attempted"] / 2
    assert result["metrics"]["host_idle_ms_per_solve"]["value"] == \
        pytest.approx(1e3 * 0.25 / traced)
