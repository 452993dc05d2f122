"""The harness finds cells, configurations, mixes and metrics by name, and
refuses to run where it cannot measure."""

import json
import os
import subprocess
import sys

from conftest import BENCH_DIR, ROOT
from helpers import make_checkout, run, small_config


def test_new_files_are_found_by_name(tmp_path, capsys):
    """A configuration, a mix and a metric added as new files, with entries
    in BENCHMARK.json, run without any existing file being edited."""
    before = {p: p.read_bytes() for p in BENCH_DIR.rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}
    root = make_checkout(tmp_path, {})
    bench = root / "benchmarks" / "chip"
    cfg = small_config("laplace2d", name="tiny2d")
    cfg["problem"] = {"kind": "stencil2d", "nx": 16, "ny": 16}
    (bench / "configs" / "tiny2d.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "single_solve.json").read_text())
    mix["pool"] = 3
    (bench / "traffic" / "three_rhs.json").write_text(json.dumps(mix))
    (bench / "metrics" / "solves_in_window.py").write_text(
        "def read(run):\n    return len(run.solves)\n")
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
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    rc, result = run(root, "tiny2d.three", capsys)
    assert rc == 0
    assert result["correct"] is True
    assert set(result["metrics"]) == {"solve_s", "setup_s",
                                      "solves_in_window"}
    assert result["metrics"]["solves_in_window"]["value"] == \
        result["attempted"] >= 3
    assert list(result)[-1] == "checks"
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
