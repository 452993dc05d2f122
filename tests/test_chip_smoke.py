"""CPU rehearsal of ``chip_smoke.py`` at ``laplace2d-smoke`` size.

The script itself refuses any platform but TPU; its phase functions are
the same code the chip runs, so they are driven here directly: the
single solve, the served stream with its shed/death/retry checks, and
the 4-shard mesh comparison in a subprocess with four virtual CPU
devices (x64 off, as on the chip).
"""

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.configs import get_config

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load()


@pytest.fixture(scope="module")
def cfg():
    return get_config("laplace2d", smoke=True)


def test_host_laplacian_matches_operator(smoke, cfg):
    from repro.configs.problems import build_operator

    op = build_operator(cfg)
    x = np.random.default_rng(3).standard_normal(op.n)
    np.testing.assert_allclose(smoke.lap5_np(x, cfg.nx, cfg.ny),
                               np.asarray(op.apply(x)), rtol=0, atol=1e-12)


def test_solve_phase(smoke, cfg):
    out = smoke.solve_phase(cfg)
    assert out["converged"] and out["true_rel"] <= cfg.tol, out


def test_serve_phase(smoke, cfg):
    out = smoke.serve_phase(cfg)
    assert out["requests_retired"] == smoke.N_REQUESTS
    assert out["requests_shed"] == out["worker_deaths"] == 0
    assert out["requests_retried"] == out["requests_resubmitted"] == 0
    assert out["worst_true_rel"] <= cfg.tol


def test_check_raises_smoke_failure(smoke):
    with pytest.raises(smoke.SmokeFailure, match="boom"):
        smoke.check(False, "boom")


def test_compare_histories(smoke):
    a = np.linspace(1.0, 0.1, 40).astype(np.float32)
    same = smoke.compare_histories(a, a.copy())
    assert same == dict(bitwise=True, first_diff=None, early_rel=0.0)
    b = a.copy()
    b[5] = np.nextafter(b[5], np.float32(2))
    one_ulp = smoke.compare_histories(b, a)
    assert not one_ulp["bitwise"] and one_ulp["first_diff"] == 5
    assert 0 < one_ulp["early_rel"] < 1e-6
    b[0] *= 0.75                      # a lost shard's partial
    assert smoke.compare_histories(b, a)["early_rel"] > smoke.HIST_RTOL


def test_mesh_phase_on_four_virtual_devices():
    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import importlib.util
spec = importlib.util.spec_from_file_location("chip_smoke", {str(SCRIPT)!r})
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
from repro.configs import get_config
out = smoke.mesh_phase(get_config("laplace2d", smoke=True))
assert all(out["bitwise"].values()), out["bitwise"]
assert out["max_in_flight"] >= 2, out
print("MESH-OK")
"""
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, cwd=ROOT, timeout=600)
    assert proc.returncode == 0, (proc.stdout[-3000:], proc.stderr[-3000:])
    assert "MESH-OK" in proc.stdout
    assert "x on" in proc.stdout        # the placement line was printed


def test_main_refuses_non_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs a TPU" in out.err
