"""The p(l)-CG slab's tile-aligned rows (DESIGN.md §13) at an n that is
no multiple of 1024: a 30 x 30 stencil (n = 900) stores each vector as
(8, 128) with a 124-entry zero tail, and on three shards (n = 300 each)
as (8, 128) with a 724-entry tail.  Every depth l and both recurrences:
the solve converges to the dense solution, the tail is still zero after
the loop, a checkpoint resumes to the same x, batched columns agree with
sequential solves, and a shard_map run on virtual CPU devices agrees
with the local run (multi-device runs go to a subprocess: conftest pins
one device)."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointConfig, LAST_RESTORE
from repro.core import METHODS, pipelined_cg
from repro.core.chebyshev import shifts_for_operator
from repro.core.types import SolverOps
from repro.kernels.fused_iter import SlabLayout
from repro.linalg.operators import Stencil2D5
from repro.parallel import get_backend

NX = NY = 30
SHARDS = 3
CASES = [(l, rec) for l in (1, 2, 3) for rec in ("ghysels", "stable")]


def _kw(op, l, recurrence):
    return dict(l=l, sigmas=shifts_for_operator(op, l), tol=1e-10,
                maxit=600, recurrence=recurrence)


def _rhs(n, cols=None):
    rng = np.random.default_rng(30)
    return jnp.asarray(rng.standard_normal(n if cols is None else (n, cols)))


_SHARD_MAP = """
import json, os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={shards}"
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from repro.core.chebyshev import shifts_for_operator
from repro.linalg.operators import Stencil2D5
from repro.parallel import get_backend
op = Stencil2D5({nx}, {ny})
b = np.random.default_rng(30).standard_normal(op.n)
out = {{}}
for l, rec in {cases!r}:
    kw = dict(l=l, sigmas=shifts_for_operator(op, l), tol=1e-10, maxit=600,
              recurrence=rec)
    res = get_backend("shard_map", n_shards={shards}).solve(op, b, **kw)
    out[f"{{l}}-{{rec}}"] = {{"iters": int(res.iters),
                             "converged": bool(res.converged),
                             "x": np.asarray(res.x).tolist()}}
print("SHARD-MAP", json.dumps(out))
"""


@pytest.fixture(scope="module")
def x_dense():
    op = Stencil2D5(NX, NY)
    return np.linalg.solve(op.to_dense(), np.asarray(_rhs(op.n)))


@pytest.fixture(scope="module")
def shard_map_runs():
    """All cases' shard_map solves, in one subprocess."""
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    code = _SHARD_MAP.format(shards=SHARDS, nx=NX, ny=NY, cases=CASES)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=os.getcwd(), timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [s for s in out.stdout.splitlines() if s.startswith("SHARD-MAP")]
    return json.loads(line[-1].split(" ", 1)[1])


def test_layout_rows_are_whole_tiles():
    for n, rows in [(900, 8), (300, 8), (1024, 8), (1025, 16),
                    (4_000_000, 31_256), (1_000_000, 7_816)]:
        layout = SlabLayout.for_solver(2, n)
        assert layout.row_shape == (rows, 128)
        v = jnp.arange(n, dtype=jnp.float32) + 1
        row = layout.to_row(v)
        assert row.shape == (rows, 128)
        assert not np.asarray(row).reshape(-1)[n:].any()
        np.testing.assert_array_equal(np.asarray(layout.from_row(row)), v)
    flat = SlabLayout.for_solver(2, 900, fused=True)
    assert flat.row_shape == (900,)


@pytest.mark.parametrize("l,recurrence", CASES,
                         ids=[f"l{l}-{rec}" for l, rec in CASES])
def test_padded_slab_solve(tmp_path, x_dense, shard_map_runs, l,
                           recurrence):
    op = Stencil2D5(NX, NY)
    b = _rhs(op.n)
    kw = _kw(op, l, recurrence)
    ops = SolverOps.local(op)

    # The solve converges to the dense solution, and the slab's tail past
    # n is still zero after the loop.
    prog = pipelined_cg.build(ops, b, **kw)
    final = jax.jit(lambda bb: jax.lax.while_loop(
        prog.cond, prog.body, prog.init(jnp.zeros_like(bb))))(b)
    res = prog.finish(final)
    assert bool(res.converged)
    np.testing.assert_allclose(np.asarray(res.x), x_dense, atol=1e-8)
    S = np.asarray(final.cyc.S)
    layout = SlabLayout.for_solver(l, op.n, recurrence)
    assert S.shape == (layout.nv, 8, 128)
    assert not S.reshape(layout.nv, -1)[:, op.n:].any()

    # A checkpointed solve resumed from its last snapshot gives back the
    # uninterrupted x.
    be = get_backend("local")
    d = str(tmp_path)
    full = be.solve(op, b, checkpoint=CheckpointConfig(every=25, directory=d),
                    **kw)
    resumed = be.solve(op, b, checkpoint=CheckpointConfig(
        every=25, directory=d, resume=True), **kw)
    assert int(LAST_RESTORE[-1].meta["tot"]) > 0
    assert bool(resumed.converged)
    np.testing.assert_array_equal(np.asarray(resumed.x), np.asarray(full.x))

    # Batched columns agree with sequential solves as everywhere else
    # (tests/test_serve.py).
    B = _rhs(op.n, 3)
    res_b = be.solve_batched(op, B, **kw)
    sequential = jax.jit(lambda bb: METHODS["plcg"](ops, bb, kw))
    for j in range(B.shape[1]):
        res_j = sequential(B[:, j])
        assert int(res_b.iters[j]) == int(res_j.iters)
        np.testing.assert_allclose(
            np.asarray(res_b.res_history[j]), np.asarray(res_j.res_history),
            rtol=1e-8, atol=1e-11)

    # Three shards of 300 entries: the shard_map run agrees with the local
    # run as in tests/test_distributed.py.
    sm = shard_map_runs[f"{l}-{recurrence}"]
    assert sm["converged"] and sm["iters"] == int(res.iters)
    np.testing.assert_allclose(np.asarray(sm["x"]), np.asarray(res.x),
                               atol=1e-9)
