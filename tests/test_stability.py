"""Stability-governed deep pipelines (DESIGN.md §18): the stable p(l)-CG
recurrence, the attainable-accuracy governor, and the chaos harness that
PROVES governed recovery where ungoverned deep-l p(l)-CG stagnates.

The contract under test:

* ``recurrence="stable"`` converges wherever ghysels does, behind the
  same fused/unfused calling convention;
* the governor (``GovernorConfig``) repairs injected reduction-payload
  corruption through truth-certified residual replacements — the
  recovery demonstration: governed stable reaches tol under a seeded
  fault where ungoverned ghysels stagnates ~2000x above it;
* governor-off paths are BITWISE identical to the pre-§18 solver
  (single, batched s=8, staged shard_map);
* every governed/instrumented compile still issues EXACTLY ONE
  pipelined reduction start per iteration (the paper's invariant);
* catastrophic corruption demotes down the host ladder
  (``governed_solve``) and raises a typed :class:`StagnationError`
  instead of returning silent non-convergence.

The shard_map half follows the tests/test_distributed.py subprocess
idiom (8 fake host devices configured before jax imports).
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_enable_x64", True)

from repro.chaos import ChaosConfig, chaos_ops                 # noqa: E402
from repro.core import batched, pipelined_cg                   # noqa: E402
from repro.core.chebyshev import shifts_for_operator           # noqa: E402
from repro.core.types import SolverOps                         # noqa: E402
from repro.linalg import Stencil2D5                            # noqa: E402
from repro.linalg.preconditioners import JacobiPrec            # noqa: E402
from repro.parallel import get_backend                         # noqa: E402
from repro.stability import (                                  # noqa: E402
    GovernorConfig,
    StagnationError,
    diagnose,
    governed_solve,
)
from repro.stability import model as gov_model                 # noqa: E402

ENV = dict(os.environ, PYTHONPATH="src")
ENV.pop("XLA_FLAGS", None)


def _run(code: str) -> str:
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=ENV, cwd=os.getcwd(), timeout=900)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-3000:])
    return out.stdout


HEADER = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp
import numpy as np
from repro.parallel import get_backend
from repro.linalg import Stencil2D5
from repro.core.chebyshev import shifts_for_operator
from repro.stability import GovernorConfig
op = Stencil2D5(32, 24)
b = jnp.asarray(np.random.default_rng(3).standard_normal(op.n))
sig = shifts_for_operator(op, 2)
"""


def _problem():
    op = Stencil2D5(48, 24)
    prec = JacobiPrec.from_operator(op)
    ops = SolverOps.local(op, prec)
    b = jnp.asarray(np.random.default_rng(0).standard_normal(op.n))
    return op, prec, ops, b


def _true_rel(op, b, x):
    r = np.asarray(b) - np.asarray(op.apply(jnp.asarray(np.asarray(x))))
    return float(np.linalg.norm(r) / np.linalg.norm(np.asarray(b)))


# ------------------------------------------------------- stable recurrence --

def test_stable_recurrence_converges_clean():
    """The coupled-recurrence variant converges on a clean problem to the
    same tolerance as ghysels, unfused and fused, and the two variants
    agree on the solution (not bitwise — different recurrences — but to
    solver accuracy)."""
    op, prec, ops, b = _problem()
    kw = dict(l=4, tol=1e-6, maxit=400, max_restarts=60)
    rg = pipelined_cg.solve(ops, b, **kw)
    assert bool(rg.converged)
    for fused in (False, True):
        rs = pipelined_cg.solve(ops, b, recurrence="stable",
                                fused_iteration=fused, **kw)
        assert bool(rs.converged), fused
        assert _true_rel(op, b, rs.x) < 1e-5, fused
        assert abs(int(rs.iters) - int(rg.iters)) <= 40, \
            (int(rs.iters), int(rg.iters))


def test_stable_recurrence_fused_unfused_bitwise():
    """Both recurrence variants honor the fused/unfused parity contract:
    the Pallas superkernel and the reference loop produce bitwise-equal
    residual histories (the §13 invariant, extended to §18)."""
    op, prec, ops, b = _problem()
    for rec in ("ghysels", "stable"):
        kw = dict(l=3, tol=1e-8, maxit=300, recurrence=rec)
        ru = pipelined_cg.solve(ops, b, fused_iteration=False, **kw)
        rf = pipelined_cg.solve(ops, b, fused_iteration=True, **kw)
        assert np.array_equal(np.asarray(ru.res_history),
                              np.asarray(rf.res_history)), rec
        assert np.array_equal(np.asarray(ru.x), np.asarray(rf.x)), rec


def test_unknown_recurrence_rejected():
    op, prec, ops, b = _problem()
    with pytest.raises(ValueError, match="recurrence"):
        pipelined_cg.solve(ops, b, l=2, tol=1e-8, maxit=50,
                           recurrence="typo")


# ------------------------------------------------------- governed recovery --

def test_clean_governed_solve_truth_certified():
    """On a clean problem the governor costs only its periodic
    verification replacements: convergence is declared from the TRUE
    residual (never the recursion), and the certified solution meets
    tol."""
    op, prec, ops, b = _problem()
    res = pipelined_cg.solve(ops, b, l=4, tol=1e-6, maxit=400,
                             max_restarts=60, recurrence="stable",
                             governor=GovernorConfig())
    d = diagnose(res)
    assert d["converged"]
    assert d["replacements"] >= 1          # at least the certifying check
    assert not d["stagnated"]
    assert _true_rel(op, b, res.x) < 1e-6


def test_verify_only_governor_certifies_and_never_stagnates():
    """``verify_only`` arms the verification alone: the same decisions
    as a governor whose gap and patience arms cannot fire and which
    never demotes (bitwise), convergence only from a true-residual
    check, and at an unreachable tol it runs to maxit and never
    declares stagnation."""
    op, prec, ops, b = _problem()
    kw = dict(l=2, maxit=600, max_restarts=60, replace_every=50)
    never = 2**30
    res = pipelined_cg.solve(ops, b, tol=1e-6,
                             governor=GovernorConfig(verify_only=True), **kw)
    ref = pipelined_cg.solve(ops, b, tol=1e-6, governor=GovernorConfig(
        safety=0.0, patience=never, demote_after=never), **kw)
    assert np.array_equal(np.asarray(res.res_history),
                          np.asarray(ref.res_history))
    assert np.array_equal(np.asarray(res.x), np.asarray(ref.x))
    d = diagnose(res)
    assert d["converged"] and not d["stagnated"]
    assert _true_rel(op, b, res.x) < 1e-6

    far = pipelined_cg.solve(ops, b, tol=1e-30,
                             governor=GovernorConfig(verify_only=True), **kw)
    d = diagnose(far)
    assert not d["converged"] and not d["stagnated"]
    assert d["iters"] == kw["maxit"]


def test_governed_recovery_where_ungoverned_stagnates():
    """THE recovery demonstration (ISSUE acceptance): under a seeded
    ULP-scale reduction-payload fault at l=4, ungoverned ghysels p(l)-CG
    stagnates orders of magnitude above tol — the recursive residual
    detaches from the true one — while the governed stable solver
    reaches tol, certified against the true residual."""
    op, prec, ops, b = _problem()
    tol = 1e-5
    kw = dict(l=4, tol=tol, maxit=400, max_restarts=120)
    cops = chaos_ops(ops, ChaosConfig(seed=7, payload_rel_amp=1e-5))

    ungov = pipelined_cg.solve(cops, b, **kw)
    assert not bool(ungov.converged)
    assert _true_rel(op, b, ungov.x) > 100 * tol        # ~2e-2 measured

    gov = pipelined_cg.solve(cops, b, recurrence="stable",
                             governor=GovernorConfig(), **kw)
    d = diagnose(gov)
    assert d["converged"]
    assert d["replacements"] >= 5           # the governor did the work
    assert _true_rel(op, b, gov.x) < tol


def test_governed_batched_per_column():
    """Batched s=4 slab with the governor armed: every column converges
    truth-certified, and the per-column governor vectors record each
    column's own replacement count (masked interrupts — no cross-column
    coupling)."""
    op, prec, ops, b = _problem()
    B = jnp.asarray(np.random.default_rng(5).standard_normal((op.n, 4)))
    res = batched.solve_batched(ops, B, method="plcg", l=4, tol=1e-6,
                                maxit=400, max_restarts=60,
                                recurrence="stable",
                                governor=GovernorConfig())
    assert res.governor is not None
    g = np.asarray(res.governor)
    assert g.shape == (4, gov_model.N_SLOTS)
    assert np.asarray(res.converged).all()
    assert (g[:, int(gov_model.REPL)] >= 1).all()
    for j in range(4):
        assert _true_rel(op, B[:, j], np.asarray(res.x)[j]) < 1e-6, j


# ------------------------------------------------------ bitwise governor-off --

def test_governor_off_bitwise_single_and_batched():
    """Passing the new kwargs at their defaults (recurrence='ghysels',
    governor=None) is BITWISE invisible: identical histories, solutions
    and telemetry to omitting them — single RHS and batched s=8."""
    op, prec, ops, b = _problem()
    kw = dict(l=3, tol=1e-8, maxit=300)
    plain = pipelined_cg.solve(ops, b, **kw)
    expl = pipelined_cg.solve(ops, b, recurrence="ghysels",
                              governor=None, **kw)
    assert plain.governor is None and expl.governor is None
    assert np.array_equal(np.asarray(plain.res_history),
                          np.asarray(expl.res_history))
    assert np.array_equal(np.asarray(plain.x), np.asarray(expl.x))

    B = jnp.asarray(np.random.default_rng(5).standard_normal((op.n, 8)))
    bp = batched.solve_batched(ops, B, method="plcg", **kw)
    be_ = batched.solve_batched(ops, B, method="plcg",
                                recurrence="ghysels", governor=None, **kw)
    assert bp.governor is None and be_.governor is None
    assert np.array_equal(np.asarray(bp.res_history),
                          np.asarray(be_.res_history))
    assert np.array_equal(np.asarray(bp.x), np.asarray(be_.x))


def test_governor_off_bitwise_staged_shard_map():
    """The staged shard_map ladder keeps the same guarantee across the
    8-device mesh: explicit-default kwargs leave staged histories
    bitwise, and a GOVERNED staged solve still converges with bitwise
    parity vs the local virtual-shards ladder oracle."""
    out = _run(HEADER + """
kw = dict(method="plcg", l=2, sigmas=sig, tol=1e-8, maxit=400)
be_m = get_backend("shard_map", n_shards=8, reduction="staged")
plain = be_m.solve(op, b, **kw)
expl = be_m.solve(op, b, recurrence="ghysels", governor=None, **kw)
assert np.array_equal(np.asarray(plain.res_history),
                      np.asarray(expl.res_history))
assert np.array_equal(np.asarray(plain.x), np.asarray(expl.x))

gkw = dict(kw, tol=1e-6, recurrence="stable", governor=GovernorConfig())
be_o = get_backend("local", reduction="staged", virtual_shards=8)
gm = be_m.solve(op, b, **gkw)
go = be_o.solve(op, b, **gkw)
assert bool(gm.converged)
assert np.array_equal(np.asarray(gm.res_history), np.asarray(go.res_history))
assert np.array_equal(np.asarray(gm.governor), np.asarray(go.governor))
print("STAB-BITWISE-OK")
""")
    assert "STAB-BITWISE-OK" in out


# --------------------------------------------- one reduction start per iter --

def test_governed_compile_one_reduction_start_per_iteration():
    """The sacred invariant survives §18: with the governor armed and the
    stable recurrence selected, the compiled schedule still issues
    EXACTLY ONE pipelined reduction start per iteration — fused psum
    (starts_per_window) and staged ladder (staged_starts_per_window,
    zero dot-block all-reduces) alike."""
    out = _run(HEADER + """
from repro.utils.trace import plcg_overlap_report
gov = GovernorConfig()
be = get_backend("shard_map", n_shards=8)
bspec = jax.ShapeDtypeStruct((op.n,), jnp.float64)
for l in (2, 3):
    rep = plcg_overlap_report(be, op, bspec, l=l, window=l + 2,
                              sigmas=shifts_for_operator(op, l),
                              recurrence="stable", governor=gov)
    assert rep.max_in_flight >= l, (l, str(rep))
    assert len(rep.starts_per_window) == rep.window, str(rep)
    assert all(v == 1 for v in rep.starts_per_window.values()), \\
        (l, rep.starts_per_window)

be_s = get_backend("shard_map", n_shards=8, reduction="staged")
rep = plcg_overlap_report(be_s, op, bspec, l=2, window=4, sigmas=sig,
                          recurrence="stable", governor=gov)
assert rep.n_collectives == 0, rep.n_collectives
assert max(rep.staged_starts_per_window.values()) == 1, \\
    rep.staged_starts_per_window
print("STAB-HLO-OK")
""")
    assert "STAB-HLO-OK" in out


# ---------------------------------------------------------- demotion ladder --

def test_catastrophic_chaos_demotes_then_raises():
    """Catastrophic payload corruption (30% relative) defeats residual
    replacement at every depth: the host ladder demotes 4 -> 2 -> 1 and
    raises a typed StagnationError carrying the per-depth diagnosis —
    never a silently non-converged result."""
    op, prec, ops0, b = _problem()
    chaos = ChaosConfig(seed=3, payload_rel_amp=3e-1)
    be = get_backend("local")
    with pytest.raises(StagnationError) as ei:
        governed_solve(be, op, b, l=4, prec=prec,
                       ops_transform=lambda o: chaos_ops(o, chaos),
                       tol=1e-6, maxit=400, max_restarts=60)
    err = ei.value
    assert "l=1" in str(err)
    tried = [a["l"] for a in err.diagnosis["attempts"]]
    assert tried == [4, 2, 1], tried
    for a in err.diagnosis["attempts"]:
        assert not a["converged"]


def test_governed_solve_recovers_without_demotion():
    """Mild injected corruption is repaired at full depth: the ladder
    returns after one attempt, converged, with the chaos wire point
    exercised through ops_transform (the same hook the bench uses)."""
    op, prec, ops0, b = _problem()
    chaos = ChaosConfig(seed=7, payload_rel_amp=1e-5)
    be = get_backend("local")
    res, attempts = governed_solve(
        be, op, b, l=4, prec=prec,
        ops_transform=lambda o: chaos_ops(o, chaos),
        tol=1e-5, maxit=400, max_restarts=120)
    assert len(attempts) == 1 and attempts[0]["l"] == 4
    assert attempts[0]["converged"]
    assert _true_rel(op, b, res.x) < 1e-5
