"""Distributed paths (subprocess with 8 fake host devices): CG domain
decomposition vs single-device, one-fused-reduction structure in HLO,
reduction-backend parity (local vs shard_map residual histories), the
overlap tracer's in-flight chain count, and split-KV decode merge under
shard_map."""

import os
import subprocess
import sys

import pytest

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
from repro.parallel import distributed_solve, make_solver_mesh
from repro.linalg import Stencil2D5, Stencil3D7
from repro.core.chebyshev import shifts_for_operator
"""


def test_distributed_plcg_matches_local():
    out = _run(HEADER + """
from repro.core import pipelined_cg
from repro.core.types import SolverOps
op = Stencil2D5(32, 24)
b = jnp.asarray(np.random.default_rng(1).standard_normal(op.n))
mesh = make_solver_mesh(8)
sig = shifts_for_operator(op, 2)
res_d = distributed_solve(mesh, op, b, method="plcg", l=2, sigmas=sig,
                          tol=1e-10, maxit=2000)
res_l = pipelined_cg.solve(SolverOps.local(op), b, l=2, sigmas=sig,
                           tol=1e-10, maxit=2000)
assert int(res_d.iters) == int(res_l.iters)
np.testing.assert_allclose(np.asarray(res_d.x), np.asarray(res_l.x),
                           atol=1e-9)
print("DIST-MATCH-OK")
""")
    assert "DIST-MATCH-OK" in out


def test_distributed_3d_blockjacobi():
    out = _run(HEADER + """
from repro.linalg.preconditioners import BlockJacobi
op = Stencil3D7(16, 8, 8, eps_z=0.1)
b = jnp.asarray(np.random.default_rng(2).standard_normal(op.n))
bj = BlockJacobi.from_operator(op, block_size=8)
mesh = make_solver_mesh(8)
res = distributed_solve(mesh, op, b, method="plcg", prec=bj, l=1,
                        sigmas=shifts_for_operator(op, 1),
                        tol=1e-9, maxit=3000)
x_direct = np.linalg.solve(op.to_dense(), np.asarray(b))
assert np.abs(np.asarray(res.x) - x_direct).max() < 1e-5
print("DIST-3D-OK")
""")
    assert "DIST-3D-OK" in out


def test_single_fused_reduction_per_iteration():
    """The paper's key structure: ONE all-reduce site in the iteration
    body (plus init/restart), vs TWO for classic CG."""
    out = _run(HEADER + """
op = Stencil2D5(32, 24)
b = jax.ShapeDtypeStruct((op.n,), jnp.float64)
mesh = make_solver_mesh(8)
from jax.sharding import NamedSharding, PartitionSpec as P
def hlo_for(method, **kw):
    fn, arrays = distributed_solve(mesh, op, b, method=method, jit=False,
                                   maxit=50, **kw)
    bsh = NamedSharding(mesh, P("shards"))
    ash = jax.tree.map(lambda _: bsh, arrays)
    return jax.jit(fn, in_shardings=(bsh, ash)).lower(b, arrays)\
        .compile().as_text()

def count_ar(txt):
    return sum(line.count(" all-reduce(") + line.count(" all-reduce-start(")
               for line in txt.splitlines())

n_cg = count_ar(hlo_for("cg"))
n_pl = count_ar(hlo_for("plcg", l=2,
                        sigmas=shifts_for_operator(op, 2)))
# classic CG: 2 body + 1 init = 3; p(l)-CG: 1 body + 1 init + 1 restart = 3
# but the BODY difference is what matters: CG body has 2, plcg body has 1.
# The compiled while-body appears once; total sites: cg >= 3, plcg <= 3
assert n_cg >= 3, n_cg
assert n_pl <= n_cg, (n_pl, n_cg)
print("HLO-SITES-OK", n_cg, n_pl)
""")
    assert "HLO-SITES-OK" in out


@pytest.mark.parametrize("method", ["cg", "plcg"])
def test_backend_residual_history_parity(method):
    """The reduction backends are drop-in replacements: `local` and
    `shard_map` produce identical residual histories (fp32 tolerance) —
    ISSUE 1 acceptance, via the registry API."""
    kw = "l=2, sigmas=sig," if method == "plcg" else ""
    out = _run(HEADER + f"""
from repro.parallel import get_backend
op = Stencil2D5(32, 24)
b = jnp.asarray(np.random.default_rng(3).standard_normal(op.n), jnp.float32)
sig = jnp.asarray(shifts_for_operator(op, 2), jnp.float32)
kw = dict(method="{method}", {kw} tol=1e-5, maxit=400)
res_l = get_backend("local").solve(op, b, **kw)
res_s = get_backend("shard_map", n_shards=8).solve(op, b, **kw)
h_l = np.asarray(res_l.res_history)
h_s = np.asarray(res_s.res_history)
assert (h_l >= 0).sum() > 5
np.testing.assert_allclose(h_s, h_l, rtol=2e-4, atol=1e-5)
assert int(res_l.iters) == int(res_s.iters)
print("BACKEND-PARITY-OK")
""")
    assert "BACKEND-PARITY-OK" in out


def test_overlap_tracer_reports_inflight_chains():
    """The overlap tracer recovers >= l in-flight reduction chains from
    the compiled schedule of a window of l+2 p(l)-CG iterations on the
    8-device mesh (the paper's Fig. 4 staggering), while classic CG's
    blocking structure yields exactly 1."""
    out = _run(HEADER + """
from repro.parallel import get_backend
from repro.utils.trace import plcg_overlap_report
op = Stencil2D5(32, 24)
bspec = jax.ShapeDtypeStruct((op.n,), jnp.float64)
be = get_backend("shard_map", n_shards=8)
for l in (1, 2, 3):
    rep = plcg_overlap_report(be, op, bspec, l=l, window=l + 2,
                              sigmas=shifts_for_operator(op, l))
    assert rep.max_in_flight >= l, (l, rep.max_in_flight, str(rep))
    # one reduction per traced window; XLA's all-reduce combiner may
    # merge the independent pipeline-fill reductions into one tuple
    # all-reduce, so count handles per window, not instructions
    assert rep.starts_per_window == {k: 1 for k in range(l + 2)}, str(rep)
print("TRACER-OK")
""")
    assert "TRACER-OK" in out


def test_batched_slab_single_reduction_per_iteration():
    """ISSUE 2 acceptance: batched p(l)-CG with s=8 RHS on the 8-device
    mesh issues EXACTLY ONE reduction handle per iteration — the whole
    (2l+1, 8) dot-block matrix rides one all-reduce — while keeping the
    staggered in-flight depth >= l of the single-RHS pipeline."""
    out = _run(HEADER + """
from repro.parallel import get_backend
from repro.utils.trace import batched_plcg_overlap_report
op = Stencil2D5(32, 24)
be = get_backend("shard_map", n_shards=8)
s = 8
for l in (2, 3):
    Bspec = jax.ShapeDtypeStruct((op.n, s), jnp.float64)
    rep = batched_plcg_overlap_report(be, op, Bspec, l=l,
                                      sigmas=shifts_for_operator(op, l))
    assert rep.max_in_flight >= l, (l, rep.max_in_flight, str(rep))
    assert len(rep.starts_per_window) == rep.window, str(rep)
    assert all(v == 1 for v in rep.starts_per_window.values()), \\
        (l, rep.starts_per_window)
    # the window payload really is the full (2l+1, s) f64 matrix
    assert rep.collective_bytes >= rep.window * (2 * l + 1) * s * 8, str(rep)

# The PRODUCTION batched loop (not just the flat trace window) keeps the
# one-reduction structure: in the compiled solve_batched module no HLO
# computation — in particular no while body — carries more than one
# all-reduce.  (The restart/replacement interrupt reduction lives in its
# own per-segment computation; a vmapped in-loop lax.cond would instead
# inline a second all-reduce into the iteration body.)
import re
from repro.parallel import distributed_solve_batched
Bspec = jax.ShapeDtypeStruct((op.n, s), jnp.float64)
fn, arrays = distributed_solve_batched(
    be.mesh, op, Bspec, method="plcg", l=2,
    sigmas=shifts_for_operator(op, 2), tol=1e-9, maxit=300, jit=False)
hlo = jax.jit(fn).lower(Bspec, arrays).compile().as_text()
counts, cur = {}, None
for line in hlo.splitlines():
    m = re.match(r"^%?([\\w.\\-]+)\\s*\\(.*\\)\\s*->.*{", line) \\
        or re.match(r"^ENTRY\\s+%?([\\w.\\-]+)", line)
    if m:
        cur = m.group(1)
    if " all-reduce(" in line or " all-reduce-start(" in line:
        counts[cur] = counts.get(cur, 0) + 1
assert counts and max(counts.values()) <= 1, counts
print("BATCHED-TRACE-OK")
""")
    assert "BATCHED-TRACE-OK" in out


def test_batched_slab_parity_on_mesh():
    """Batched solve on the 8-device mesh == batched solve on one device,
    column by column (residual histories + iteration counts)."""
    out = _run(HEADER + """
from repro.parallel import get_backend
op = Stencil2D5(32, 24)
B = jnp.asarray(np.random.default_rng(5).standard_normal((op.n, 4)))
sig = shifts_for_operator(op, 2)
kw = dict(method="plcg", l=2, sigmas=sig, tol=1e-9, maxit=600)
res_s = get_backend("shard_map", n_shards=8).solve_batched(op, B, **kw)
res_l = get_backend("local").solve_batched(op, B, **kw)
assert np.array_equal(np.asarray(res_s.iters), np.asarray(res_l.iters))
np.testing.assert_allclose(np.asarray(res_s.res_history),
                           np.asarray(res_l.res_history),
                           rtol=1e-9, atol=1e-12)
np.testing.assert_allclose(np.asarray(res_s.x), np.asarray(res_l.x),
                           atol=1e-8)
print("BATCHED-PARITY-OK")
""")
    assert "BATCHED-PARITY-OK" in out


def test_unstructured_distributed_parity_three_backends():
    """ISSUE 3 tentpole: a general SparseOp (random FEM mesh) solved
    through the partition layer — RCM ordering, contiguous row blocks,
    ppermute halo gather — matches the single-device oracle on all three
    reduction backends and the direct dense solve.  (multiprocess in its
    single-process degradation shares shard_map's mesh: those two must
    agree bitwise; local vs sharded is compared on a tight head /
    bounded tail, since Krylov recurrences chaotically amplify
    reduction-order ULPs — a 1-ULP b perturbation alone moves the late
    history by ~0.5 relative on this operator class.)"""
    out = _run(HEADER + """
from repro.parallel import get_backend
from repro.linalg import random_fem_mesh, rcm_reorder
op, _perm = rcm_reorder(random_fem_mesh(0, 400))
b = jnp.asarray(np.random.default_rng(1).standard_normal(op.n))
sig = shifts_for_operator(op, 2)
xd = np.linalg.solve(op.to_dense(), np.asarray(b))
for method in ("cg", "pcg", "plcg"):
    kw = dict(method=method, tol=1e-9, maxit=900)
    if method == "plcg":
        kw.update(l=2, sigmas=sig)
    res = {name: get_backend(name, **(dict(n_shards=8)
                                      if name != "local" else {}))
           .solve(op, b, **kw)
           for name in ("local", "shard_map", "multiprocess")}
    for name, r in res.items():
        assert bool(r.converged), (method, name)
        assert np.abs(np.asarray(r.x) - xd).max() < 1e-6, (method, name)
    h_s = np.asarray(res["shard_map"].res_history)
    h_m = np.asarray(res["multiprocess"].res_history)
    np.testing.assert_array_equal(h_s, h_m)        # same mesh -> bitwise
    h_l = np.asarray(res["local"].res_history)
    n0 = float(res["local"].norm0)
    m = (h_l >= 0) & (h_s >= 0)
    diff = np.abs(h_s[m] - h_l[m]) / n0
    # Tight head (pre-amplification; a wrong halo/remap errs at O(1)
    # here), bounded tail (Krylov chaos, see docstring).  The head bound
    # leaves room for XLA CPU thread-level reduction-order jitter.
    assert diff[:10].max() < 1e-8, (method, diff[:10].max())
    assert diff.max() < 5e-2, (method, diff.max())
    assert abs(int(res["local"].iters) - int(res["shard_map"].iters)) <= 5
print("UNSTRUCTURED-PARITY-OK")
""")
    assert "UNSTRUCTURED-PARITY-OK" in out


def test_unstructured_overlap_and_halo_staggering():
    """ISSUE 3 acceptance: unstructured p(l)-CG keeps EXACTLY ONE
    allreduce per iteration with >= l reductions in flight, and the halo
    ppermutes are scheduled INSIDE the in-flight reduction windows —
    all asserted on compiled HLO via utils/trace.py."""
    out = _run(HEADER + """
from repro.parallel import get_backend
from repro.utils.trace import plcg_overlap_report, batched_plcg_overlap_report
from repro.linalg import random_fem_mesh, rcm_reorder
op, _perm = rcm_reorder(random_fem_mesh(0, 400))
be = get_backend("shard_map", n_shards=8)
bspec = jax.ShapeDtypeStruct((op.n,), jnp.float64)
for l in (2, 3):
    rep = plcg_overlap_report(be, op, bspec, l=l, window=l + 2,
                              sigmas=shifts_for_operator(op, l))
    assert rep.max_in_flight >= l, (l, str(rep))
    # exactly one reduction handle per iteration window
    assert len(rep.starts_per_window) == rep.window, str(rep)
    assert all(v == 1 for v in rep.starts_per_window.values()), \\
        (l, rep.starts_per_window)
    # halo ppermutes present and riding inside reduction windows
    assert rep.n_halo_permutes >= 2 * rep.window, str(rep)
    assert rep.halos_in_flight >= l, (l, str(rep))
# batched slab keeps the same structure (one handle, staggered halos)
Bspec = jax.ShapeDtypeStruct((op.n, 8), jnp.float64)
rep = batched_plcg_overlap_report(be, op, Bspec, l=2,
                                  sigmas=shifts_for_operator(op, 2))
assert rep.max_in_flight >= 2, str(rep)
assert all(v == 1 for v in rep.starts_per_window.values()), \\
    rep.starts_per_window
assert rep.halos_in_flight >= 2, str(rep)
print("UNSTRUCTURED-TRACE-OK")
""")
    assert "UNSTRUCTURED-TRACE-OK" in out


def test_fused_iteration_on_mesh():
    """ISSUE 4: the fused superkernel path on the 8-device mesh —
    bitwise-identical residual history to the unfused distributed path
    (stencil operator), and the overlap tracer still reports EXACTLY ONE
    reduction handle per iteration with >= l chains in flight: fusing
    the local phase must not change the communication structure."""
    out = _run(HEADER + """
from repro.parallel import get_backend
from repro.utils.trace import plcg_overlap_report, batched_plcg_overlap_report
op = Stencil2D5(32, 24)
b = jnp.asarray(np.random.default_rng(4).standard_normal(op.n))
be = get_backend("shard_map", n_shards=8)
for l in (1, 2, 3):
    kw = dict(method="plcg", l=l, sigmas=shifts_for_operator(op, l),
              tol=1e-9, maxit=600)
    ru = be.solve(op, b, **kw)
    rf = be.solve(op, b, fused_iteration=True, **kw)
    np.testing.assert_array_equal(np.asarray(ru.res_history),
                                  np.asarray(rf.res_history))
    np.testing.assert_array_equal(np.asarray(ru.x), np.asarray(rf.x))

bspec = jax.ShapeDtypeStruct((op.n,), jnp.float64)
for l in (2, 3):
    rep = plcg_overlap_report(be, op, bspec, l=l, window=l + 2,
                              sigmas=shifts_for_operator(op, l),
                              fused_iteration=True)
    assert rep.max_in_flight >= l, (l, rep.max_in_flight, str(rep))
    assert len(rep.starts_per_window) == rep.window, str(rep)
    assert all(v == 1 for v in rep.starts_per_window.values()), \\
        (l, rep.starts_per_window)

# batched slab, fused: still one handle per iteration, >= l in flight
Bspec = jax.ShapeDtypeStruct((op.n, 8), jnp.float64)
rep = batched_plcg_overlap_report(be, op, Bspec, l=2,
                                  sigmas=shifts_for_operator(op, 2),
                                  fused_iteration=True)
assert rep.max_in_flight >= 2, str(rep)
assert all(v == 1 for v in rep.starts_per_window.values()), \\
    rep.starts_per_window
print("FUSED-MESH-OK")
""")
    assert "FUSED-MESH-OK" in out


def test_staged_reduction_hlo_structure():
    """ISSUE 5 tentpole acceptance: with ``reduction="staged"`` the dot
    block compiles to REDUCE_TAG'd collective-permute hops and the
    module carries ZERO all-reduces; the tracer still sees >= l chains
    in flight, >= l ladder hops in every traced window, EXACTLY one
    logical reduction (hop-0 permute) per iteration, and the hop/halo
    staggering — ladder hops scheduled inside open reduction windows."""
    out = _run(HEADER + """
from repro.parallel import get_backend
from repro.utils.trace import plcg_overlap_report, batched_plcg_overlap_report
op = Stencil2D5(32, 24)
bspec = jax.ShapeDtypeStruct((op.n,), jnp.float64)
for stages in (1, 2):
    be = get_backend("shard_map", n_shards=8, reduction="staged",
                     reduction_stages=stages)
    for l in (2, 3):
        rep = plcg_overlap_report(be, op, bspec, l=l, window=l + 2,
                                  sigmas=shifts_for_operator(op, l))
        # no all-reduce anywhere in the staged dot-block schedule
        assert rep.n_collectives == 0, (stages, l, rep.n_collectives)
        assert rep.max_in_flight >= l, (stages, l, str(rep))
        # ladder hops present in every window, >= l per window
        assert len(rep.reduce_hops_per_window) == rep.window, str(rep)
        assert min(rep.reduce_hops_per_window.values()) >= l, \\
            (stages, l, rep.reduce_hops_per_window)
        # exactly ONE logical reduction handle enters the wire per
        # iteration, whatever the stage grouping
        assert all(v == 1 for v in rep.staged_starts_per_window.values()), \\
            (stages, l, rep.staged_starts_per_window)
        assert len(rep.staged_starts_per_window) == rep.window
        # hop/halo staggering: ladder hops AND halo permutes ride inside
        # the open reduction windows
        assert rep.hops_in_flight >= l, (stages, l, rep.hops_in_flight)
        assert rep.halos_in_flight >= l, (stages, l, str(rep))

# batched slab (s=8): same structure — one hop-0 permute per window (the
# vmapped ladder collapses to ONE permute per hop carrying the whole
# (2l+1, s) payload), zero all-reduce, >= l in flight.
be = get_backend("shard_map", n_shards=8, reduction="staged")
Bspec = jax.ShapeDtypeStruct((op.n, 8), jnp.float64)
rep = batched_plcg_overlap_report(be, op, Bspec, l=2,
                                  sigmas=shifts_for_operator(op, 2))
assert rep.n_collectives == 0, rep.n_collectives
assert rep.max_in_flight >= 2, str(rep)
assert all(v == 1 for v in rep.staged_starts_per_window.values()), \\
    rep.staged_starts_per_window
assert min(rep.reduce_hops_per_window.values()) >= 2
print("STAGED-HLO-OK")
""")
    assert "STAGED-HLO-OK" in out


def test_staged_reduction_parity():
    """Staged-vs-monolithic residual histories are BITWISE identical on
    stencils (the ladder's rank-order sum reproduces the monolithic
    all-reduce's deterministic linear order), across stage counts and
    for the batched slab; the local eager ladder oracle with
    virtual_shards=8 matches the 8-shard mesh bitwise too.  FEM
    SparseOp follows the PR 3 convention (tight head, bounded tail:
    local partials differ at ULP level between substrates)."""
    out = _run(HEADER + """
from repro.parallel import get_backend
op = Stencil2D5(32, 24)
b = jnp.asarray(np.random.default_rng(1).standard_normal(op.n))
sig = shifts_for_operator(op, 2)
kw = dict(method="plcg", l=2, sigmas=sig, tol=1e-10, maxit=2000)
r_mono = get_backend("shard_map", n_shards=8).solve(op, b, **kw)
hm = np.asarray(r_mono.res_history)
for stages in (1, 2, 7):
    r = get_backend("shard_map", n_shards=8, reduction="staged",
                    reduction_stages=stages).solve(op, b, **kw)
    np.testing.assert_array_equal(np.asarray(r.res_history), hm)
    np.testing.assert_array_equal(np.asarray(r.x), np.asarray(r_mono.x))
# eager ladder oracle == staged mesh, bitwise
r_o = get_backend("local", reduction="staged", virtual_shards=8).solve(
    op, b, **kw)
np.testing.assert_array_equal(np.asarray(r_o.res_history), hm)
# ghysels p-CG staged (one advance inside its overlap window)
kw_p = dict(method="pcg", tol=1e-10, maxit=2000)
r_pm = get_backend("shard_map", n_shards=8).solve(op, b, **kw_p)
r_ps = get_backend("shard_map", n_shards=8, reduction="staged").solve(
    op, b, **kw_p)
np.testing.assert_array_equal(np.asarray(r_ps.res_history),
                              np.asarray(r_pm.res_history))
# batched slab staged == batched monolithic, bitwise
B = jnp.asarray(np.random.default_rng(5).standard_normal((op.n, 4)))
kwb = dict(method="plcg", l=2, sigmas=sig, tol=1e-9, maxit=600)
rb_m = get_backend("shard_map", n_shards=8).solve_batched(op, B, **kwb)
rb_s = get_backend("shard_map", n_shards=8,
                   reduction="staged").solve_batched(op, B, **kwb)
np.testing.assert_array_equal(np.asarray(rb_s.res_history),
                              np.asarray(rb_m.res_history))
np.testing.assert_array_equal(np.asarray(rb_s.x), np.asarray(rb_m.x))
# fused superkernel on the staged mesh: vector phase fuses, the ladder
# carries the VMEM-accumulated partials — still bitwise vs unfused.
r_f = get_backend("shard_map", n_shards=8, reduction="staged").solve(
    op, b, fused_iteration=True, **kw)
np.testing.assert_array_equal(np.asarray(r_f.res_history), hm)
print("STAGED-PARITY-OK")
""")
    assert "STAGED-PARITY-OK" in out


def test_staged_reduction_fem_and_fp32():
    """Staged reduction on an unstructured FEM SparseOp (bounded-tail
    vs monolithic, PR 3 convention) and the fp32-payload wire with fp64
    compensated accumulation (halved hop bytes; bounded-tail parity,
    converges at the same iteration count +-2)."""
    out = _run(HEADER + """
from repro.parallel import get_backend
from repro.linalg import random_fem_mesh, rcm_reorder
op, _perm = rcm_reorder(random_fem_mesh(0, 400))
b = jnp.asarray(np.random.default_rng(1).standard_normal(op.n))
sig = shifts_for_operator(op, 2)
kw = dict(method="plcg", l=2, sigmas=sig, tol=1e-9, maxit=900)
r_m = get_backend("shard_map", n_shards=8).solve(op, b, **kw)
r_s = get_backend("shard_map", n_shards=8, reduction="staged").solve(
    op, b, **kw)
xd = np.linalg.solve(op.to_dense(), np.asarray(b))
for r in (r_m, r_s):
    assert bool(r.converged)
    assert np.abs(np.asarray(r.x) - xd).max() < 1e-6
hm, hs = np.asarray(r_m.res_history), np.asarray(r_s.res_history)
n0 = float(r_m.norm0)
m = (hm >= 0) & (hs >= 0)
diff = np.abs(hs[m] - hm[m]) / n0
assert diff[:10].max() < 1e-8, diff[:10].max()
assert diff.max() < 5e-2, diff.max()
assert abs(int(r_s.iters) - int(r_m.iters)) <= 5

# fp32 payload on the stencil: bounded tail, same convergence
op2 = Stencil2D5(32, 24)
b2 = jnp.asarray(np.random.default_rng(2).standard_normal(op2.n))
sig2 = shifts_for_operator(op2, 2)
kw2 = dict(method="plcg", l=2, sigmas=sig2, tol=1e-9, maxit=2000)
r64 = get_backend("shard_map", n_shards=8, reduction="staged").solve(
    op2, b2, **kw2)
r32 = get_backend("shard_map", n_shards=8, reduction="staged",
                  reduction_dtype=jnp.float32).solve(op2, b2, **kw2)
assert bool(r32.converged)
assert abs(int(r32.iters) - int(r64.iters)) <= 2
h64, h32 = np.asarray(r64.res_history), np.asarray(r32.res_history)
n0 = float(r64.norm0)
m = (h64 >= 0) & (h32 >= 0)
diff = np.abs(h64[m] - h32[m]) / n0
assert diff[:10].max() < 1e-5, diff[:10].max()
assert diff.max() < 5e-2, diff.max()
# the fp32 wire really is half-width in the compiled HLO: the hop
# permutes carry f32 payloads
from repro.parallel.distributed import distributed_solve
from jax.sharding import NamedSharding, PartitionSpec as P
be32 = get_backend("shard_map", n_shards=8, reduction="staged",
                   reduction_dtype=jnp.float32)
bspec = jax.ShapeDtypeStruct((op2.n,), jnp.float64)
fn, arrays = distributed_solve(be32.mesh, op2, bspec, method="plcg", l=2,
                               sigmas=sig2, tol=1e-9, maxit=100, jit=False,
                               reduction=be32.reduction_cfg)
bsh = NamedSharding(be32.mesh, P("shards"))
ash = jax.tree.map(lambda _: bsh, arrays)
hlo = jax.jit(fn, in_shardings=(bsh, ash)).lower(bspec, arrays)\\
    .compile().as_text()
hop_lines = [ln for ln in hlo.splitlines()
             if "collective-permute" in ln and "glred_hop" in ln
             and "-done" not in ln]
assert hop_lines and all(" f32[" in ln for ln in hop_lines), \\
    hop_lines[:3]
assert not any(" all-reduce(" in ln or " all-reduce-start(" in ln
               for ln in hlo.splitlines())
print("STAGED-FEM-FP32-OK")
""")
    assert "STAGED-FEM-FP32-OK" in out


def test_splitkv_merge_under_shard_map():
    """Cross-shard split-KV decode: sequence sharded over 8 devices,
    merged with one pmax + one fused psum == unsharded attention."""
    out = _run("""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.models.attention import decode_attention_jnp, merge_decode_shards
from repro.kernels import ops as kops

b, h, hkv, d, s = 2, 8, 4, 32, 512
rng = np.random.default_rng(0)
q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
mesh = jax.make_mesh((8,), ("kv",))

def shard_fn(q, k, v):
    o, m, l = kops.decode_attention_stats(q, k, v, k.shape[1], block_s=64)
    return merge_decode_shards(o, m, l, "kv")

fn = jax.shard_map(shard_fn, mesh=mesh,
                   in_specs=(P(), P(None, "kv", None, None),
                             P(None, "kv", None, None)),
                   out_specs=P(), check_vma=False)
merged = jax.jit(fn)(q, k, v).reshape(b, h, d)
full = kops.decode_attention(q, k, v, kv_len=s, block_s=64)
np.testing.assert_allclose(merged, np.asarray(full), rtol=3e-4, atol=3e-4)
print("SPLITKV-OK")
""")
    assert "SPLITKV-OK" in out
