"""Fused-iteration superkernel (DESIGN.md §13): bitwise parity against
the unfused reference path, the >= 2x modeled-HBM-bytes reduction, and
the donated / in-place slab state."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_enable_x64", True)

from repro.core import pipelined_cg
from repro.core.batched import solve_batched
from repro.core.chebyshev import shifts_for_operator
from repro.core.types import SolverOps
from repro.kernels import ref
from repro.kernels.fused_iter import SlabLayout, idx_layout, scal_layout
from repro.kernels.ops import fused_iteration_factory
from repro.launch.autotune import (fused_iteration_bytes,
                                   measured_iteration_bytes)
from repro.linalg.operators import DiagonalOp, Stencil2D5, Stencil3D7
from repro.linalg.preconditioners import BlockJacobi, JacobiPrec
from repro.linalg.sparse import random_fem_mesh, rcm_reorder
from repro.parallel import get_backend

RNG = np.random.default_rng(11)


def _solve_pair(op, prec, l, maxit=800, tol=1e-9):
    ops = SolverOps.local(op, prec)
    sig = shifts_for_operator(op, l)
    b = jnp.asarray(RNG.standard_normal(op.n))

    def run(fused):
        return jax.jit(lambda bb: pipelined_cg.solve(
            ops, bb, l, sigmas=sig, tol=tol, maxit=maxit,
            fused_iteration=fused))(b)

    return run(False), run(True)


# ------------------------------------------------------- kernel vs oracle --

@pytest.mark.parametrize("l", [1, 2, 3])
def test_kernel_matches_unfused_oracle(l):
    """One vector phase, random (valid-shaped) slab/idx/scal: the
    superkernel must reproduce ref.fused_iter_ref BITWISE — same
    expressions, same operands, one pass."""
    op = Stencil2D5(16, 12)
    layout = SlabLayout(l=l, RB=max(l + 1, 3))
    factory = fused_iteration_factory(op)
    fiter = factory(layout)
    IX, IS = idx_layout(l), scal_layout(l)
    S = jnp.asarray(RNG.standard_normal((layout.nv, op.n)))
    # plausible late-phase index bundle (i = l + 2)
    i = jnp.int32(l + 2)
    idx = jnp.zeros((IX["size"],), jnp.int32)
    for k in range(l):
        idx = idx.at[IX["fill"] + k].set(layout.zk_row(k, i + 1))
        idx = idx.at[IX["rec_w"] + k].set(layout.zk_row(k, i - l + k + 1))
        idx = idx.at[IX["rec_a"] + k].set(layout.zk_row(k + 1, i - l + k + 1))
        idx = idx.at[IX["rec_b"] + k].set(layout.zk_row(k, i - l + k))
        idx = idx.at[IX["rec_c"] + k].set(layout.zk_row(k, i - l + k - 1))
        idx = idx.at[IX["mat_v"] + k].set(layout.zk_row(0, i - 2 * l + 1 + k))
    for t in range(l - 1):
        idx = idx.at[IX["mat_z"] + t].set(layout.zk_row(l, i - l + 2 + t))
    idx = idx.at[IX["z_top"]].set(layout.zk_row(l, i))
    idx = idx.at[IX["zl_im1"]].set(layout.zk_row(l, i - 1))
    idx = idx.at[IX["z_w"]].set(layout.zk_row(l, i + 1))
    idx = idx.at[IX["u_i"]].set(layout.u_row(i))
    idx = idx.at[IX["u_im1"]].set(layout.u_row(i - 1))
    idx = idx.at[IX["u_w"]].set(layout.u_row(i + 1))
    idx = idx.at[IX["p_im"]].set(layout.zk_row(0, i - l))
    idx = idx.at[IX["f_late"]].set(1)
    idx = idx.at[IX["f_upd"]].set(1)
    scal = jnp.asarray(RNG.standard_normal(IS["size"]))
    scal = scal.at[IS["dlt_safe"]].set(1.25)
    scal = scal.at[IS["eta_new_safe"]].set(0.75)
    scal = scal.at[IS["eta0_safe"]].set(1.5)

    S_k, d_k = jax.jit(fiter)(S, idx, scal)
    S_r, d_r = jax.jit(lambda a, b_, c: ref.fused_iter_ref(
        a, b_, c, op.apply, lambda v: v, layout))(S, idx, scal)
    np.testing.assert_array_equal(np.asarray(S_k), np.asarray(S_r))
    np.testing.assert_array_equal(np.asarray(d_k), np.asarray(d_r))


def test_kernel_multi_tile_rows_bitwise():
    """Tiling the slab over columns must not change any ROW update
    bitwise; only the dot-partial summation order moves (documented
    tight-tail policy, DESIGN.md §13)."""
    op = Stencil2D5(16, 12)
    l = 2
    layout = SlabLayout(l=l, RB=3)
    factory = fused_iteration_factory(op)
    f1 = factory(layout)                       # single tile (default)
    f4 = factory(layout, block_n=op.n // 4)    # 4 tiles
    IX, IS = idx_layout(l), scal_layout(l)
    S = jnp.asarray(RNG.standard_normal((layout.nv, op.n)))
    idx = jnp.asarray(RNG.integers(0, layout.nv, IX["size"]), jnp.int32)
    idx = idx.at[IX["f_late"]].set(1).at[IX["f_upd"]].set(1)
    for k in range(l):
        idx = idx.at[IX["f_fill"] + k].set(0)
    scal = jnp.asarray(RNG.standard_normal(IS["size"]))
    scal = scal.at[IS["dlt_safe"]].set(1.1)
    scal = scal.at[IS["eta_new_safe"]].set(0.9)
    scal = scal.at[IS["eta0_safe"]].set(1.2)
    S1, d1 = jax.jit(f1)(S, idx, scal)
    S4, d4 = jax.jit(f4)(S, idx, scal)
    np.testing.assert_array_equal(np.asarray(S1), np.asarray(S4))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d4),
                               rtol=1e-12, atol=1e-12)


# ------------------------------------------------- solver-level parity ----

@pytest.mark.parametrize("l", [1, 2, 3])
def test_bitwise_parity_stencil2d(l):
    ru, rf = _solve_pair(Stencil2D5(32, 24), None, l)
    assert bool(ru.converged) and bool(rf.converged)
    assert int(ru.iters) == int(rf.iters)
    np.testing.assert_array_equal(np.asarray(ru.res_history),
                                  np.asarray(rf.res_history))
    np.testing.assert_array_equal(np.asarray(ru.x), np.asarray(rf.x))


def test_bitwise_parity_stencil3d_jacobi():
    op = Stencil3D7(8, 8, 8, eps_z=0.1)
    ru, rf = _solve_pair(op, JacobiPrec.from_operator(op), 2)
    assert bool(ru.converged)
    np.testing.assert_array_equal(np.asarray(ru.res_history),
                                  np.asarray(rf.res_history))
    np.testing.assert_array_equal(np.asarray(ru.x), np.asarray(rf.x))


def test_bitwise_parity_sparse():
    """Unstructured ELL rows through the superkernel: the in-kernel
    gather + explicit rowsum chain mirrors SparseOp.apply, so even the
    sparse path holds bitwise on a single device."""
    op, _perm = rcm_reorder(random_fem_mesh(0, 400))
    ru, rf = _solve_pair(op, None, 2, maxit=900)
    assert bool(ru.converged)
    np.testing.assert_array_equal(np.asarray(ru.res_history),
                                  np.asarray(rf.res_history))
    np.testing.assert_array_equal(np.asarray(ru.x), np.asarray(rf.x))


@pytest.mark.parametrize("l", [1, 2, 3])
def test_bitwise_parity_batched_s8(l):
    """The s=8 slab: vmapped superkernel vs vmapped unfused path, every
    column bitwise (the trailing-axis dot-block reduction is what makes
    this hold under batching — types.dot_block_rows)."""
    op = Stencil2D5(32, 24)
    ops = SolverOps.local(op)
    sig = shifts_for_operator(op, l)
    B = jnp.asarray(RNG.standard_normal((op.n, 8)))

    def run(fused):
        return jax.jit(lambda BB: solve_batched(
            ops, BB, "plcg", l=l, sigmas=sig, tol=1e-9, maxit=600,
            fused_iteration=fused))(B)

    ru, rf = run(False), run(True)
    np.testing.assert_array_equal(np.asarray(ru.res_history),
                                  np.asarray(rf.res_history))
    np.testing.assert_array_equal(np.asarray(ru.x), np.asarray(rf.x))
    assert np.array_equal(np.asarray(ru.iters), np.asarray(rf.iters))


def test_unsupported_combination_raises():
    import dataclasses

    op = Stencil3D7(8, 8, 8)
    bj = BlockJacobi.from_operator(op, block_size=8)
    ops = SolverOps.local(op, bj)
    with pytest.raises(ValueError, match="fused_iter_factory"):
        pipelined_cg.build(ops, jnp.zeros((op.n,)), 2, fused_iteration=True)
    # kernel-routed operators have no fused mirror either (their
    # standalone-kernel reductions round differently from the jnp
    # expressions the superkernel mirrors) — must fail loudly, not
    # silently break the bitwise contract
    sop, _ = rcm_reorder(random_fem_mesh(1, 200))
    sop_k = dataclasses.replace(sop, use_kernel=True)
    ops_k = SolverOps.local(sop_k)
    with pytest.raises(ValueError, match="fused_iter_factory"):
        pipelined_cg.build(ops_k, jnp.zeros((sop_k.n,)), 2,
                           fused_iteration=True)


# ------------------------------------------------------- HBM bytes gate ---

@pytest.mark.parametrize("l", [1, 2, 3])
def test_fused_hbm_bytes_at_least_2x_smaller(l):
    """ISSUE 4 acceptance: modeled HBM bytes per iteration drop >= 2x.

    Unfused side: XLA cost_analysis of the compiled iteration (the
    ~dozen separate slab passes, measured).  Fused side: the TPU
    accounting of the superkernel — an opaque custom call reads its
    operands and writes its results once (slab in/out + resident SPMV
    operand; ``fused_iteration_bytes``).  The interpret-mode
    cost_analysis of the fused path is NOT used here: the interpreter
    re-materializes kernel-interior temporaries that Mosaic keeps in
    VMEM (documented in benchmarks/iter_bench.py, which records all
    three numbers)."""
    op = Stencil2D5(128, 128)
    sig = shifts_for_operator(op, l)
    unfused = measured_iteration_bytes(op, l, sigmas=sig, fused=False)
    fused = fused_iteration_bytes(op.n, l)
    assert fused * 2 <= unfused, (l, fused, unfused, fused / unfused)


def test_iteration_bytes_grow_with_depth():
    n = 4096
    vals = [fused_iteration_bytes(n, l) for l in (1, 2, 3)]
    assert vals[0] < vals[1] < vals[2]
    # dominated by slab in + out: 2 * NV * n * 8, NV = (l+1)*RB + 5
    for l, v in zip((1, 2, 3), vals):
        nv = (l + 1) * max(l + 1, 3) + 5
        assert v >= 2 * nv * n * 8


# ----------------------------------------------------------- donation -----

def _slab_copy_count(prog, B, st):
    txt = prog.chunk.lower(B, st).compile().as_text()
    # (s, NV, N) fused, (s, NV, R, 128) tiled rows (SlabLayout)
    shape = f"f64[{','.join(map(str, st.cyc.S.shape))}]"
    alias = "input_output_alias" in txt.splitlines()[0]
    copies = sum(line.count(" copy(") for line in txt.splitlines()
                 if shape in line)
    return copies, alias


@pytest.mark.parametrize("fused", [False, True])
def test_slab_program_donation(fused):
    """The slab program's chunk donates its state: the jit boundary
    aliases the (s, NV, *row) slab (input_output_alias in the compiled
    module), the while loop carries it with NO per-iteration copy (the
    slab-shaped copy count is INVARIANT to chunk length — a per-
    iteration copy would scale it), and the donated buffer is actually
    consumed (the old state is unusable afterwards)."""
    op = Stencil2D5(16, 12)
    be = get_backend("local")
    kw = dict(method="plcg", l=2, sigmas=shifts_for_operator(op, 2),
              tol=1e-9, maxit=200, fused_iteration=fused)
    B = jnp.asarray(RNG.standard_normal((op.n, 4)))

    prog1 = be.make_slab_program(op, s=4, chunk_iters=1, **kw)
    prog16 = be.make_slab_program(op, s=4, chunk_iters=16, **kw)
    st = prog1.init(B)
    c1, alias1 = _slab_copy_count(prog1, B, st)
    c16, alias16 = _slab_copy_count(prog16, B, prog16.init(B))
    assert alias1 and alias16
    assert c1 == c16, (c1, c16)        # no per-iteration state copy

    # Donation is live: the consumed state must be unusable afterwards.
    st2 = prog16.chunk(B, st)
    assert st2.cyc.S.shape == st.cyc.S.shape
    with pytest.raises(RuntimeError):
        np.asarray(st.cyc.S)


def test_fused_kernel_aliases_slab_buffer():
    """input_output_aliases on the pallas call: the compiled single
    iteration (state donated) reports the slab param aliased through to
    the output in the module's alias table."""
    op = Stencil2D5(16, 12)
    ops = SolverOps.local(op)
    b = jnp.zeros((op.n,), jnp.float64)
    prog = pipelined_cg.build(ops, b, 2, sigmas=shifts_for_operator(op, 2),
                              fused_iteration=True)
    st0 = jax.eval_shape(prog.init, b)
    txt = jax.jit(lambda st: prog.iteration(st, static_phase="late"),
                  donate_argnums=(0,)).lower(st0).compile().as_text()
    header = txt.splitlines()[0]
    assert "input_output_alias" in header
    # the (NV, N) slab itself appears in the alias table (shape-matched
    # param aliased to a shape-matched output)
    assert re.search(r"f64\[14,192\]", txt)
