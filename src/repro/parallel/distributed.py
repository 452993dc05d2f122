"""Distributed execution of the CG solver family under ``shard_map``.

This module is the *mechanism* behind the ``shard_map`` reduction backend
(``repro.parallel.backends.shard_map``, DESIGN.md §3).  Prefer the backend
API for new code::

    from repro.parallel import get_backend
    be = get_backend("shard_map", n_shards=8)
    res = be.solve(op, b, method="plcg", l=2, sigmas=sig, tol=1e-8)

``distributed_solve`` below remains the stable low-level entry point the
backend delegates to.

This is the paper's MPI rank layout mapped to a JAX mesh (DESIGN.md §2):

  * the solution vector is DOMAIN-DECOMPOSED: each device owns a contiguous
    block of grid rows (the paper's per-rank sub-domain);
  * the SPMV is a halo exchange (``lax.ppermute`` of one boundary plane in
    each direction — point-to-point neighbour communication, the MPI halo
    send/recv) followed by a purely local stencil application;
  * the preconditioner is communication-free (Jacobi / block-Jacobi with
    blocks interior to a shard — the paper's "limited communication
    preconditioner" that motivates longer pipelines);
  * ALL inner products of one iteration form ONE fused ``lax.psum`` — the
    single ``MPI_Iallreduce`` of the G-column block (Alg. 2, line 11).

The solvers themselves (``repro.core``) are substrate-agnostic: the same
code runs locally or distributed, because every global operation goes
through ``SolverOps``.  Under ``shard_map`` the p(l)-CG data-dependency
structure means the ``psum`` issued at iteration i has no consumer for l
loop iterations — XLA's latency-hiding scheduler can keep l reductions in
flight (the Iallreduce/Wait window of Fig. 1).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import METHODS
from repro.core import batched as batched_mod
from repro.core.types import (HALO_TAG, SolveResult, SolverOps,
                              dot_block_rows)
from repro.linalg import partition as partition_mod
from repro.linalg.operators import (
    DiagonalOp,
    LinearOperator,
    Stencil2D5,
    Stencil3D7,
    Stencil3D27,
)
from repro.linalg.preconditioners import (BlockJacobi, IdentityPrec,
                                         JacobiPrec, inverse_diagonal)
from repro.linalg.sparse import SparseOp


def make_solver_mesh(n_shards: int | None = None, devices=None) -> Mesh:
    """1-D mesh over all (or the first ``n_shards``) devices.

    The solver path flattens whatever production mesh exists into a single
    "shards" axis: CG's domain decomposition is rank-structured, exactly as
    in the paper's MPI runs.
    """
    devs = jax.devices() if devices is None else devices
    n = len(devs) if n_shards is None else n_shards
    return Mesh(np.asarray(devs[:n]).reshape(n), ("shards",))


# --------------------------------------------------------------------------
# Halo exchange (the MPI neighbour send/recv).
# --------------------------------------------------------------------------

def _halo_first_dim(g: jax.Array, axis: str) -> tuple[jax.Array, jax.Array]:
    """Exchange one boundary plane along the (sharded) first grid dim.

    Returns (plane_above, plane_below) for this shard.  ``ppermute`` leaves
    zeros where no neighbour exists — which is exactly the homogeneous
    Dirichlet boundary condition of the operators.
    """
    n = lax.axis_size(axis)
    if n == 1:
        z = jnp.zeros_like(g[:1])
        return z, z
    # HALO_TAG scope: the overlap tracer locates these point-to-point
    # exchanges in the compiled schedule to verify they ride inside the
    # in-flight reduction windows (DESIGN.md §6/§12).
    with jax.named_scope(HALO_TAG):
        above = lax.ppermute(g[-1:], axis, [(i, i + 1) for i in range(n - 1)])
        below = lax.ppermute(g[:1], axis, [(i, i - 1) for i in range(1, n)])
    return above, below


def _apply_2d5_local(x: jax.Array, nxl: int, ny: int, axis: str) -> jax.Array:
    g = x.reshape(nxl, ny)
    up, dn = _halo_first_dim(g, axis)
    gp = jnp.concatenate([up, g, dn], axis=0)          # (nxl+2, ny)
    gy = jnp.pad(g, ((0, 0), (1, 1)))
    out = 4.0 * g - gp[:-2] - gp[2:] - gy[:, :-2] - gy[:, 2:]
    return out.reshape(-1)


def _apply_3d7_local(
    x: jax.Array, nxl: int, ny: int, nz: int, eps_z: float, axis: str
) -> jax.Array:
    g = x.reshape(nxl, ny, nz)
    up, dn = _halo_first_dim(g, axis)
    gp = jnp.concatenate([up, g, dn], axis=0)
    gy = jnp.pad(g, ((0, 0), (1, 1), (0, 0)))
    gz = jnp.pad(g, ((0, 0), (0, 0), (1, 1)))
    ez = jnp.asarray(eps_z, dtype=x.dtype)
    out = (
        (4.0 + 2.0 * ez) * g
        - gp[:-2] - gp[2:]
        - gy[:, :-2, :] - gy[:, 2:, :]
        - ez * gz[:, :, :-2] - ez * gz[:, :, 2:]
    )
    return out.reshape(-1)


def _apply_3d27_local(
    x: jax.Array, nxl: int, ny: int, nz: int, centre: float, axis: str
) -> jax.Array:
    g = x.reshape(nxl, ny, nz)
    up, dn = _halo_first_dim(g, axis)
    gp = jnp.concatenate([up, g, dn], axis=0)          # (nxl+2, ny, nz)
    gp = jnp.pad(gp, ((0, 0), (1, 1), (1, 1)))         # pad y,z of halo too
    out = jnp.asarray(centre, x.dtype) * g
    for di in (-1, 0, 1):
        for dj in (-1, 0, 1):
            for dk in (-1, 0, 1):
                order = abs(di) + abs(dj) + abs(dk)
                if order == 0:
                    continue
                w = {1: 1.0, 2: 0.5, 3: 0.25}[order]
                out = out - w * gp[
                    1 + di : 1 + di + nxl,
                    1 + dj : 1 + dj + ny,
                    1 + dk : 1 + dk + nz,
                ]
    return out.reshape(-1)


# --------------------------------------------------------------------------
# Partitioning of operators / preconditioners into (sharded arrays, builder).
# --------------------------------------------------------------------------

def _partition_op(op: LinearOperator, n_shards: int):
    """Return (arrays, build, perm) where ``arrays`` is a pytree of global
    arrays sharded over the solver axis, ``build(local_arrays, axis)``
    yields the local apply function (for use INSIDE shard_map), and
    ``perm`` is the global row ordering the partition imposed
    (``perm[new] = old``; None when the operator keeps its own order).

    Structured operators partition for free (their halo is a boundary
    plane); a general :class:`SparseOp` goes through the partitioning
    layer (``repro.linalg.partition``, DESIGN.md §12): RCM ordering →
    contiguous row blocks → precomputed send/recv index sets, making the
    shard-level SpMV local-rows + ``ppermute`` halo gather.
    """
    if isinstance(op, SparseOp):
        plan = partition_mod.plan_for(op, n_shards)
        arrays = {
            "cols": plan.cols, "vals": plan.vals,
            "send_up": plan.send_up, "send_dn": plan.send_dn,
        }
        use_kernel = op.use_kernel

        def build(loc, axis):
            return lambda x: partition_mod.apply_local(
                x, loc["cols"][0], loc["vals"][0],
                loc["send_up"][0], loc["send_dn"][0], axis,
                use_kernel=use_kernel,
            )

        perm = None if plan.identity_perm else plan.perm
        return arrays, build, perm

    if isinstance(op, DiagonalOp):
        arrays = {"d": op.d}

        def build(loc, axis):
            return lambda x: loc["d"].astype(x.dtype) * x

        return arrays, build, None

    if isinstance(op, Stencil2D5):
        assert op.nx % n_shards == 0, (op.nx, n_shards)
        nxl = op.nx // n_shards
        return {}, lambda loc, axis: partial(
            _apply_2d5_local, nxl=nxl, ny=op.ny, axis=axis
        ), None

    if isinstance(op, Stencil3D7):
        assert op.nx % n_shards == 0, (op.nx, n_shards)
        nxl = op.nx // n_shards
        return {}, lambda loc, axis: partial(
            _apply_3d7_local, nxl=nxl, ny=op.ny, nz=op.nz, eps_z=op.eps_z, axis=axis
        ), None

    if isinstance(op, Stencil3D27):
        assert op.nx % n_shards == 0, (op.nx, n_shards)
        nxl = op.nx // n_shards
        return {}, lambda loc, axis: partial(
            _apply_3d27_local, nxl=nxl, ny=op.ny, nz=op.nz, centre=op.centre,
            axis=axis,
        ), None

    raise TypeError(f"no distributed implementation for {type(op).__name__}")


def _partition_prec(prec, op: LinearOperator, n_shards: int, perm=None):
    """As ``_partition_op`` for the preconditioner.  ``perm`` is the row
    ordering the operator partition imposed: pointwise preconditioners
    are permuted to match; block-structured ones cannot be re-blocked
    after the fact — pre-order the operator (``sparse.rcm_reorder``) and
    factor the preconditioner in that basis instead."""
    if prec is None or isinstance(prec, IdentityPrec):
        return {}, lambda loc, axis: (lambda x: x)
    if isinstance(prec, JacobiPrec):
        inv_diag = prec.inv_diag if perm is None \
            else prec.inv_diag[jnp.asarray(perm)]
        arrays = {"inv_diag": inv_diag}
        return arrays, lambda loc, axis: (
            lambda x: loc["inv_diag"].astype(x.dtype) * x
        )
    if perm is not None:
        raise TypeError(
            f"{type(prec).__name__} is block-structured and cannot follow "
            "the partitioner's RCM reordering; reorder the operator first "
            "(repro.linalg.sparse.rcm_reorder) and build the "
            "preconditioner from the ordered operator")
    if isinstance(prec, BlockJacobi):
        nb, bs, _ = prec.inv_blocks.shape
        assert (op.n // n_shards) % bs == 0, (
            "block-Jacobi blocks must be interior to a shard "
            f"(local size {op.n // n_shards}, block {bs})"
        )

        def build(loc, axis):
            def apply(x):
                inv = loc["inv_blocks"]
                nbl = inv.shape[0]
                y = jnp.einsum(
                    "nij,nj->ni", inv.astype(x.dtype), x.reshape(nbl, bs)
                )
                return y.reshape(-1)

            return apply

        return {"inv_blocks": prec.inv_blocks}, build
    raise TypeError(f"no distributed implementation for {type(prec).__name__}")


def _fused_spmv_local(op, loc, n_shards: int, axis: str):
    """Shard-level :class:`~repro.kernels.fused_iter.FusedSpmv` for the
    fused-iteration superkernel (DESIGN.md §13): the halo exchange stays
    OUTSIDE the kernel in ``prepare`` (one HALO_TAG'd ppermute per
    direction/hop, riding the open reduction windows exactly as the
    unfused path, DESIGN.md §12); the kernel then evaluates the same
    local stencil / ELL expression as the unfused shard apply, so row
    updates stay bitwise.  None when the operator has no fused path.
    """
    from repro.kernels import fused_iter as fi

    if isinstance(op, DiagonalOp):
        return fi.diagonal_spmv(loc["d"])
    if isinstance(op, SparseOp):
        if op.use_kernel:
            return None              # kernel-in-kernel: no fused mirror
        cols, vals = loc["cols"][0], loc["vals"][0]
        send_up, send_dn = loc["send_up"][0], loc["send_dn"][0]
        nxl = cols.shape[0]
        hops, max_send = send_up.shape

        def prep_sparse(z):
            return partition_mod.halo_exchange(z, send_up, send_dn, axis)

        return fi.ell_spmv(cols, vals, prep_sparse,
                           nxl + 2 * hops * max_send)
    if getattr(op, "use_kernel", False):
        return None
    if isinstance(op, Stencil2D5):
        nxl, ny = op.nx // n_shards, op.ny

        def prep2d(z):
            g = z.reshape(nxl, ny)
            up, dn = _halo_first_dim(g, axis)
            return jnp.concatenate([up, g, dn], axis=0).reshape(-1)

        def expr2d(zf):
            gp = zf.reshape(nxl + 2, ny)
            g = gp[1:-1]
            gy = jnp.pad(g, ((0, 0), (1, 1)))
            out = 4.0 * g - gp[:-2] - gp[2:] - gy[:, :-2] - gy[:, 2:]
            return out.reshape(-1)

        return fi.resident_spmv(expr2d, prep2d, (nxl + 2) * ny)
    if isinstance(op, Stencil3D7):
        nxl, ny, nz, eps_z = op.nx // n_shards, op.ny, op.nz, op.eps_z

        def prep3d(z):
            g = z.reshape(nxl, ny, nz)
            up, dn = _halo_first_dim(g, axis)
            return jnp.concatenate([up, g, dn], axis=0).reshape(-1)

        def expr3d(zf):
            gp = zf.reshape(nxl + 2, ny, nz)
            g = gp[1:-1]
            gy = jnp.pad(g, ((0, 0), (1, 1), (0, 0)))
            gz = jnp.pad(g, ((0, 0), (0, 0), (1, 1)))
            ez = jnp.asarray(eps_z, dtype=zf.dtype)
            out = (
                (4.0 + 2.0 * ez) * g
                - gp[:-2] - gp[2:]
                - gy[:, :-2, :] - gy[:, 2:, :]
                - ez * gz[:, :, :-2] - ez * gz[:, :, 2:]
            )
            return out.reshape(-1)

        return fi.resident_spmv(expr3d, prep3d, (nxl + 2) * ny * nz)
    return None


def _local_inverse_diagonal(prec, loc):
    """``inverse_diagonal`` of the shard's preconditioner: its slice of
    a Jacobi ``inv_diag`` (in ``loc``), 1.0 or None as globally."""
    diag = inverse_diagonal(prec)
    return diag if diag is None or isinstance(diag, float) \
        else loc["inv_diag"]


def _fused_factory_dist(op, prec, loc, n_shards: int, axis: str):
    """``SolverOps.fused_iter_factory`` for the shard_map substrate, or
    None for unsupported (operator, preconditioner) pairs."""
    from repro.kernels import fused_iter as fi
    from repro.kernels.ops import _interpret_default

    diag = _local_inverse_diagonal(prec, loc)
    if diag is None:
        return None                  # block solves are not pointwise
    inv_diag = None if isinstance(diag, float) else diag
    spmv = _fused_spmv_local(op, loc, n_shards, axis)
    if spmv is None:
        return None

    def factory(layout, interpret=None, block_n=None):
        interp = _interpret_default() if interpret is None else interpret
        return fi.build_fused_iteration(layout, spmv, inv_diag,
                                        block_n=block_n, interpret=interp)

    return factory


def partitioned_solver_ops(op, prec, n_shards: int, axis: str = "shards",
                           reduction=None):
    """(arrays, build, perm) for a full SolverOps: build(local_arrays,
    axis) must be called inside shard_map; dot_block is ONE fused psum
    over ``axis``.  ``perm`` (``perm[new] = old``, or None) is the row
    ordering the partition imposed — callers permute b on the way in and
    un-permute x on the way out (the solver runs entirely in the
    permuted basis; every scalar it derives is permutation-invariant).

    ``reduction`` (a :class:`repro.parallel.reduction.StagedConfig`, or
    None for the monolithic psum) swaps the dot-block combine for the
    staged ring ladder (DESIGN.md §14): the start site parks local
    partials in a gather buffer, the solver advances one REDUCE_TAG'd
    ``ppermute`` hop group per iteration, and the wait finishes the ring
    and reduces the partials in rank order — the compiled dot block then
    carries NO all-reduce at all (asserted in tests/test_distributed.py).
    """
    op_arrays, op_build, perm = _partition_op(op, n_shards)
    pr_arrays, pr_build = _partition_prec(prec, op, n_shards, perm)
    arrays = {"op": op_arrays, "prec": pr_arrays}

    def build(loc) -> SolverOps:
        from repro.parallel import reduction as reduction_mod

        apply_a = op_build(loc["op"], axis)
        prec_fn = pr_build(loc["prec"], axis)

        def dot_block(mat, vec):
            # (K5): all local contributions + ONE global reduction.
            # dot_block_rows (not mat @ vec) so local partials round
            # identically to the superkernel's VMEM accumulation and to
            # the vmapped slab path (types.dot_block_rows).
            return lax.psum(dot_block_rows(mat, vec), axis)

        # create() tags the issue/consume sites for the overlap tracer
        # (DESIGN.md §6) — monolithic: the psum above is the
        # MPI_Iallreduce payload and combine_partials its superkernel
        # half (ONE psum of the VMEM-accumulated local dot partials,
        # DESIGN.md §13); staged: the whole handle life cycle comes from
        # the ladder subsystem (same tagged sites, zero all-reduces).
        if reduction is None:
            staged_kw = dict(combine_partials=lambda p: lax.psum(p, axis))
        else:
            cfg = dataclasses.replace(reduction, n_shards=n_shards,
                                      axis=axis)
            staged_kw = reduction_mod.staged_ops_pieces(cfg)
        return SolverOps.create(
            apply_a=apply_a, prec=prec_fn, dot_block=dot_block,
            prec_diag=_local_inverse_diagonal(prec, loc["prec"]),
            fused_iter_factory=_fused_factory_dist(
                op, prec, {**loc["op"], **loc["prec"]}, n_shards, axis),
            **staged_kw,
        )

    return arrays, build, perm


def _permutation_wrappers(perm):
    """(pre, post) callables for a partition-imposed row ordering: ``pre``
    maps an (n,) or (n, s) operand into the permuted basis, ``post`` maps
    a SolveResult's solution back.  Identity pass-throughs for None."""
    if perm is None:
        return (lambda b: b), (lambda res: res)
    pj = jnp.asarray(perm)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(perm.size)
    ij = jnp.asarray(inv)

    def pre(b):
        return b[pj]

    def post(res: SolveResult) -> SolveResult:
        x = res.x
        # single-RHS x is (n,); batched results carry a leading s-axis.
        return res._replace(x=x[ij] if x.ndim == 1 else x[..., ij])

    return pre, post


# One dispatch table for every substrate (repro.core.METHODS).
_METHODS = METHODS


def state_specs(state_shapes, vec_axes, axis: str):
    """Partition specs for a (batched or single) solver state under
    ``shard_map``.

    ``state_shapes`` is the ``jax.eval_shape`` pytree of the state and
    ``vec_axes`` its ``repro.core.batched.vector_mask``: vector-valued
    leaves shard their domain-decomposed axis over ``axis``; windows,
    scalars and histories are replicated (they are derived from psum'd
    dot blocks, hence identical on every shard)."""
    def spec(sh, vec_axis):
        if not vec_axis:
            return P()
        dims = [None] * sh.ndim
        dims[sh.ndim + vec_axis] = axis
        return P(*dims)

    return jax.tree.map(spec, state_shapes, vec_axes)


def result_specs(axis: str, kw: dict) -> SolveResult:
    """Out-specs of a single-RHS SolveResult solved with kwargs ``kw``:
    x is domain-decomposed; everything else replicated.  The telemetry
    ring is replicated when instrumented (every recorded scalar is
    post-psum state) and absent otherwise, mirroring
    SolveResult.telemetry; the governor vector follows the same
    contract (DESIGN.md §16/§18)."""
    return SolveResult(
        x=P(axis), iters=P(), restarts=P(), converged=P(),
        res_history=P(), norm0=P(),
        telemetry=P() if kw.get("telemetry_cap", 0) else None,
        governor=P() if kw.get("governor") is not None else None)


def batched_result_specs(axis: str, telemetry: bool = False,
                         governor: bool = False) -> SolveResult:
    """Out-specs of a stacked (leading s-axis) SolveResult: x is (s, n)
    with n domain-decomposed; everything else replicated.  ``telemetry``
    mirrors whether the solve is instrumented (telemetry_cap > 0): the
    telemetry ring is replicated scalar state (P()), and None on plain
    solves — None is an empty pytree subtree, so both shapes of result
    match their spec (DESIGN.md §16).  ``governor`` mirrors whether the
    solve is governed (same contract: replicated scalar state when
    armed, absent otherwise — DESIGN.md §18)."""
    return SolveResult(x=P(None, axis), iters=P(), restarts=P(),
                       converged=P(), res_history=P(), norm0=P(),
                       telemetry=P() if telemetry else None,
                       governor=P() if governor else None)


def distributed_solve_batched(
    mesh: Mesh,
    op: LinearOperator,
    B: jax.Array,
    method: str = "plcg",
    prec=None,
    jit: bool = True,
    reduction=None,
    **kwargs,
):
    """Solve A X = B for all s columns of B (n, s) in lock-step, domain-
    decomposed over ``mesh`` — per iteration ONE fused psum of the whole
    (K, s) dot-block matrix (DESIGN.md §11), or its staged ring-ladder
    equivalent when ``reduction`` names a StagedConfig (DESIGN.md §14).
    Mirrors :func:`distributed_solve`; the result's leaves carry a
    leading s-axis.
    """
    axis = mesh.axis_names[0]
    n_shards = mesh.devices.size
    assert B.shape[0] % n_shards == 0
    arrays, build, perm = partitioned_solver_ops(op, prec, n_shards, axis,
                                                 reduction=reduction)
    pre, post = _permutation_wrappers(perm)

    def run(B_local, local_arrays):
        ops = build(local_arrays)
        return batched_mod.solve_batched(ops, B_local, method, **kwargs)

    arr_specs = jax.tree.map(lambda _: P(axis), arrays)
    inner = jax.shard_map(
        run, mesh=mesh, in_specs=(P(axis, None), arr_specs), check_vma=False,
        out_specs=batched_result_specs(
            axis, telemetry=bool(kwargs.get("telemetry_cap", 0)),
            governor=kwargs.get("governor") is not None),
    )

    def fn(B, arrays):
        return post(inner(pre(B), arrays))

    if not jit:
        return fn, arrays
    return jax.jit(fn)(B, arrays)


def distributed_checkpointed_solve(
    mesh: Mesh,
    op: LinearOperator,
    b: jax.Array,
    method: str = "plcg",
    prec=None,
    reduction=None,
    checkpoint=None,
    x0=None,
    pieces: bool = False,
    **kwargs,
):
    """Checkpointed solve on the shard_map substrate (DESIGN.md §19).

    The segmented driver of ``repro.checkpoint`` with every compiled
    piece shard_map-wrapped: ``seg`` runs the solver between interrupt
    boundaries (the in-loop arithmetic is the UNCHANGED program pieces,
    so histories stay bitwise vs the monolithic while-loop), ``gather``
    all-gathers the domain-decomposed vector leaves into fully
    replicated hosts arrays at each drained-ring boundary, and only
    process 0 writes.  The host evaluates ``cond``/``needs_interrupt``
    directly on the replicated scalar leaves — deterministic, so every
    process takes the same branch (SPMD-safe).  Snapshots store the
    state in the partition-imposed row ordering (``perm``); stencil and
    diagonal operators impose none, which is what makes their restores
    substrate-elastic.
    """
    from repro import checkpoint as ckpt_mod
    from repro.core.batched import BUILDERS
    from repro.parallel.reduction import oracle_solver_ops

    cfg = checkpoint
    axis = mesh.axis_names[0]
    n_shards = mesh.devices.size
    assert b.shape[0] % n_shards == 0
    arrays, build, perm = partitioned_solver_ops(op, prec, n_shards, axis,
                                                 reduction=reduction)
    pre, post = _permutation_wrappers(perm)
    kw = ckpt_mod.effective_kw(method, kwargs, cfg.every)
    b_p = pre(jnp.asarray(b))
    x0_p = jnp.zeros_like(b_p) if x0 is None else pre(x0.astype(b_p.dtype))

    # Host-side program: cond / needs_interrupt touch only replicated
    # scalar leaves, so a shape-oracle ops (never executed through a
    # collective) is sufficient; its state's leaf ranks give the specs.
    # The staged oracle shares the staged mesh's handle-ring structure
    # (DESIGN.md §14), so the eval_shape'd state tree matches.
    ops_shape = SolverOps.local(op, prec) if reduction is None else \
        oracle_solver_ops(op, prec, dataclasses.replace(
            reduction, n_shards=n_shards, axis=None))
    prog_host = BUILDERS[method](ops_shape, b_p, **kw)
    if prog_host.needs_interrupt is None or prog_host.interrupt is None:
        raise ckpt_mod.CheckpointError(
            f"method {method!r} exposes no interrupt boundary")
    st_shapes = jax.eval_shape(prog_host.init,
                               jax.ShapeDtypeStruct(b_p.shape, b_p.dtype))
    vec = batched_mod.vector_mask(method, kw)
    st_specs = state_specs(st_shapes, vec, axis)
    # The persisted form (``checkpoint.solve.persist_codec``) keeps
    # vectors flat: each shard's n_l entries on the trailing axis (the
    # eval_shape'd form serves for its leaves' ranks only).
    codec = ckpt_mod.solve.persist_codec(method, kw, b.shape[0] // n_shards)
    flat_shapes = jax.eval_shape(codec[0], st_shapes) if codec else st_shapes
    flat_vec = jax.tree.map(lambda v: -1 if v else 0, vec)
    flat_specs = state_specs(flat_shapes, flat_vec, axis)
    arr_specs = jax.tree.map(lambda _: P(axis), arrays)

    def _prog(bl, loc):
        return BUILDERS[method](build(loc), bl, **kw)

    def _init(bl, xl, loc):
        return _prog(bl, loc).init(xl)

    def _seg(bl, st, loc):
        p = _prog(bl, loc)
        return lax.while_loop(lambda t: p.cond(t) & ~p.needs_interrupt(t),
                              p.step, st)

    def _int(bl, st, loc):
        return _prog(bl, loc).interrupt(st)

    rel_fn = ckpt_mod.make_rel_fn(method, kw)

    def _rel(bl, st, loc):
        return rel_fn(build(loc), bl, st)

    def _encode(st):
        return codec[0](st) if codec else st

    def _decode(st):
        return codec[1](st) if codec else st

    def _gather(st):
        # Vector leaves -> fully replicated global arrays (tiled
        # all_gather of the persisted form's trailing n axis); everything
        # else is already replicated at a drained-ring boundary
        # (post-psum scalars) — EXCEPT the in-flight D ring, which the
        # checkpoint excludes.
        return jax.tree.map(
            lambda v, is_vec: lax.all_gather(v, axis, axis=v.ndim - 1,
                                             tiled=True) if is_vec else v,
            _encode(st), flat_vec)

    sm = partial(jax.shard_map, mesh=mesh, check_vma=False)
    init_j = jax.jit(sm(_init, in_specs=(P(axis), P(axis), arr_specs),
                        out_specs=st_specs))
    seg_j = jax.jit(sm(_seg, in_specs=(P(axis), st_specs, arr_specs),
                       out_specs=st_specs))
    int_j = jax.jit(sm(_int, in_specs=(P(axis), st_specs, arr_specs),
                       out_specs=st_specs))
    rel_j = jax.jit(sm(_rel, in_specs=(P(axis), st_specs, arr_specs),
                       out_specs=P()))
    gather_j = jax.jit(sm(_gather, in_specs=(st_specs,),
                          out_specs=jax.tree.map(lambda _: P(), st_shapes)))
    fin_j = jax.jit(sm(lambda bl, st, loc: _prog(bl, loc).finish(st),
                       in_specs=(P(axis), st_specs, arr_specs),
                       out_specs=result_specs(axis, kw)))
    encode_j = jax.jit(sm(_encode, in_specs=(st_specs,),
                          out_specs=flat_specs))
    decode_j = jax.jit(sm(_decode, in_specs=(flat_specs,),
                          out_specs=st_specs))

    st = init_j(b_p, x0_p, arrays)
    if pieces:
        # Structural introspection for tests: the EXACT jitted pieces
        # the segmented driver runs (lowerable for HLO assertions —
        # e.g. "the seg piece keeps one pipelined reduction start per
        # iteration"), plus the initial state to lower them against.
        return {"init": init_j, "seg": seg_j, "interrupt": int_j,
                "rel": rel_j, "gather": gather_j, "encode": encode_j,
                "decode": decode_j, "state": st,
                "b_p": b_p, "arrays": arrays, "prog_host": prog_host}
    mask = ckpt_mod.solve.exclude_mask(method, st)
    meta_base = ckpt_mod.solve.solver_meta(method, b.shape[0], b.dtype, kw,
                                           cfg.every)
    meta_base["treedef"] = ckpt_mod.solve.state_treedef_str(st)
    rel_of = lambda s: rel_j(b_p, s, arrays)
    if cfg.resume:
        st = ckpt_mod.solve.try_restore(st, cfg, meta_base, mask, rel_of,
                                        codec=(encode_j, decode_j))
    snapshot = ckpt_mod.solve.make_snapshot_fn(
        cfg, meta_base, mask, method, rel_of, gather=gather_j,
        is_writer=jax.process_index() == 0)
    st = ckpt_mod.run_segmented(
        st, cond=prog_host.cond, needs=prog_host.needs_interrupt,
        seg=lambda s: seg_j(b_p, s, arrays), method=method,
        interrupt=lambda s: int_j(b_p, s, arrays), cfg=cfg,
        snapshot=snapshot)
    return post(fin_j(b_p, st, arrays))


def distributed_solve(
    mesh: Mesh,
    op: LinearOperator,
    b: jax.Array,
    method: str = "plcg",
    prec=None,
    jit: bool = True,
    reduction=None,
    **kwargs,
):
    """Solve A x = b with the chosen CG variant, domain-decomposed over
    ``mesh`` (1-D).  Returns (callable_or_result, lowered-compatible fn).

    ``kwargs`` are forwarded to the solver (l, tol, maxit, sigmas, unroll...).
    ``reduction`` (StagedConfig | None) selects the staged ring ladder
    for the dot block (DESIGN.md §14).
    """
    axis = mesh.axis_names[0]
    n_shards = mesh.devices.size
    assert b.shape[0] % n_shards == 0
    arrays, build, perm = partitioned_solver_ops(op, prec, n_shards, axis,
                                                 reduction=reduction)
    pre, post = _permutation_wrappers(perm)

    def run(b_local, local_arrays):
        ops = build(local_arrays)
        return _METHODS[method](ops, b_local, kwargs)

    arr_specs = jax.tree.map(lambda _: P(axis), arrays)
    inner = jax.shard_map(
        run, mesh=mesh, in_specs=(P(axis), arr_specs),
        out_specs=result_specs(axis, kwargs), check_vma=False)

    def fn(b, arrays):
        return post(inner(pre(b), arrays))

    if not jit:
        return fn, arrays
    jfn = jax.jit(fn)
    return jfn(b, arrays)
