"""``shard_map`` reduction backend — the paper's MPI layout on one process
(DESIGN.md §2/§3).

Domain decomposition over a 1-D "shards" mesh: halo exchange via
``lax.ppermute``, communication-free preconditioner, and ALL inner
products of an iteration fused into ONE ``lax.psum`` — the single
MPI_Iallreduce of the G-column.  This ports the original
``repro.parallel.distributed`` path onto the backend interface; the heavy
lifting (operator partitioning, halo kernels) stays in that module.

Example (8 simulated hosts — set ``XLA_FLAGS=--xla_force_host_platform_
device_count=8`` before importing jax)::

    from repro.parallel import get_backend
    be = get_backend("shard_map", n_shards=8)
    res = be.solve(op, b, method="plcg", l=2, sigmas=sig, tol=1e-8)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import batched as batched_mod
from repro.core.batched import SlabProgram, SlabStatus
from repro.core.types import SolverOps
from repro.parallel.distributed import (
    _permutation_wrappers,
    batched_result_specs,
    distributed_solve,
    distributed_solve_batched,
    make_solver_mesh,
    partitioned_solver_ops,
    state_specs,
)
from repro.parallel.backends.base import ReductionBackend
from repro.parallel.reduction import (StagedConfig, oracle_solver_ops,
                                      resolve_backend_reduction)


class ShardMapBackend(ReductionBackend):
    name = "shard_map"

    def __init__(self, mesh: Mesh | None = None, n_shards: int | None = None,
                 jit: bool = True, reduction: str = "monolithic",
                 reduction_stages: int = 2, reduction_dtype=None):
        """``reduction="staged"`` swaps the dot block's monolithic psum
        for the hop-per-iteration ring ladder (DESIGN.md §14):
        ``reduction_stages`` advance steps spread the P-1 allgather hops
        over the solver's in-flight window, and ``reduction_dtype``
        (e.g. jnp.float32) narrows the wire payload with fp64
        compensated accumulation at the wait."""
        self.mesh = mesh if mesh is not None else make_solver_mesh(n_shards)
        self.axis = self.mesh.axis_names[0]
        self.n_shards = self.mesh.devices.size
        self.jit = jit
        self.reduction_cfg = self._resolve_reduction(
            reduction, reduction_stages, reduction_dtype)

    def _resolve_reduction(self, reduction: str, stages: int,
                           dtype) -> StagedConfig | None:
        # One shared policy (validation, stage clamp, capability
        # fallback) for every backend — see reduction.py.
        return resolve_backend_reduction(self, reduction, stages, dtype,
                                         self.n_shards, self.axis)

    # ------------------------------------------------------------ solve --
    def solve(self, op, b, method: str = "plcg", prec=None, **solver_kwargs):
        ckpt = solver_kwargs.pop("checkpoint", None)
        if ckpt is not None and getattr(ckpt, "armed", False):
            # Host-segmented checkpointing driver (DESIGN.md §19) — the
            # pieces jit+shard_map themselves; no outer jit here.
            from repro.parallel.distributed import \
                distributed_checkpointed_solve
            return distributed_checkpointed_solve(
                self.mesh, op, b, method=method, prec=prec,
                reduction=self.reduction_cfg, checkpoint=ckpt,
                **solver_kwargs)
        return distributed_solve(self.mesh, op, b, method=method, prec=prec,
                                 jit=self.jit, reduction=self.reduction_cfg,
                                 **solver_kwargs)

    def make_solver(self, op, method: str = "plcg", prec=None,
                    **solver_kwargs):
        # jit=False hands back (shard_map fn, partitioned arrays); one
        # jit wrapper around the pair is the reusable compiled solver.
        # distributed_solve only reads b's shape on this path.
        bspec = jax.ShapeDtypeStruct((op.n,), jnp.float32)
        fn, arrays = distributed_solve(self.mesh, op, bspec, method=method,
                                       prec=prec, jit=False,
                                       reduction=self.reduction_cfg,
                                       **solver_kwargs)
        jfn = jax.jit(fn)
        return lambda bb: jfn(bb, arrays)

    # -------------------------------------------------- batched multi-RHS --
    def solve_batched(self, op, B, method: str = "plcg", prec=None,
                      **solver_kwargs):
        return distributed_solve_batched(self.mesh, op, B, method=method,
                                         prec=prec, jit=self.jit,
                                         reduction=self.reduction_cfg,
                                         **solver_kwargs)

    def make_batched_solver(self, op, method: str = "plcg", prec=None,
                            **solver_kwargs):
        bspec = jax.ShapeDtypeStruct((op.n, 1), jnp.float32)
        fn, arrays = distributed_solve_batched(
            self.mesh, op, bspec, method=method, prec=prec, jit=False,
            reduction=self.reduction_cfg, **solver_kwargs)
        jfn = jax.jit(fn)
        return lambda BB: jfn(BB, arrays)

    def make_slab_program(self, op, s: int, method: str = "plcg", prec=None,
                          chunk_iters: int = 16, dtype=None,
                          **solver_kwargs) -> SlabProgram:
        """Slab lifecycle under shard_map (DESIGN.md §11).

        Each piece is one shard_map-wrapped jit: the slab B (n, s) is
        domain-decomposed on n, the state's vector leaves shard their
        vector axis (``state_specs``), and per-column scalars /
        histories are replicated.  The state crosses the host boundary
        between chunks so the serve layer can retire and inject columns —
        with fixed shapes throughout, nothing ever retraces.
        """
        kw = dict(solver_kwargs)
        dtype = jnp.zeros((), jnp.float64).dtype if dtype is None else dtype
        n, axis = op.n, self.axis
        arrays, build, perm = partitioned_solver_ops(
            op, prec, self.n_shards, axis, reduction=self.reduction_cfg)
        pre, post = _permutation_wrappers(perm)
        arr_specs = jax.tree.map(lambda _: P(axis), arrays)
        b_spec = P(axis, None)

        # State structure/ndims are substrate-independent: eval_shape the
        # batched init against plain local ops to derive partition specs.
        # Staged mode must mirror the widened D-ring handle shapes, so
        # the shape oracle is the eager ladder with the same config.
        if self.reduction_cfg is None:
            ops_shape = SolverOps.local(op, prec)
        else:
            ops_shape = oracle_solver_ops(
                op, prec, dataclasses.replace(self.reduction_cfg, axis=None))
        st_struct = jax.eval_shape(
            lambda BB: batched_mod.batched_init(ops_shape, BB, method, kw),
            jax.ShapeDtypeStruct((n, s), dtype))
        st_specs = state_specs(st_struct, batched_mod.vector_mask(method, kw),
                               axis)
        status_specs = SlabStatus(running=P(), converged=P(), iters=P())

        def staged(fn, in_specs, out_specs, donate=()):
            wrapped = jax.shard_map(fn, mesh=self.mesh,
                                    in_specs=in_specs,
                                    out_specs=out_specs, check_vma=False)
            # donate=(1,) on chunk/inject: the slab state is consumed and
            # replaced every call, so its sharded buffers alias through
            # the jit boundary instead of copying (DESIGN.md §13).
            return jax.jit(wrapped, donate_argnums=donate)

        init_j = staged(
            lambda Bl, loc: batched_mod.batched_init(build(loc), Bl, method,
                                                     kw),
            (b_spec, arr_specs), st_specs)
        chunk_j = staged(
            lambda Bl, st, loc: batched_mod.batched_chunk(
                build(loc), Bl, st, method, kw, chunk_iters),
            (b_spec, st_specs, arr_specs), st_specs, donate=(1,))
        inject_j = staged(
            lambda Bl, st, mask, loc: batched_mod.batched_inject(
                build(loc), Bl, st, mask, method, kw),
            (b_spec, st_specs, P(), arr_specs), st_specs, donate=(1,))
        status_j = staged(
            lambda Bl, st, loc: batched_mod.batched_status(build(loc), Bl,
                                                           st, method, kw),
            (b_spec, st_specs, arr_specs), status_specs)
        extract_j = staged(
            lambda Bl, st, loc: batched_mod.batched_extract(build(loc), Bl,
                                                            st, method, kw),
            (b_spec, st_specs, arr_specs),
            batched_result_specs(
                axis, telemetry=bool(kw.get("telemetry_cap", 0)),
                governor=kw.get("governor") is not None))

        # The slab B crosses into the solver's (possibly RCM-permuted)
        # basis on every entry point and the extracted solutions map back
        # on the way out; the state itself lives permuted throughout.
        return SlabProgram(
            method=method, s=s, n=n, chunk_iters=chunk_iters,
            init=lambda B: init_j(pre(B), arrays),
            chunk=lambda B, st: chunk_j(pre(B), st, arrays),
            inject=lambda B, st, mask: inject_j(pre(B), st, mask, arrays),
            status=lambda B, st: status_j(pre(B), st, arrays),
            extract=lambda B, st: post(extract_j(pre(B), st, arrays)),
        )

    # ----------------------------------------------------- SPMD staging --
    def _staged(self, fn: Callable[[SolverOps, jax.Array], Any], op, prec,
                b_spec=None):
        """(wrapped_fn, arrays): shard_map-wrapped ``fn`` with replicated
        outputs, plus the partitioned operator arrays to pass alongside.

        ``fn`` sees the solver's basis: for an RCM-partitioned SparseOp
        the local shard of ``b`` is in permuted order — irrelevant for
        schedule tracing (the staging use case), which often passes a
        ShapeDtypeStruct anyway."""
        arrays, build, _perm = partitioned_solver_ops(
            op, prec, self.n_shards, self.axis,
            reduction=self.reduction_cfg)

        def run(b_local, loc):
            return fn(build(loc), b_local)

        b_spec = P(self.axis) if b_spec is None else b_spec
        arr_specs = jax.tree.map(lambda _: P(self.axis), arrays)
        wrapped = jax.shard_map(
            run, mesh=self.mesh, in_specs=(b_spec, arr_specs),
            out_specs=P(), check_vma=False)
        return wrapped, arrays

    def run(self, fn, op, b, prec=None, b_spec=None) -> Any:
        wrapped, arrays = self._staged(fn, op, prec, b_spec)
        return jax.jit(wrapped)(b, arrays)

    def lower_hlo(self, fn, op, b, prec=None, b_spec=None) -> str:
        wrapped, arrays = self._staged(fn, op, prec, b_spec)
        bsh = NamedSharding(
            self.mesh, P(self.axis) if b_spec is None else b_spec)
        ash = jax.tree.map(lambda _: NamedSharding(self.mesh, P(self.axis)),
                           arrays)
        lowered = jax.jit(wrapped, in_shardings=(bsh, ash)).lower(b, arrays)
        return lowered.compile().as_text()

    def describe(self) -> str:
        if self.reduction_cfg is not None:
            cfg = self.reduction_cfg
            wire = "solver-dtype" if cfg.payload_dtype is None else str(
                jnp.dtype(cfg.payload_dtype))
            return (f"shard_map over {self.n_shards} device(s), axis "
                    f"'{self.axis}' (staged ring dot block: "
                    f"{cfg.n_hops} hops / {cfg.stages} stage(s), "
                    f"{wire} wire)")
        return (f"shard_map over {self.n_shards} device(s), "
                f"axis '{self.axis}' (fused psum dot block)")
