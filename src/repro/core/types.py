"""Common solver interfaces.

``SolverOps`` abstracts what a Krylov solver needs from the execution
substrate, so the *same* solver code runs single-device or under
``shard_map`` on a production mesh (DESIGN.md §3):

  apply_a    A @ x          (distributed: halo exchange + local stencil)
  prec       M^{-1} x       (distributed: communication-free block solve)
  dot_block  (K,N)@(N,)->(K,)  ALL inner products of one iteration fused
             into ONE global reduction — this is the paper's single
             ``MPI_Iallreduce`` of the G-column (distributed: one psum).

On top of the fused block, the reduction is exposed as an *async-friendly
handle pair* — the paper's MPI_Iallreduce / MPI_Wait split:

  start(mat, vec) -> dots   initiate the fused reduction.  The returned
                            array is a lazy handle: nothing forces its
                            completion until a consumer reads it.
  wait(dots)      -> dots   declare the consumption point.  Backends tag
                            both sites with named scopes (GLRED_START_TAG /
                            GLRED_WAIT_TAG) so the overlap tracer
                            (``repro.utils.trace``, DESIGN.md §6) can
                            recover the staggered in-flight chains from the
                            compiled HLO schedule, and insert an
                            ``optimization_barrier`` so XLA cannot collapse
                            the issue→consume window.

The solvers never call more than one ``dot_block`` per iteration (p-CG,
p(l)-CG) or two (classic CG) — exactly the reduction counts of Table 1.
``SolverOps`` instances are normally built by a reduction backend
(``repro.parallel.backends.get_backend``); ``SolverOps.local`` remains the
single-device shortcut used by tests and examples.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

# Named-scope tags attached by ``SolverOps.create`` at the reduction issue
# and consumption sites.  They flow into HLO instruction metadata
# (op_name), which is how the overlap tracer identifies the chains after
# XLA optimization — see DESIGN.md §6.
GLRED_START_TAG = "glred_start"
GLRED_WAIT_TAG = "glred_wait"

# Scope tag on the point-to-point halo exchange (``lax.ppermute``) of the
# distributed SpMV — both the structured stencil halo and the unstructured
# send/recv-set exchange (``repro.linalg.partition``).  The overlap tracer
# uses it to verify the paper's second staggering claim: neighbour
# communication rides INSIDE the in-flight reduction windows
# (DESIGN.md §6/§12).
HALO_TAG = "halo_xchg"

# Scope tag prefix on the staged ring-reduction ladder hops
# (``repro.parallel.reduction``, DESIGN.md §14): hop k of a staged dot
# block is one ``lax.ppermute`` inside a ``f"{REDUCE_TAG}{k}"`` scope.
# The overlap tracer counts these per iteration window and checks they
# interleave with HALO_TAG traffic inside the open reduction windows —
# the hop/halo staggering invariant.
REDUCE_TAG = "glred_hop"

# Phase scopes of the p(l)-CG solve (``repro.core.pipelined_cg``).  The
# innermost of them on an instruction's ``metadata op_name`` is its phase
# (the loop body's interrupt conditional is control and nests the phase
# of the branch it takes).  A profiler trace names the instructions it
# ran, so joining trace event names to the compiled program's op_names
# gives device time per phase on the trace's own clock (DESIGN.md §16).
SCALAR_TAG = "plcg_scalar"      # arrival + K2/K3, K6 scalars, idx/scal packing
VECTOR_TAG = "plcg_vector"      # SPMV, fills, K4, dot block, x/p, reduction start
CONTROL_TAG = "plcg_control"    # history, governor arms, telemetry, loop tests
RESTART_TAG = "plcg_restart"    # breakdown restart / residual replacement
INIT_TAG = "plcg_init"          # first cycle set-up and the result
PHASE_TAGS = (SCALAR_TAG, VECTOR_TAG, CONTROL_TAG, RESTART_TAG, INIT_TAG)


def dot_block_rows(mat: jax.Array, vec: jax.Array) -> jax.Array:
    """The fused dot block (K, N) x (N,) -> (K,) as an elementwise
    product + trailing-axis reduction instead of ``mat @ vec``.

    Semantically identical; chosen because it is bitwise-REPRODUCIBLE
    across every execution shape this repo runs the block in: a vmapped
    ``dot_general`` (the batched multi-RHS slab) and the Pallas
    interpreter's per-grid-step dot (the fused superkernel off-TPU) hit
    different gemm kernels whose reduction order differs at the ULP
    level, while a trailing-axis reduce lowers to the same per-row chain
    everywhere.  Every substrate's ``dot_block`` and the superkernel's
    in-VMEM partials use THIS expression, which is what makes
    fused/unfused and batched/sequential residual histories bitwise
    comparable (DESIGN.md §13; tests/test_fused_iter.py).
    """
    return (mat * vec[None, :]).sum(axis=1)


class SolveResult(NamedTuple):
    x: jax.Array           # approximate solution
    iters: jax.Array       # number of solution updates (CG-comparable count)
    restarts: jax.Array    # breakdown restarts performed (p(l)-CG only)
    converged: jax.Array   # bool
    res_history: jax.Array # recursive residual M-norms, -1 padded
    norm0: jax.Array       # initial residual M-norm
    # On-device iteration telemetry ring (cap, K), or None when the solve
    # was not instrumented (telemetry_cap=0, the default).  Row layout is
    # ``repro.kernels.fused_iter.tel_layout``; ``TelemetrySlab.unpack``
    # decodes it.  None is an EMPTY pytree subtree, so uninstrumented
    # results keep their pre-telemetry pytree structure — shard_map
    # out_specs, vmap axes and donation contracts are unchanged
    # (DESIGN.md §16).
    telemetry: jax.Array | None = None
    # Final stability-governor state vector (repro.stability.GOV_SLOTS) or
    # None when the solve ran ungoverned (governor=None, the default).
    # Same empty-subtree contract as ``telemetry``: ungoverned results
    # keep the pre-governor pytree structure (DESIGN.md §18).
    governor: jax.Array | None = None


@dataclasses.dataclass(frozen=True)
class TelemetrySlab:
    """Descriptor of the per-iteration telemetry ring (DESIGN.md §16).

    The instrumented p(l)-CG solve appends a small ``(cap, K)`` ring to
    its donated state: one row per iteration holding the already-computed
    per-iteration scalars (residual norm, the arrived 2l+1-entry dot
    block, restart/replacement flags, hop-group age).  Every recorded
    value is replicated scalar state on distributed substrates — the ring
    adds ZERO collectives and ZERO host syncs; it is drained only where
    the state already crosses the host boundary (solve end / chunk
    boundaries).  ``cap`` rows wrap: row ``tot % cap`` belongs to global
    iteration ``tot`` (the "iter" column disambiguates after wrap).
    """

    cap: int
    l: int

    @property
    def k(self) -> int:
        from repro.kernels.fused_iter import tel_layout

        return tel_layout(self.l)["size"]

    @property
    def shape(self) -> tuple[int, int]:
        return (self.cap, self.k)

    def bytes_per_iter(self, dtype=jnp.float64) -> int:
        """HBM bytes the ring write adds per iteration (one K-row store
        + the ring-index arithmetic) — the overhead-accounting input of
        the instrumented-replay gate (DESIGN.md §16)."""
        return self.k * jnp.dtype(dtype).itemsize

    def unpack(self, tel) -> dict:
        """Decode a telemetry ring (…, cap, K) into named columns.

        Returns a dict of (…, cap) arrays for the scalar columns plus
        ``dots`` of shape (…, cap, 2l+1).  Rows never written (ring not
        yet full) carry the -1.0 fill in every column.
        """
        from repro.kernels.fused_iter import tel_layout

        tl = tel_layout(self.l)
        out = {name: tel[..., :, tl[name]]
               for name in ("iter", "upd", "rnorm", "age", "breakdown",
                            "restart", "replacement", "gap", "action")}
        out["dots"] = tel[..., :, tl["dots"]:tl["size"]]
        return out


@dataclasses.dataclass(frozen=True)
class SolverOps:
    apply_a: Callable[[jax.Array], jax.Array]
    prec: Callable[[jax.Array], jax.Array]
    dot_block: Callable[[jax.Array, jax.Array], jax.Array]
    # Async reduction-handle pair.  None means "derive from dot_block":
    # start falls back to a plain (synchronous) dot_block and wait to the
    # identity, which keeps hand-rolled SolverOps (benchmarks/table1.py)
    # working unchanged.
    dot_block_start: Callable[[jax.Array, jax.Array], jax.Array] | None = None
    dot_block_wait: Callable[..., jax.Array] | None = None
    # Staged-reduction extension (repro.parallel.reduction, DESIGN.md
    # §14).  ``dot_block_advance(handle, step)`` runs ONE ladder step of
    # an in-flight reduction — the solvers call it once per iteration per
    # outstanding handle, which is what spreads the reduction's latency
    # structurally over min(l, stages) iterations instead of leaving the
    # overlap to XLA's scheduler.  None (monolithic substrates) makes
    # ``advance`` the identity.  ``dot_block_handle_zeros(shape, dtype)``
    # builds the zero in-flight handle for a dot block of the given
    # payload shape — staged substrates return a (P, K[, s]) wire-dtype
    # gather buffer; None keeps the plain (K[, s]) payload array.
    dot_block_advance: Callable[[jax.Array, int], jax.Array] | None = None
    dot_block_handle_zeros: Callable[..., jax.Array] | None = None
    # Global combine of LOCALLY accumulated dot-block partials — the
    # reduction half of the fused-iteration superkernel path
    # (DESIGN.md §13).  The megakernel computes each shard's (2l+1)
    # partial dots in VMEM during its single pass over the basis slab;
    # ``start_partials`` then issues the same single global reduction as
    # ``start`` would (one psum on distributed substrates, a tagged
    # barrier locally) without re-reading any basis vector from HBM.
    combine_partials: Callable[[jax.Array], jax.Array] | None = None
    # Factory for the fused-iteration superkernel: called by
    # ``pipelined_cg.build(..., fused_iteration=True)`` with the solver's
    # :class:`repro.kernels.fused_iter.SlabLayout`; returns the
    # per-iteration vector-phase callable (slab, idx, scal) ->
    # (new slab, local dot partials).  None means the substrate/operator
    # combination has no fused path (the solver raises).
    fused_iter_factory: Callable[..., Callable] | None = None

    def start(self, mat: jax.Array, vec: jax.Array) -> jax.Array:
        """Initiate the fused dot block (the MPI_Iallreduce)."""
        if self.dot_block_start is None:
            return self.dot_block(mat, vec)
        return self.dot_block_start(mat, vec)

    def advance(self, handle: jax.Array, step: int) -> jax.Array:
        """Run ladder step ``step`` of an in-flight reduction handle —
        the hop-per-iteration progress call of the staged subsystem
        (DESIGN.md §14).  ``step`` is static (the handle's pipeline age
        minus one); monolithic substrates are already complete at issue,
        so the default is the identity."""
        if self.dot_block_advance is None:
            return handle
        return self.dot_block_advance(handle, step)

    def handle_zeros(self, shape: tuple, dtype) -> jax.Array:
        """Zero in-flight handle for a dot block with payload ``shape``
        — what a p(l)-CG D-ring slot holds before its first start.
        Staged substrates widen this to their (P, K[, s]) wire-dtype
        gather buffer."""
        if self.dot_block_handle_zeros is None:
            return jnp.zeros(shape, dtype)
        return self.dot_block_handle_zeros(shape, dtype)

    def start_partials(self, partials: jax.Array) -> jax.Array:
        """Initiate the global combine of locally-accumulated dot-block
        partials (the fused-iteration analogue of :meth:`start`): ONE
        reduction carrying the same 2l+1-entry payload, issued at the
        same tagged site so the overlap tracer sees an identical chain
        structure (DESIGN.md §6/§13)."""
        with jax.named_scope(GLRED_START_TAG):
            if self.combine_partials is None:
                # Single-device: nothing to combine, but the barrier (a)
                # marks the issue site for the tracer and (b) keeps XLA
                # from folding the handle into its consumer.
                return jax.lax.optimization_barrier(partials)
            return self.combine_partials(partials)

    def wait(self, dots: jax.Array, advanced: int = 0) -> jax.Array:
        """Consumption point of a previously started block (MPI_Wait).

        ``advanced`` (static) is how many ladder steps the solver already
        ran on this handle via :meth:`advance` — p(l)-CG passes l-1, a
        blocking start+wait passes 0; staged substrates finish the
        remaining steps here, monolithic ones ignore it."""
        if self.dot_block_wait is None:
            return dots
        return self.dot_block_wait(dots, advanced=advanced)

    @staticmethod
    def create(
        apply_a: Callable[[jax.Array], jax.Array],
        prec: Callable[[jax.Array], jax.Array],
        dot_block: Callable[[jax.Array, jax.Array], jax.Array],
        combine_partials: Callable[[jax.Array], jax.Array] | None = None,
        fused_iter_factory: Callable[..., Callable] | None = None,
        dot_block_start: Callable[[jax.Array, jax.Array], jax.Array] | None = None,
        dot_block_wait: Callable[..., jax.Array] | None = None,
        dot_block_advance: Callable[[jax.Array, int], jax.Array] | None = None,
        handle_zeros: Callable[..., jax.Array] | None = None,
    ) -> "SolverOps":
        """Build SolverOps with tracer-tagged start/wait around dot_block.

        Every reduction backend funnels through here so the issue and
        consumption sites of each reduction carry GLRED_START_TAG /
        GLRED_WAIT_TAG scopes in the lowered HLO (DESIGN.md §6).
        ``combine_partials``/``fused_iter_factory`` wire the
        fused-iteration superkernel path (DESIGN.md §13) where the
        substrate supports it.  Staged substrates override the whole
        handle life cycle (``dot_block_start`` / ``dot_block_advance`` /
        ``dot_block_wait`` / ``handle_zeros``,
        ``repro.parallel.reduction.staged_ops_pieces``); the overrides
        are wrapped in the same tracer scopes as the monolithic pair.
        """

        if dot_block_start is None:
            def dot_block_start(mat, vec):  # noqa: F811 - default impl
                return dot_block(mat, vec)

        def start(mat, vec, _start=dot_block_start):
            with jax.named_scope(GLRED_START_TAG):
                return _start(mat, vec)

        if dot_block_wait is None:
            def dot_block_wait(dots, advanced=0):  # noqa: F811
                return dots

        def wait(dots, advanced=0, _wait=dot_block_wait):
            with jax.named_scope(GLRED_WAIT_TAG):
                return jax.lax.optimization_barrier(
                    _wait(dots, advanced=advanced))

        return SolverOps(
            apply_a=apply_a,
            prec=prec,
            dot_block=dot_block,
            dot_block_start=start,
            dot_block_wait=wait,
            dot_block_advance=dot_block_advance,
            dot_block_handle_zeros=handle_zeros,
            combine_partials=combine_partials,
            fused_iter_factory=fused_iter_factory,
        )

    @staticmethod
    def local(op, prec=None) -> "SolverOps":
        """Single-device ops (tests, small problems)."""
        from repro.kernels.ops import fused_iteration_factory

        pfun = (lambda v: v) if prec is None else (lambda v: prec.apply(v))
        return SolverOps.create(
            apply_a=lambda v: op.apply(v),
            prec=pfun,
            dot_block=dot_block_rows,
            fused_iter_factory=fused_iteration_factory(op, prec),
        )


def dot1(ops: SolverOps, a: jax.Array, b: jax.Array) -> jax.Array:
    """Single global dot through the fused-block path, started and
    immediately waited — a blocking reduction (classic CG's
    synchronization point).  The result is normalized to the operand
    dtype: a staged substrate may accumulate a narrowed wire payload in
    a wider dtype (DESIGN.md §14)."""
    return ops.wait(ops.start(a[None, :], b))[0].astype(a.dtype)
