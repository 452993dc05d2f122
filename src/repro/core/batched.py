"""Batched multi-RHS solvers — the amortized-reduction layer (DESIGN.md §11).

The paper hides the latency of the per-iteration global reduction behind
local work; a solver *service* additionally amortizes it: solving s
right-hand sides against the same operator in lock-step turns the fused
2l+1-entry dot block into ONE (2l+1, s) payload reduced in a single
allreduce — s× the work per reduction latency without any extra
synchronization, the same lever as deepening the pipeline (Cornelis/
Cools/Vanroose, arXiv:1801.04728).

Mechanically this module is a thin, principled layer over the per-column
programs exposed by the three solvers (``classic_cg.build``,
``ghysels_pcg.build``, ``pipelined_cg.build``): each column runs the
UNMODIFIED per-column arithmetic and ``jax.vmap`` over the s-axis does the
batching —

* every ``ops.start`` dot block picks up a trailing batch dimension, so
  the backend's single ``lax.psum`` becomes a single psum of the full
  (2l+1, s) matrix payload (verified against the compiled HLO by
  ``repro.utils.trace.batched_plcg_overlap_report``);
* ``lax.while_loop``'s batching rule applies per-column conds as selects
  on the carry, so a column whose cond goes false is **bitwise frozen**
  while its neighbours keep iterating — this IS masked retirement, by
  construction rather than by bespoke masking code
  (tests/test_serve.py::test_retired_column_bitwise_frozen).

The staged ring reduction (DESIGN.md §14) batches the same way: the
per-column program's ``ops.advance`` ladder hops vmap into ONE
``ppermute`` per hop carrying the whole (P, 2l+1, s) gather buffer, and
the D-ring slots widen to the staged handle shape transparently — so
the amortization claim (one logical reduction per iteration, payload
wide, handle count 1) holds verbatim in staged mode, asserted on
compiled HLO by ``trace.batched_plcg_overlap_report``'s
``staged_starts_per_window``.

One vmap caveat shapes the loop structure: a batched ``lax.cond`` lowers
to select-with-both-branches, so the sequential drivers' in-loop
restart/replacement cond would execute its extra SPMV + reduction EVERY
slab iteration.  The batched drivers therefore run the program's bare
``step`` (one reduction) and pause a column at ``needs_interrupt``
(breakdown, due residual replacement); the ``interrupt`` (cycle re-init
/ vector replacement) is applied as a masked segment-boundary step —
same per-column arithmetic and restart schedule as the sequential path,
with the interrupt's reduction amortized to boundaries (asserted on
compiled HLO in tests/test_distributed.py: no computation carries more
than one all-reduce).

Two entry points:

``solve_batched(ops, B, method, **kw)``
    run every column to completion; returns a ``SolveResult`` whose
    leaves carry a leading s-axis (x: (s, n), res_history: (s, H), ...).
    Zero columns have norm0 == 0 and retire at iteration 0 — padding a
    partial slab with zeros is exact, not approximate.

``column_kernels`` / ``batched_init`` / ``batched_chunk`` / ...
    the chunked serving interface: init / chunk / inject / status /
    extract pieces over an explicit slab state, stepped ``chunk_iters``
    iterations at a time so the service layer (``repro.serve``) can
    retire converged columns and recycle their slots between chunks
    without recompiling.  Backends wrap these in their SPMD context
    (``make_slab_program`` -> :class:`SlabProgram`).
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import classic_cg, ghysels_pcg, pipelined_cg
from repro.core.types import SolveResult, SolverOps

# Per-column program builders — the batched layer shares THE solver
# arithmetic with the sequential path (same dispatch keys as
# repro.core.METHODS), so batched-vs-sequential residual histories agree
# bitwise per backend (tests/test_serve.py).
BUILDERS: dict[str, Callable] = {
    "cg": classic_cg.build,
    "pcg": ghysels_pcg.build,
    "plcg": pipelined_cg.build,
}


def vector_mask(method: str, kw: dict | None = None):
    """Pytree (matching the method's state) of ints: for a leaf that
    holds vectors, the (negative) axis of the domain-decomposed vector
    axis n; 0 for everything else.

    Distributed backends use this to build shard_map partition specs for
    the slab state (vector leaves sharded on that axis, everything else
    — windows, scalars, histories — replicated).  Vectors are a leaf's
    trailing axis, except in the p(l)-CG slab with (R, 128) tiled rows
    (``kernels.fused_iter.SlabLayout``), which shards its R axis; the
    solver kwargs ``kw`` say which row storage the solve uses.
    """
    if method == "cg":
        return classic_cg.CgState(
            x=-1, r=-1, u=-1, p=-1,
            gamma=0, it=0, conv=0, hist=0)
    if method == "pcg":
        return ghysels_pcg.PcgState(
            S=-1, gamma=0, alpha=0, it=0, conv=0,
            hist=0, since_rr=0)
    if method == "plcg":
        from repro.kernels.fused_iter import SlabLayout

        fused = bool((kw or {}).get("fused_iteration", False))
        rows = SlabLayout.for_solver(1, 1, fused=fused).row_shape
        cyc = pipelined_cg._Cycle(
            S=-len(rows), G=0, D=0, gam=0, dlt=0,
            eta_prev=0, zet_prev=0, i=0, norm0_cycle=0)
        return pipelined_cg._State(
            cyc=cyc, tot=0, upd=0, restarts=0, converged=0,
            breakdown=0, hist=0, norm0=0, since_rr=0, tel=0, gov=0)
    raise KeyError(method)


class SlabStatus(NamedTuple):
    """Cheap per-chunk slab view (everything replicated / O(s))."""

    running: jax.Array      # (s,) bool — column's loop cond still true
    converged: jax.Array    # (s,) bool
    iters: jax.Array        # (s,) solution updates so far


class ColumnKernels(NamedTuple):
    """Per-column (unbatched) slab pieces; backends vmap + stage these."""

    init: Callable[[jax.Array], Any]                    # bcol -> st
    chunk: Callable[[jax.Array, Any], Any]              # (bcol, st) -> st
    status: Callable[[jax.Array, Any], SlabStatus]
    extract: Callable[[jax.Array, Any], SolveResult]


def _masked_interrupt(p, st):
    """Apply the program's interrupt (restart / residual replacement) as
    a per-column masked boundary step: the interrupt computation runs
    once and a select keeps it only where due.  Under vmap this costs ONE
    extra reduction per boundary — never per iteration — which is why the
    batched drivers run ``step`` (bare iteration) instead of ``body``
    (whose lax.cond would lower to select-both-branches per iteration)."""
    if p.needs_interrupt is None:
        return st
    due = p.needs_interrupt(st)
    fresh = p.interrupt(st)
    return jax.tree.map(lambda f, o: jnp.where(due, f, o), fresh, st)


def _col_cond(p):
    """Per-column loop cond for batched drivers: a column pauses at an
    interrupt boundary (breakdown / due replacement) instead of running
    the interrupt in-loop."""
    if p.needs_interrupt is None:
        return p.cond
    return lambda st: p.cond(st) & ~p.needs_interrupt(st)


def column_kernels(
    ops: SolverOps, method: str, kw: dict, chunk_iters: int
) -> ColumnKernels:
    """Build the per-column program pieces for one (method, kwargs) pair.

    Every piece takes the column's RHS ``bcol`` explicitly (the solver
    builders close over b), so the serve layer can swap a slot's RHS at
    inject time and the very same compiled computation serves the new
    request.
    """
    assert chunk_iters >= 1

    def prog(bcol):
        return BUILDERS[method](ops, bcol, **kw)

    def init(bcol):
        p = prog(bcol)
        return p.init(jnp.zeros_like(bcol))

    def chunk(bcol, st):
        p = prog(bcol)
        inner_cond = _col_cond(p)

        def cond(carry):
            st, j = carry
            return inner_cond(st) & (j < chunk_iters)

        def body(carry):
            st, j = carry
            return p.step(st), j + 1

        st, _ = jax.lax.while_loop(cond, body, (st, jnp.int32(0)))
        # Boundary interrupts: a column that paused mid-chunk (breakdown,
        # due replacement) restarts here and resumes next chunk.
        return _masked_interrupt(p, st)

    def status(bcol, st):
        p = prog(bcol)
        res = p.finish(st)
        return SlabStatus(running=p.cond(st), converged=res.converged,
                          iters=res.iters)

    def extract(bcol, st):
        return prog(bcol).finish(st)

    return ColumnKernels(init=init, chunk=chunk, status=status,
                         extract=extract)


# --------------------------------------------------------------------------
# Batched (vmapped) forms.  B is (n, s) column-major-by-request; states and
# results carry a LEADING s-axis (vmap out_axes=0).
# --------------------------------------------------------------------------

def _select_columns(mask: jax.Array, new, old):
    """Per-column pytree select: leaf[i] <- new[i] where mask[i]."""

    def sel(f, o):
        m = mask.reshape(mask.shape + (1,) * (f.ndim - 1))
        return jnp.where(m, f, o)

    return jax.tree.map(sel, new, old)


def batched_init(ops, B, method: str, kw: dict, chunk_iters: int = 1):
    ck = column_kernels(ops, method, kw, chunk_iters)
    return jax.vmap(ck.init, in_axes=1)(B)


def batched_chunk(ops, B, st, method: str, kw: dict, chunk_iters: int):
    ck = column_kernels(ops, method, kw, chunk_iters)
    return jax.vmap(ck.chunk, in_axes=(1, 0))(B, st)


def batched_inject(ops, B, st, refresh, method: str, kw: dict,
                   chunk_iters: int = 1):
    """Re-initialize the columns flagged in ``refresh`` (s,) from the
    CURRENT columns of B, leaving every other column bitwise untouched —
    the slot-recycling primitive (retired slot -> fresh request)."""
    fresh = batched_init(ops, B, method, kw, chunk_iters)
    return _select_columns(refresh, fresh, st)


def batched_status(ops, B, st, method: str, kw: dict,
                   chunk_iters: int = 1) -> SlabStatus:
    ck = column_kernels(ops, method, kw, chunk_iters)
    return jax.vmap(ck.status, in_axes=(1, 0))(B, st)


def batched_extract(ops, B, st, method: str, kw: dict,
                    chunk_iters: int = 1) -> SolveResult:
    ck = column_kernels(ops, method, kw, chunk_iters)
    return jax.vmap(ck.extract, in_axes=(1, 0))(B, st)


def solve_batched(ops: SolverOps, B: jax.Array, method: str = "plcg",
                  **kw) -> SolveResult:
    """Solve A X = B for all s columns of B (n, s) in lock-step.

    Per-iteration communication: ONE fused reduction of the full
    (K, s) dot-block matrix (K = 2l+1 for p(l)-CG), whatever s is.
    Leaves of the result carry a leading s-axis.  Column i reproduces
    the sequential ``METHODS[method](ops, B[:, i], kw)`` result exactly
    (converged columns are frozen by the while-loop batching rule while
    the rest run on).
    """
    kw = dict(kw)
    kw.pop("unroll", None)          # window unrolling is a solve()-driver knob

    def col(bcol):
        p = BUILDERS[method](ops, bcol, **kw)
        st = p.init(jnp.zeros_like(bcol))
        if p.needs_interrupt is None:
            return p.finish(jax.lax.while_loop(p.cond, p.body, st))
        # Interrupt-aware methods: bare steps in the inner loop (ONE
        # reduction per slab iteration under vmap), interrupts applied
        # masked between segments.  Outer rounds advance every column by
        # at least one segment, so termination mirrors the sequential
        # restart budget.
        inner_cond = _col_cond(p)

        def outer(st):
            st = jax.lax.while_loop(inner_cond, p.step, st)
            return _masked_interrupt(p, st)

        return p.finish(jax.lax.while_loop(p.cond, outer, st))

    return jax.vmap(col, in_axes=1)(B)


# --------------------------------------------------------------------------
# Multi-slab step hooks (DESIGN.md §15).  The continuous-batching
# scheduler (repro.serve.scheduler) runs SEVERAL slabs per tick; these
# helpers keep the cross-slab concerns — dispatch overlap and
# slot-utilization accounting — next to the slab machinery they measure.
# --------------------------------------------------------------------------

def dispatch_slab_chunks(slabs) -> list:
    """Issue the chunk computation of EVERY slab before synchronizing on
    any of them.

    ``slabs`` yields ``(program, B_dev, state)`` triples; returns the new
    states in order.  jax dispatch is asynchronous, so enqueueing all
    chunks back-to-back lets XLA overlap independent slabs on the device
    stream — the scheduler ticks in three phases (pack all / chunk all /
    poll all) precisely so no slab's host-side status read serializes its
    neighbours' device work.  Each slab still reduces its own dot block
    as ONE (K, s) handle per iteration; running slabs concurrently
    multiplies slabs, never handles per slab (asserted on compiled HLO in
    tests/test_serve_replay.py).
    """
    return [prog.chunk(B, st) for prog, B, st in slabs]


def slab_slot_iterations(iters_before, iters_after) -> int:
    """Occupied-slot-iterations advanced between two status polls.

    ``SlabStatus.iters`` counts solution updates per column, so the
    element-wise delta across a chunk is exactly the number of
    iterations each slot spent doing useful work: free/zero-padded slots
    and bitwise-frozen converged columns contribute 0.  Summed against a
    capacity of ``s * chunk_iters`` per chunk this yields the slab
    slot-utilization metric the continuous-batching scheduler reports
    (occupied-slot-iterations / total slot-iterations) — the quantity
    that decays as a slab drains and that mid-flight injection keeps
    high (gated in BENCH_serve.json).
    """
    return int(np.sum(np.asarray(iters_after) - np.asarray(iters_before)))


class SlabProgram(NamedTuple):
    """Compiled slab-solver handles (built once per slab shape by a
    reduction backend's ``make_slab_program``; DESIGN.md §11).

    All callables are jit-compiled with fixed shapes (n, s) — the serve
    lifecycle (init -> [chunk -> retire -> inject]* -> extract) never
    retraces, whatever mix of requests flows through the slots.
    """

    method: str
    s: int
    n: int
    chunk_iters: int
    init: Callable[[jax.Array], Any]                      # B -> state
    chunk: Callable[[jax.Array, Any], Any]                # (B, st) -> st
    inject: Callable[[jax.Array, Any, jax.Array], Any]    # (B, st, mask) -> st
    status: Callable[[jax.Array, Any], SlabStatus]
    extract: Callable[[jax.Array, Any], SolveResult]
