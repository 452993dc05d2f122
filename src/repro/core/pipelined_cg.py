"""Deep pipelined Conjugate Gradients — p(l)-CG (Alg. 1 of the paper).

Faithful JAX implementation with production storage: ALL vector state —
the l+1 auxiliary bases Z^(0..l) in ring buffers (window max(l+1,3) per
basis), the 3-deep u ring, the search direction p and the iterate x —
lives in ONE contiguous structure-of-arrays slab ``S`` of shape (NV, N)
(:class:`repro.kernels.fused_iter.SlabLayout`), total vector storage O(l)
irrespective of iteration count (cf. the paper's 4l+1-vector budget,
Table 1).  One array with one trailing N axis is what the fused-iteration
superkernel tiles (DESIGN.md §13), what ``donate_argnums`` aliases across
slab-program chunks, and what the G matrix / Hessenberg windows ride
alongside as O(l^2) scalars.

The communication structure per iteration i is exactly the paper's:

  * ONE SPMV (+ preconditioner)                                (K1)
  * ONE fused dot-product block of 2l+1 entries — the single
    MPI_Iallreduce of G(i-2l+1:i+1, i+1)                       (K5)
  * its result is FIRST READ at iteration i+l (lines 8-10)     (MPI_Wait)

The reduction is issued through the backend handle API
(``ops.start``, DESIGN.md §3) and its raw 2l+1-entry payload parked in an
explicit in-flight ring ``D`` of depth l — the JAX analogue of the paper's
l outstanding ``MPI_Request`` objects.  Only at iteration i+l is the slot
consumed (``ops.wait``) and scattered into the G window, so the reduction
initiated at iteration i has l iterations of SPMVs, AXPYs and l-1 other
in-flight reductions between initiation and first use.  On TPU the overlap
is realized by XLA's latency-hiding scheduler when the iteration window is
unrolled (``unroll`` parameter; see DESIGN.md §2) — the lowered HLO then
carries l independent all-reduce chains in flight, the staggering of
Fig. 4 (bottom), which ``repro.utils.trace`` measures (DESIGN.md §6).

Each iteration is split into a *scalar phase* (MPI_Wait arrival scatter
into G, the K2 column correction and K3 Hessenberg column — O(l^2)
scalars) and a *vector phase* (K1 SPMV + preconditioner, pipeline-fill
copies, K4 recurrence AXPYs, the K5 dot block and the K6 x/p updates).
The vector phase has two interchangeable implementations sharing one
index/coefficient calling convention:

  * **unfused** (default) — ``repro.kernels.ref.fused_iter_unfused``:
    one jnp op per pass, dots via ``ops.start`` (the reference path);
  * **fused** (``fused_iteration=True``) — the Pallas superkernel
    (``repro.kernels.fused_iter``): slab read once / written once per
    row tile, dot partials accumulated in VMEM, the single global
    reduction issued on the partials via ``ops.start_partials``.  Both
    paths evaluate identical expressions on identical operands, so
    stencil-operator residual histories agree BITWISE
    (tests/test_fused_iter.py; DESIGN.md §13).

Breakdown handling: square-root breakdown (line 10/11) triggers an explicit
restart from the current iterate (§2.2), implemented as a state re-init
inside the while-loop.  Convergence uses the recursive residual M-norm
|zeta_{i-l}| relative to the *original* residual norm.

Residual replacement (``replace_every > 0``): long pipelines let the
recursive residual drift from the true residual (rounding-error
propagation, Cools/Cornelis/Vanroose arXiv:1902.03100), capping attainable
accuracy.  Every ``replace_every`` solution updates the solver forces a
cycle re-init from the current iterate — ``init_cycle`` recomputes the
TRUE residual b - A x and restarts the basis from it, the p(l)-CG
equivalent of the paper's periodic true-residual recompute (the
counterpart for Ghysels p-CG replaces the recurred vectors in place; see
``ghysels_pcg``).  Each replacement costs the l+1-iteration pipeline
refill, so choose ``replace_every >> l``; replacement restarts share the
breakdown-restart budget ``max_restarts``
(tests/test_residual_replacement.py).
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.types import (CONTROL_TAG, GLRED_WAIT_TAG, INIT_TAG,
                              RESTART_TAG, SCALAR_TAG, VECTOR_TAG,
                              SolveResult, SolverOps, dot1)
from repro.kernels.fused_iter import (SlabLayout, idx_layout, scal_layout,
                                      tel_layout)
from repro.kernels.ref import fused_iter_unfused
from repro.stability import model as gov_model


class _Cycle(NamedTuple):
    """Per-restart-cycle state (re-initialized on breakdown)."""

    S: jax.Array        # (NV, N) vector slab: ZK rings | U ring | p | x
    G: jax.Array        # (W, W) sliding window of the basis-transform matrix
    D: jax.Array        # (l, *handle) in-flight dot blocks (reduction
                        # handles: the raw (2l+1,) payload on monolithic
                        # substrates, a (P, 2l+1) wire-dtype gather buffer
                        # on staged ones — ops.handle_zeros decides)
    gam: jax.Array      # (W,) gamma ring  (Hessenberg diagonal)
    dlt: jax.Array      # (W,) delta ring  (Hessenberg off-diagonal)
    eta_prev: jax.Array # scalar eta_{i-l-1}
    zet_prev: jax.Array # scalar zeta_{i-l-1}
    i: jax.Array        # cycle-local iteration counter
    norm0_cycle: jax.Array


class _State(NamedTuple):
    cyc: _Cycle
    tot: jax.Array        # global iteration counter (monotone — termination)
    upd: jax.Array        # number of solution updates (CG-comparable iters)
    restarts: jax.Array
    converged: jax.Array
    breakdown: jax.Array
    hist: jax.Array
    norm0: jax.Array      # original residual M-norm (stopping reference)
    since_rr: jax.Array   # solution updates since the last (re)start —
                          # drives periodic residual replacement
    tel: jax.Array        # (telemetry_cap, K) on-device telemetry ring
                          # (row layout: kernels.fused_iter.tel_layout;
                          # (0, K) when uninstrumented — writes are
                          # statically skipped, DESIGN.md §16)
    gov: jax.Array        # (gov_model.N_SLOTS,) stability-governor state
                          # (repro.stability.model; zeros and statically
                          # untouched when ungoverned, DESIGN.md §18)


class PlcgProgram(NamedTuple):
    """The p(l)-CG iteration decomposed for external drivers.

    ``solve`` runs ``body`` under ``lax.while_loop``; the overlap tracer
    (``repro.utils.trace``) instead unrolls ``iteration`` into a flat
    window so the staggered reduction chains are visible in one HLO
    schedule (DESIGN.md §6).

    ``step`` / ``needs_interrupt`` / ``interrupt`` decompose ``body`` for
    the batched multi-RHS drivers (DESIGN.md §11): under vmap a batched
    ``lax.cond`` executes BOTH branches, so running ``body`` per slab
    iteration would pay the restart's extra SPMV + reduction every
    iteration.  Batched drivers instead run ``step`` (the bare iteration,
    ONE reduction) until ``needs_interrupt`` (breakdown or a due residual
    replacement) stops the column, then apply ``interrupt`` (the cycle
    re-init) as a masked boundary step — same arithmetic per column as
    the sequential path, with the restart's reduction amortized to chunk
    boundaries.  ``step`` mutates only the slab's touched rows (in place
    under the fused path's ``input_output_aliases``), so the slab-program
    drivers that jit it with ``donate_argnums`` carry NO per-iteration
    state copy (tests/test_fused_iter.py::test_slab_program_donation).
    """

    init: Callable[[jax.Array], "_State"]        # x0 -> st0
    iteration: Callable[..., "_State"]           # raw iteration (no restart)
    body: Callable[["_State"], "_State"]         # breakdown-aware step
    cond: Callable[["_State"], jax.Array]
    finish: Callable[["_State"], SolveResult]
    step: Callable[["_State"], "_State"] | None = None
    needs_interrupt: Callable[["_State"], jax.Array] | None = None
    interrupt: Callable[["_State"], "_State"] | None = None


def build(
    ops: SolverOps,
    b: jax.Array,
    l: int,
    tol: float = 1e-6,
    maxit: int = 1000,
    sigmas: jax.Array | None = None,
    max_restarts: int = 10,
    replace_every: int = 0,
    fused_iteration: bool = False,
    telemetry_cap: int = 0,
    recurrence: str = "ghysels",
    governor: "gov_model.GovernorConfig | None" = None,
) -> PlcgProgram:
    """Construct the p(l)-CG iteration pieces for ``b`` (depth ``l`` static).

    ``fused_iteration=True`` routes the vector phase through the Pallas
    superkernel built by the substrate's ``ops.fused_iter_factory``
    (DESIGN.md §13); raises if the (operator, preconditioner, backend)
    combination has no fused path.

    ``telemetry_cap > 0`` appends a (cap, K) on-device telemetry ring to
    the solver state (DESIGN.md §16): each iteration stores one row of
    already-computed replicated scalars (residual norm, the arrived dot
    block, restart/replacement flags, handle age) at ring slot
    ``tot % cap`` — zero extra collectives, zero host syncs, and the
    uninstrumented arithmetic is untouched (instrumented-vs-plain residual
    histories are bitwise identical, tests/test_telemetry.py).  The ring
    is returned as ``SolveResult.telemetry``.

    ``recurrence`` selects the vector-phase basis recurrence
    (:class:`~repro.kernels.fused_iter.SlabLayout`): ``"ghysels"`` (the
    paper's formulation, the default) or ``"stable"`` (the coupled
    variant of arXiv:1902.03100, DESIGN.md §18).  Both run the identical
    one-reduction-per-iteration communication structure.

    ``governor`` (a :class:`repro.stability.model.GovernorConfig`) arms
    the stability governor (DESIGN.md §18): each late iteration updates
    a first-order attainable-accuracy gap estimate from the already
    replicated scalar phase (zero extra reductions) and, when the gap
    or a patience stall trips, schedules a residual replacement through
    the SAME interrupt machinery as breakdowns — per-column masked in
    the batched drivers.  Replacements that keep failing to improve the
    true residual flip the terminal STAGNATED flag, which stops the
    loop early (``repro.stability.governor`` turns it into pipeline
    demotion / :class:`~repro.stability.governor.StagnationError`).
    ``None`` (the default) statically skips every governor computation:
    ungoverned solves are bitwise identical to the pre-governor solver
    (tests/test_stability.py).
    """
    assert l >= 1
    assert telemetry_cap >= 0
    assert replace_every == 0 or replace_every > l, \
        "residual replacement must be rarer than the pipeline refill"
    n = b.shape[0]
    dtype = b.dtype
    sig = jnp.zeros((l,), dtype) if sigmas is None else jnp.asarray(sigmas, dtype)
    assert sig.shape == (l,)
    if recurrence not in ("ghysels", "stable"):
        raise ValueError(
            f"unknown recurrence {recurrence!r}: expected 'ghysels' "
            f"(paper Alg. 1) or 'stable' (coupled recurrence, "
            f"DESIGN.md §18)")

    RB = max(l + 1, 3)        # per-basis ring length
    W = 3 * l + 4             # G / Hessenberg window
    tot_max = maxit + (max_restarts + 1) * (l + 1)
    H = tot_max + 2

    layout = SlabLayout(l=l, RB=RB, recurrence=recurrence)
    NV = layout.nv
    IX = idx_layout(l)
    IS = scal_layout(l)
    TL = tel_layout(l)
    TK = TL["size"]

    fiter = None
    if fused_iteration:
        if ops.fused_iter_factory is None:
            raise ValueError(
                "fused_iteration=True but this SolverOps has no "
                "fused_iter_factory — unsupported operator/preconditioner "
                "for the superkernel (DESIGN.md §13)")
        fiter = ops.fused_iter_factory(layout)

    # ----------------------------------------------------------- helpers --
    def g_get(G, r, c, valid=True):
        v = G[jnp.mod(r, W), jnp.mod(c, W)]
        return jnp.where(valid, v, jnp.zeros((), dtype))

    def g_set(G, r, c, val):
        return G.at[jnp.mod(r, W), jnp.mod(c, W)].set(val)

    def ring_get(arr, idx, valid=True):  # 1-D scalar rings (gam / dlt)
        return jnp.where(valid, arr[jnp.mod(idx, W)], jnp.zeros((), dtype))

    zk_row, u_row = layout.zk_row, layout.u_row

    def tel_write(tel, tot, **cols):
        """Store one telemetry row at ring slot ``tot % cap``.

        Statically a no-op when uninstrumented (telemetry_cap == 0 is a
        Python-level check — the plain solve's HLO is unchanged).  Every
        value passed in is an already-computed replicated scalar, so the
        write is one K-wide row store: no collectives, no host syncs
        (DESIGN.md §16; invariants asserted in tests/test_telemetry.py).
        """
        if not telemetry_cap:
            return tel
        row = jnp.zeros((TK,), dtype)
        for name, val in cols.items():
            if name == "dots":
                row = row.at[TL["dots"]:TL["size"]].set(val.astype(dtype))
            else:
                row = row.at[TL[name]].set(
                    jnp.asarray(val).astype(dtype))
        return jax.lax.dynamic_update_slice(
            tel, row[None, :],
            (jnp.mod(tot, telemetry_cap), jnp.int32(0)))

    # ------------------------------------------------------------- init ---
    def _make_cycle(x, u0_raw, r0_raw, eta0) -> _Cycle:
        safe = jnp.where(eta0 == 0, jnp.ones((), dtype), eta0)
        v0 = r0_raw / safe
        S = jnp.zeros((NV, n), dtype)
        for k in range(l + 1):
            S = S.at[k * RB].set(v0)          # z_0^(k) = v_0 for all k
        S = S.at[layout.u_off].set(u0_raw / safe)
        S = S.at[layout.x_row].set(x)
        h0 = ops.handle_zeros((2 * l + 1,), dtype)
        return _Cycle(
            S=S, G=jnp.zeros((W, W), dtype).at[0, 0].set(1.0),
            D=jnp.zeros((l,) + h0.shape, h0.dtype),
            gam=jnp.zeros((W,), dtype), dlt=jnp.zeros((W,), dtype),
            eta_prev=jnp.ones((), dtype), zet_prev=jnp.zeros((), dtype),
            i=jnp.int32(0), norm0_cycle=eta0,
        )

    def init_cycle(x) -> _Cycle:
        u0_raw = b - ops.apply_a(x)
        r0_raw = ops.prec(u0_raw)
        eta0 = jnp.sqrt(jnp.abs(dot1(ops, u0_raw, r0_raw)))
        return _make_cycle(x, u0_raw, r0_raw, eta0)

    def restart_cycle(x, stagnant) -> _Cycle:
        """Cycle re-init for breakdown restarts, with a stagnation guard.

        A square-root breakdown at the FIRST late iteration (i == l,
        before any solution update) restarts into the identical cycle —
        on operator/preconditioner pairs whose preconditioned Krylov
        space is (nearly) one-dimensional (e.g. Jacobi on a diagonal
        operator: M^{-1}A = I) that loop never makes progress and burns
        the whole restart budget.  When the dying cycle produced NO
        updates (``stagnant``), fold ONE steepest-descent step into the
        re-init: x' = x + alpha z with alpha = (r, z)/(z, A z) — a
        guaranteed A-norm error reduction, and in the 1-D-Krylov case
        the exact solution, which the lucky-breakdown check then
        detects.  Everything is arranged as a SINGLE fused reduction
        (the restart's communication structure is unchanged — asserted
        on compiled HLO in tests/test_distributed.py), and a
        non-stagnant restart (alpha = 0) reproduces ``init_cycle``'s
        arithmetic bitwise: the post-step residual/eta0 recurrences
        collapse to the plain expressions when alpha == 0.
        """
        r = b - ops.apply_a(x)
        z = ops.prec(r)
        az = ops.apply_a(z)
        pz = ops.prec(az)
        # One fused reduction of the three inner products {(r,z), (az,z),
        # (az,pz)} as row-sums against ones — same payload discipline as
        # the iteration's dot block.
        dots = ops.wait(ops.start(
            jnp.stack([r * z, az * z, az * pz]),
            jnp.ones_like(z))).astype(dtype)
        a, c, e = dots[0], dots[1], dots[2]
        ok = stagnant & (c > 0) & jnp.isfinite(c)
        alpha = jnp.where(ok, a / jnp.where(c == 0, jnp.ones((), dtype), c),
                          jnp.zeros((), dtype))
        x1 = x + alpha * z
        u0_raw = r - alpha * az
        r0_raw = z - alpha * pz               # prec is linear
        # eta0^2 = (u0, r0) via the step recurrence ((r,pz) = (z,az) by
        # M^{-1}-symmetry); alpha = 0 collapses it to (r, z) exactly.
        eta0 = jnp.sqrt(jnp.abs(a - 2 * alpha * c + alpha * alpha * e))
        return _make_cycle(x1, u0_raw, r0_raw, eta0)

    # -------------------------------------------------------- iteration ---
    def iteration(st: _State, static_phase: str | None = None) -> _State:
        """One p(l)-CG iteration.

        ``static_phase`` ('early' | 'late' | None) lets flat drivers (the
        overlap tracer) bypass the ``lax.cond`` on i >= l with a
        trace-time choice, so the arrival path is inlined in the HLO
        entry computation.  ``None`` (the while-loop path) keeps the
        runtime conditional.
        """
        c = st.cyc
        i = c.i
        with jax.named_scope(SCALAR_TAG):
            im = i - l                 # index of the Hessenberg column built
            ge_l = i >= l

            # ===== scalar phase: MPI_Wait arrival + K2 + K3 ===================
            # O(l^2) scalar work on the G / Hessenberg windows — no vector
            # traffic; produces the coefficients the vector phase consumes.
            def late_scal(args):
                G, gam, dlt = args
                col = i - l + 1            # G column whose dots arrived

                # ---- MPI_Wait(req(i-l)): consume the in-flight dot block -----
                # The raw 2l+1 payload initiated l iterations ago is pulled out
                # of the D ring and scattered into G column `col` only NOW —
                # the consumption point the overlap tracer keys on (GLRED_WAIT
                # scope; DESIGN.md §6).
                with jax.named_scope(GLRED_WAIT_TAG):
                    # advanced=l-1: the solver ran one ladder step per
                    # iteration on this handle (ages 1..l-1, below); a staged
                    # substrate finishes any remaining steps here, monolithic
                    # ones ignore the count (DESIGN.md §14).
                    # .astype(dtype): staged substrates may accumulate the
                    # payload wider than the solver dtype (fp32 wire + fp64
                    # compensated wait, DESIGN.md §14) — normalize so the
                    # scalar recurrences keep the solver's dtype (no-op on
                    # monolithic substrates).
                    arrived = ops.wait(jax.lax.dynamic_index_in_dim(
                        c.D, jnp.mod(im, l), axis=0, keepdims=False),
                        advanced=l - 1).astype(dtype)
                    for t in range(2 * l + 1):         # rows im-2l+1 .. im+1
                        row = im - 2 * l + 1 + t
                        rv = row >= 0
                        G = g_set(G, row, col,
                                  jnp.where(rv, arrived[t], g_get(G, row, col)))

                # ---- (K2) lines 9-10: correct column `col` -------------------
                for t in range(l - 1):     # j = i-2l+2 .. i-l   (sequential in j)
                    j = i - 2 * l + 2 + t
                    jv = j >= 0
                    ssum = jnp.zeros((), dtype)
                    for s in range(l + 1 + t):          # k = i-3l+1+s  (<= j-1)
                        k_ = i - 3 * l + 1 + s
                        kv = (k_ >= 0) & jv
                        ssum += g_get(G, k_, j, kv) * g_get(G, k_, col, kv)
                    denom = jnp.where(jv, g_get(G, j, j, jv), jnp.ones((), dtype))
                    denom = jnp.where(denom == 0, jnp.ones((), dtype), denom)
                    val = (g_get(G, j, col, jv) - ssum) / denom
                    G = g_set(G, j, col, jnp.where(jv, val, g_get(G, j, col, jv)))

                ssum = jnp.zeros((), dtype)
                for s in range(2 * l):                   # k = i-3l+1 .. i-l
                    k_ = i - 3 * l + 1 + s
                    kv = k_ >= 0
                    ssum += jnp.square(g_get(G, k_, col, kv))
                arg = g_get(G, col, col) - ssum
                breakdown = (arg <= 0) | ~jnp.isfinite(arg)       # line 11
                sq = jnp.sqrt(jnp.where(breakdown, jnp.ones((), dtype), arg))
                G = g_set(G, col, col, sq)

                # ---- (K3) lines 12-18: new Hessenberg column -----------------
                g_mm = g_get(G, im, im)
                g_mm_safe = jnp.where(g_mm == 0, jnp.ones((), dtype), g_mm)
                g_mp = g_get(G, im, im + 1)
                g_prev = g_get(G, im - 1, im, im >= 1)
                d_prev = ring_get(dlt, im - 1, im >= 1)
                sig_im = sig[jnp.clip(im, 0, l - 1)]
                gam_early = (g_mp + sig_im * g_mm - g_prev * d_prev) / g_mm_safe
                dlt_early = sq / g_mm_safe
                gam_late = (
                    g_mm * ring_get(gam, im - l) + g_mp * ring_get(dlt, im - l)
                    - g_prev * d_prev
                ) / g_mm_safe
                dlt_late = sq * ring_get(dlt, im - l) / g_mm_safe
                early = i < 2 * l
                gam_new = jnp.where(early, gam_early, gam_late)
                dlt_new = jnp.where(early, dlt_early, dlt_late)
                gam = gam.at[jnp.mod(im, W)].set(gam_new)
                dlt = dlt.at[jnp.mod(im, W)].set(dlt_new)
                dlt_safe = jnp.where(dlt_new == 0, jnp.ones((), dtype), dlt_new)
                # ``arrived`` rides along for the telemetry row (the consumed
                # dot block is replicated scalar state) — unused and DCE'd
                # when uninstrumented.
                return (G, gam, dlt, gam_new, dlt_safe, arrived), breakdown

            def early_scal(args):
                G, gam, dlt = args
                return (G, gam, dlt, jnp.zeros((), dtype), jnp.ones((), dtype),
                        jnp.zeros((2 * l + 1,), dtype)), jnp.asarray(False)

            scal_args = (c.G, c.gam, c.dlt)
            if static_phase is None:
                (G, gam, dlt, gam_new, dlt_safe, arrived), breakdown = \
                    jax.lax.cond(ge_l, late_scal, early_scal, scal_args)
            elif static_phase == "late":
                (G, gam, dlt, gam_new, dlt_safe, arrived), breakdown = \
                    late_scal(scal_args)
            else:
                (G, gam, dlt, gam_new, dlt_safe, arrived), breakdown = \
                    early_scal(scal_args)

            d2 = ring_get(dlt, im - 1, im >= 1)       # delta_{i-l-1}

            # ---- (K6) scalar updates (lines 24-32, D-Lanczos factors) --------
            gam0 = ring_get(gam, jnp.int32(0))
            gam_im = ring_get(gam, im, ge_l)
            d_prev = ring_get(dlt, im - 1, im >= 1)
            is_first = i == l
            eta0_safe = jnp.where(gam0 == 0, jnp.ones((), dtype), gam0)
            do_upd = i >= l + 1
            eta_prev_safe = jnp.where(c.eta_prev == 0, jnp.ones((), dtype),
                                      c.eta_prev)
            lam = d_prev / eta_prev_safe
            eta_new = gam_im - lam * d_prev
            eta_new_safe = jnp.where(eta_new == 0, jnp.ones((), dtype), eta_new)
            zet_new = -lam * c.zet_prev

            # ===== vector phase ===============================================
            # Ring-row indices + coefficients for the one-pass calling
            # convention shared by the unfused reference and the superkernel
            # (repro.kernels.fused_iter; DESIGN.md §13).
            sig_i = jnp.where(i < l, sig[jnp.clip(i, 0, l - 1)],
                              jnp.zeros((), dtype))
            idx = jnp.zeros((IX["size"],), jnp.int32)
            for k in range(l):
                idx = idx.at[IX["fill"] + k].set(zk_row(k, i + 1))
                idx = idx.at[IX["rec_w"] + k].set(zk_row(k, i - l + k + 1))
                idx = idx.at[IX["rec_a"] + k].set(zk_row(k + 1, i - l + k + 1))
                idx = idx.at[IX["rec_b"] + k].set(zk_row(k, i - l + k))
                idx = idx.at[IX["rec_c"] + k].set(zk_row(k, i - l + k - 1))
                idx = idx.at[IX["f_fill"] + k].set(
                    ((i < l - 1) & (k >= i + 1)).astype(jnp.int32))
                idx = idx.at[IX["mat_v"] + k].set(zk_row(0, i - 2 * l + 1 + k))
            for t in range(l - 1):
                idx = idx.at[IX["mat_z"] + t].set(zk_row(l, i - l + 2 + t))
            idx = idx.at[IX["z_top"]].set(zk_row(l, i))
            idx = idx.at[IX["zl_im1"]].set(zk_row(l, i - 1))
            idx = idx.at[IX["z_w"]].set(zk_row(l, i + 1))
            idx = idx.at[IX["u_i"]].set(u_row(i))
            idx = idx.at[IX["u_im1"]].set(u_row(i - 1))
            idx = idx.at[IX["u_w"]].set(u_row(i + 1))
            idx = idx.at[IX["p_im"]].set(zk_row(0, im))
            idx = idx.at[IX["f_late"]].set(ge_l.astype(jnp.int32))
            idx = idx.at[IX["f_first"]].set(is_first.astype(jnp.int32))
            idx = idx.at[IX["f_upd"]].set(do_upd.astype(jnp.int32))

            scal = jnp.zeros((IS["size"],), dtype)
            scal = scal.at[IS["sig_i"]].set(sig_i)
            scal = scal.at[IS["gam_new"]].set(gam_new)
            scal = scal.at[IS["d2"]].set(d2)
            scal = scal.at[IS["dlt_safe"]].set(dlt_safe)
            scal = scal.at[IS["zet_prev"]].set(c.zet_prev)
            scal = scal.at[IS["d_prev"]].set(d_prev)
            scal = scal.at[IS["eta_new_safe"]].set(eta_new_safe)
            scal = scal.at[IS["eta0_safe"]].set(eta0_safe)
            for k in range(l):
                scal = scal.at[IS["c1"] + k].set(sig[k] - gam_new)

        with jax.named_scope(VECTOR_TAG):
            if fiter is not None:
                # One HBM pass: SPMV + prec + fills + K4 + K6 + local dot
                # partials in the superkernel, then ONE global reduction on
                # the partials (K5's MPI_Iallreduce, same payload as ever).
                S, partials = fiter(c.S, idx, scal)
                dots = ops.start_partials(partials)
            else:
                S, mat, u_new = fused_iter_unfused(c.S, idx, scal, ops.apply_a,
                                                   ops.prec, layout)
                # ---- (K5) line 23: initiate the dot block — ONE reduction ----
                # The raw payload (rows i-2l+1 .. i+1 of G column i+1) is
                # parked in the D ring; it is only consumed — and scattered
                # into G — at iteration i+l (MPI_Wait above).  Between the two
                # sites up to l reductions are simultaneously in flight.
                dots = ops.start(mat, u_new)
            D = c.D.at[jnp.mod(i, l)].set(dots)

            # ---- staged-reduction progress: one ladder hop per iteration ----
            # Every in-flight handle (pipeline age t = 1..l-1) advances by
            # exactly one ladder step — the hop-per-iteration pipeline of
            # DESIGN.md §14.  The step index is the handle's age minus one,
            # STATIC under the while loop (only the ring slot is dynamic), so
            # each ppermute's permutation is fixed at trace time.  Monolithic
            # substrates make advance the identity and XLA folds the loop
            # away; zero handles (early fill, post-restart) advance harmlessly
            # (permuting zeros writes zeros).
            for t in range(1, l):
                slot = jnp.mod(i - t, l)
                h = jax.lax.dynamic_index_in_dim(D, slot, axis=0,
                                                 keepdims=False)
                D = jax.lax.dynamic_update_index_in_dim(
                    D, ops.advance(h, t - 1), slot, axis=0)

        with jax.named_scope(SCALAR_TAG):
            eta_prev = jnp.where(is_first, gam0,
                                 jnp.where(do_upd, eta_new, c.eta_prev))
            zet_prev = jnp.where(is_first, c.norm0_cycle,
                                 jnp.where(do_upd, zet_new, c.zet_prev))

        with jax.named_scope(CONTROL_TAG):
            n_upd = jnp.where(do_upd, 1, 0).astype(jnp.int32)
            upd = st.upd + n_upd
            rnorm = jnp.abs(zet_new)
            # On a breakdown iteration the freshly computed scalars are garbage
            # (the restart discards them) — never record/converge on them.
            ok = do_upd & ~breakdown
            hist = jax.lax.cond(
                ok,
                lambda h: h.at[jnp.clip(upd, 0, H - 1)].set(rnorm),
                lambda h: h,
                st.hist,
            )
            # ---- stability governor: detection arms (DESIGN.md §18) ----------
            # Pure replicated-scalar work on values the scalar phase already
            # produced (the arrived dot block, the fresh Hessenberg entries) —
            # zero extra reductions, statically absent when ungoverned.
            gov = st.gov
            gov_cols = {}
            if governor is None:
                converged = st.converged | (ok & (rnorm / st.norm0 < tol))
            else:
                M = gov_model
                eps_c = jnp.asarray(governor.resolved_eps(dtype), dtype)
                # G(col, col) is the arrived block's last entry — the squared
                # scale of the newest basis vector.
                basis = jnp.sqrt(jnp.abs(arrived[2 * l]))
                # Grow by whichever is larger: the first-order eps model or
                # the per-iteration drift rate MEASURED over the previous
                # cycle (RATE; 0 until a restart measures one).  Under
                # injected corruption far beyond eps the measured rate
                # dominates and the gap arm fires within ~one cycle.
                inc = M.gap_step(jnp.zeros((), dtype), gam_new, d2, dlt_safe,
                                 basis, eps_c, governor.kappa)
                gap_acc = gov[M.GAP] + jnp.maximum(inc, gov[M.RATE])
                gap = jnp.where(ge_l, gap_acc, gov[M.GAP])
                rel = rnorm / st.norm0
                improved = ok & (rel < governor.improve_ratio * gov[M.BEST])
                best = jnp.where(improved, rel, gov[M.BEST])
                best_upd = jnp.where(improved, upd.astype(dtype),
                                     gov[M.BEST_UPD])
                # Gap arm: the recursive residual is within ``safety`` of the
                # (modeled + measured) gap — it can no longer be trusted.
                # The recursion claiming convergence (rel < tol) is the same
                # situation: both schedule a replacement, whose clean
                # true-residual recompute either certifies convergence (the
                # restart's lucky check) or re-seeds the gap with the
                # measured discrepancy.  A governed solve therefore never
                # sets ``converged`` from the recursion alone.
                if governor.verify_only:
                    gap_due = ok & (rel < tol)
                    pat_due = jnp.asarray(False)
                else:
                    gap_due = ok & ((governor.safety * gap >= rel) | (rel < tol))
                    pat_due = ok & (rel >= tol) & (
                        upd.astype(dtype) - best_upd
                        >= governor.resolved_patience(l))
                code = jnp.where(
                    gap_due, jnp.asarray(M.ACTION_GAP_REPLACE, dtype),
                    jnp.where(pat_due,
                              jnp.asarray(M.ACTION_PATIENCE_REPLACE, dtype),
                              jnp.zeros((), dtype)))
                due = jnp.where(gov[M.DUE] > 0, gov[M.DUE], code)
                gov = (gov.at[M.GAP].set(gap).at[M.BEST].set(best)
                          .at[M.BEST_UPD].set(best_upd).at[M.DUE].set(due))
                gov_cols = {"gap": gap, "action": code}
                converged = st.converged

            tel = tel_write(
                st.tel, st.tot,
                iter=st.tot, upd=upd,
                rnorm=jnp.where(ok, rnorm, -jnp.ones((), dtype)),
                age=jnp.minimum(i + 1, l),       # in-flight handles after park
                breakdown=breakdown, dots=arrived, **gov_cols,
            )

            cyc = _Cycle(
                S=S, G=G, D=D, gam=gam, dlt=dlt,
                eta_prev=eta_prev, zet_prev=zet_prev, i=i + 1,
                norm0_cycle=c.norm0_cycle,
            )
            return _State(
                cyc=cyc, tot=st.tot + 1, upd=upd, restarts=st.restarts,
                converged=converged, breakdown=breakdown, hist=hist, norm0=st.norm0,
                since_rr=st.since_rr + n_upd, tel=tel, gov=gov,
            )

    @jax.named_scope(RESTART_TAG)
    def do_restart(st: _State) -> _State:
        # Stagnation guard: a breakdown before the cycle's first solution
        # update (since_rr == 0) re-inits with a steepest-descent step so
        # the restart is guaranteed to make progress (see restart_cycle).
        cyc = restart_cycle(st.cyc.S[layout.x_row],
                            st.breakdown & (st.since_rr == 0))
        # A breakdown at a converged iterate is a "lucky breakdown": the
        # freshly computed residual M-norm at restart tells us directly.
        lucky = cyc.norm0_cycle / st.norm0 < tol

        # ---- governor accounting: consume the pending action ------------
        # ``norm0_cycle`` IS the true residual M-norm at the re-init, so
        # the fruitfulness of a governor-triggered replacement is judged
        # against clean arithmetic, not the (possibly corrupted)
        # recursive residual.  demote_after consecutive fruitless
        # replacements flip the terminal STAGNATED flag (DESIGN.md §18).
        gov = st.gov
        gov_cols = {}
        if governor is not None:
            M = gov_model
            was_due = gov[M.DUE]
            fired = was_due > 0
            rel_now = cyc.norm0_cycle / st.norm0   # TRUE rel residual
            # Measured true-vs-recursive gap: the recursion's latest
            # claim vs what the clean recompute actually found.  This
            # re-seeds the gap model on EVERY restart (breakdowns too),
            # so corruption far beyond the first-order eps model —
            # injected payload noise, a sick reduction wire — is
            # captured the first time a restart measures it, and the
            # gap arm then stops trusting recursive claims below it.
            eps_c = jnp.asarray(governor.resolved_eps(dtype), dtype)
            rec_rel = jnp.abs(st.cyc.zet_prev) / st.norm0
            measured = jnp.maximum(rel_now - rec_rel, jnp.zeros((), dtype))
            # The fresh cycle starts from a clean residual, so its gap
            # restarts near zero — but grows at the drift RATE this cycle
            # just exhibited (total measured gap / cycle length), which
            # sets the next replacement period adaptively.
            i_f = jnp.maximum(st.cyc.i.astype(dtype), jnp.ones((), dtype))
            rate_new = measured / i_f
            gap_new = eps_c
            fruitful = rel_now < governor.improve_ratio * gov[M.LAST_REL]
            fruitless = jnp.where(
                fired,
                jnp.where(fruitful, jnp.zeros((), dtype),
                          gov[M.FRUITLESS] + 1),
                gov[M.FRUITLESS])
            stag = gov[M.STAGNATED] if governor.verify_only else jnp.where(
                fruitless >= governor.demote_after,
                jnp.ones((), dtype), gov[M.STAGNATED])
            action = jnp.where(stag > gov[M.STAGNATED],
                               jnp.asarray(M.ACTION_STAGNATED, dtype),
                               was_due)
            gov = (gov.at[M.DUE].set(jnp.zeros((), dtype))
                      .at[M.REPL].set(gov[M.REPL]
                                      + fired.astype(dtype))
                      .at[M.FRUITLESS].set(fruitless)
                      .at[M.STAGNATED].set(stag)
                      .at[M.GAP].set(gap_new)
                      .at[M.RATE].set(rate_new)
                      .at[M.LAST_REL].set(jnp.where(fired, rel_now,
                                                    gov[M.LAST_REL]))
                      # Track the true residual as BEST too (it is the
                      # honest one), and restart the patience clock: the
                      # refill produces no updates, so the arm must wait
                      # a full window before escalating again.
                      .at[M.BEST].set(jnp.minimum(gov[M.BEST], rel_now))
                      .at[M.BEST_UPD].set(st.upd.astype(dtype)))
            gov_cols = {"gap": gov[M.GAP], "action": action}

        tel = tel_write(
            st.tel, st.tot,
            iter=st.tot, upd=st.upd,
            rnorm=cyc.norm0_cycle,           # TRUE residual M-norm at re-init
            age=jnp.int32(0),                # D-ring cleared by the restart
            breakdown=st.breakdown, restart=jnp.ones((), dtype),
            replacement=(~st.breakdown).astype(dtype), **gov_cols,
        )
        return _State(
            cyc=cyc, tot=st.tot + 1, upd=st.upd, restarts=st.restarts + 1,
            converged=st.converged | lucky, breakdown=jnp.asarray(False),
            hist=st.hist, norm0=st.norm0, since_rr=jnp.int32(0), tel=tel,
            gov=gov,
        )

    @jax.named_scope(CONTROL_TAG)
    def needs_interrupt(st: _State) -> jax.Array:
        due = st.breakdown
        if replace_every > 0:
            # Periodic residual replacement: re-init the cycle from the
            # current iterate (true-residual recompute) once enough
            # solution updates have accumulated since the last (re)start.
            due = due | (st.since_rr >= replace_every)
        if governor is not None:
            # Governor-scheduled replacement: same interrupt machinery,
            # so batched drivers apply it per-column masked at segment
            # boundaries (DESIGN.md §18).
            due = due | (st.gov[gov_model.DUE] > 0)
        return due

    # The interrupt test and its conditional are control; the branch it
    # takes nests its own phase scope inside, and the innermost phase
    # scope names an instruction's phase.
    @jax.named_scope(CONTROL_TAG)
    def body(st: _State) -> _State:
        return jax.lax.cond(needs_interrupt(st), do_restart, iteration, st)

    @jax.named_scope(CONTROL_TAG)
    def cond(st: _State) -> jax.Array:
        keep = (
            (~st.converged)
            & (st.tot < tot_max)
            & (st.upd < maxit)
            & (st.restarts <= max_restarts)
        )
        if governor is not None:
            # Terminal stagnation: stop burning iterations; the host
            # ladder (repro.stability.governor) demotes l or raises a
            # typed StagnationError from the returned governor vector.
            keep = keep & ~(st.gov[gov_model.STAGNATED] > 0)
        return keep

    @jax.named_scope(INIT_TAG)
    def init(x0: jax.Array) -> _State:
        cyc0 = init_cycle(x0)
        norm0 = cyc0.norm0_cycle
        hist0 = jnp.full((H,), -1.0, dtype).at[0].set(norm0)
        return _State(
            cyc=cyc0, tot=jnp.int32(0), upd=jnp.int32(0), restarts=jnp.int32(0),
            converged=norm0 == 0.0, breakdown=jnp.asarray(False),
            hist=hist0, norm0=norm0, since_rr=jnp.int32(0),
            tel=jnp.full((telemetry_cap, TK), -1.0, dtype),
            gov=(gov_model.gov_init(dtype) if governor is not None
                 else jnp.zeros((gov_model.N_SLOTS,), dtype)),
        )

    @jax.named_scope(INIT_TAG)
    def finish(final: _State) -> SolveResult:
        return SolveResult(
            x=final.cyc.S[layout.x_row], iters=final.upd,
            restarts=final.restarts, converged=final.converged,
            res_history=final.hist, norm0=final.norm0,
            telemetry=final.tel if telemetry_cap else None,
            governor=final.gov if governor is not None else None,
        )

    return PlcgProgram(init=init, iteration=iteration, body=body, cond=cond,
                       finish=finish, step=iteration,
                       needs_interrupt=needs_interrupt, interrupt=do_restart)


def solve(
    ops: SolverOps,
    b: jax.Array,
    l: int,
    x0: jax.Array | None = None,
    tol: float = 1e-6,
    maxit: int = 1000,
    sigmas: jax.Array | None = None,
    max_restarts: int = 10,
    unroll: int = 1,
    replace_every: int = 0,
    fused_iteration: bool = False,
    telemetry_cap: int = 0,
    recurrence: str = "ghysels",
    governor: "gov_model.GovernorConfig | None" = None,
    checkpoint=None,
) -> SolveResult:
    """Solve A x = b with p(l)-CG.  ``l`` is the pipeline depth (static);
    ``fused_iteration=True`` runs the vector phase through the one-pass
    superkernel (DESIGN.md §13); ``telemetry_cap > 0`` records the
    on-device per-iteration telemetry ring (DESIGN.md §16);
    ``recurrence="stable"`` selects the coupled basis recurrence,
    ``governor`` arms the stability governor (DESIGN.md §18) and
    ``checkpoint`` (a ``repro.checkpoint.CheckpointConfig`` with
    ``every > 0``) arms the segmented checkpointing driver
    (DESIGN.md §19; ``every=0``/None leaves this compiled path
    untouched)."""
    if checkpoint is not None and checkpoint.armed:
        from repro.checkpoint import checkpointed_solve

        return checkpointed_solve(
            ops, b, "plcg", x0, checkpoint,
            dict(l=l, tol=tol, maxit=maxit, sigmas=sigmas,
                 max_restarts=max_restarts, replace_every=replace_every,
                 fused_iteration=fused_iteration,
                 telemetry_cap=telemetry_cap, recurrence=recurrence,
                 governor=governor))
    prog = build(ops, b, l, tol=tol, maxit=maxit, sigmas=sigmas,
                 max_restarts=max_restarts, replace_every=replace_every,
                 fused_iteration=fused_iteration, telemetry_cap=telemetry_cap,
                 recurrence=recurrence, governor=governor)
    dtype = b.dtype
    st0 = prog.init(jnp.zeros_like(b) if x0 is None else x0.astype(dtype))

    if unroll > 1:
        # Unrolled driver: expose an (unroll)-iteration window to XLA so the
        # latency-hiding scheduler can stagger the in-flight reductions
        # (DESIGN.md §2).  Semantics identical to unroll=1.
        @jax.named_scope(CONTROL_TAG)
        def body_u(st: _State) -> _State:
            for _ in range(unroll):
                st = jax.lax.cond(prog.cond(st), prog.body, lambda s: s, st)
            return st

        final = jax.lax.while_loop(prog.cond, body_u, st0)
    else:
        final = jax.lax.while_loop(prog.cond, prog.body, st0)

    return prog.finish(final)
