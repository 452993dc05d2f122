#!/usr/bin/env python
"""Multi-controller parity check + strong-scaling study for the
``multiprocess`` reduction backend, exercised across REAL process
boundaries (DESIGN.md §3/§14/§17).

Default mode (CI ``multiprocess`` job, tests/test_multiprocess.py): pick
a free coordinator port (retrying bind collisions via
``repro.parallel.fabric``) and spawn ``--num-processes`` copies of this
script (default 2), each a real ``jax.distributed`` controller with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` — a 2-process x
4-device job whose solver mesh spans all 8 devices, so the fused
dot-block psum, the halo ppermutes AND the staged ladder's tagged hop
permutes genuinely cross the process boundary (the paper's MPI world).

Each process runs the same program (multi-controller SPMD): classic CG
and p(l)-CG on a structured stencil AND an unstructured FEM SparseOp,
asserting residual-history parity against the single-device ``local``
backend.  The run then exercises the STAGED HOP LADDER across the real
process boundary (DESIGN.md §17): ``reduction="staged"`` must run the
ladder for real — mode ``staged``, no fallback, the
``backend_reduction_fallback`` gauge pinned 0 — with residual histories
BITWISE against the local ``virtual_shards`` ladder oracle and ZERO
dot-block all-reduces in the compiled staged solve.

Chaos mode (``--chaos``, DESIGN.md §18): spawn the same real process
group, inject a seeded reduction-payload fault (``repro.chaos``) into
every rank's staged dot-block wait, and run a GOVERNED stable p(l)-CG
solve.  Every rank must emit a byte-identical ``CHAOS-GOV`` row —
replacement count, iteration count and bitwise residual-history hash —
proving the stability governor fires identically on every process
(divergent governor control flow would deadlock or diverge the very
next collective).

Scaling-study mode (``--study``, CI ``scaling-study`` job): a strong-
scaling sweep at FIXED n over 1..N processes (default 1,2,4 ranks x 1
device — the paper's Cori curve shape, reproduced on our own fabric):
per-P measured seconds/iteration staged vs monolithic (two-budget
differencing, min over repeats), bitwise parity vs the ladder oracle,
compiled-HLO structure (all-reduce count, hops/window), and per-process
hop/halo staggering timelines via the DESIGN.md §16 exporter
(``TIMELINE_scaling_proc*.json`` at the widest P).  Emits
``BENCH_scaling.json``; CI gates it via scripts/check_bench.py —
bitwise-parity floor, zero-all-reduce ceiling, hops floor, and
staged <= monolithic wall clock at P=2 (on a 1-core container every
collective costs a scheduler slice, so the P-1-hop ladder cannot
wall-clock-win at P>=3 — those rows gate at the documented
hop-serialization ceiling instead; see DESIGN.md §17).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(_ROOT, "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

from repro.parallel.fabric import (FabricError, launch_fabric,  # noqa: E402
                                   run_resilient)

STUDY_MARKER = "SCALING-JSON "
CHAOS_MARKER = "CHAOS-GOV "
RECOVERY_MARKER = "RECOVERY-JSON "
RECOVERY_KILL = "RECOVERY-KILL "
RECOVERY_RESUMED = "RECOVERY-RESUMED "


def _child_jax_setup():
    import jax

    # Cross-process CPU collectives need the gloo TCP backend (the
    # backend constructor also selects it).
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.config.update("jax_enable_x64", True)
    return jax


def _time_per_iter(be, op, b, sig, l, iters=(20, 60), repeats=5):
    """Measured seconds/iteration on a live backend: two fixed budgets
    (tol=0 disables early exit), differenced to cancel init/launch
    overhead, min over ``repeats`` (launch.autotune.measured_runner's
    policy)."""
    import time

    import jax

    def run(maxit):
        solver = be.make_solver(op, "plcg", None, l=l, sigmas=sig,
                                tol=0.0, maxit=maxit)
        jax.block_until_ready(solver(b).x)          # compile + warmup
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(solver(b).x)
            best = min(best, time.perf_counter() - t0)
        return best

    lo, hi = iters
    t_lo, t_hi = run(lo), run(hi)
    if t_hi <= t_lo:
        return t_hi / hi
    return (t_hi - t_lo) / (hi - lo)


def child(coordinator: str, num_processes: int, process_id: int) -> int:
    jax = _child_jax_setup()
    import warnings

    import jax.numpy as jnp
    import numpy as np

    from repro.core.chebyshev import shifts_for_operator
    from repro.linalg import Stencil2D5, random_fem_mesh, rcm_reorder
    from repro.obs.metrics import default_registry
    from repro.parallel import get_backend
    from repro.parallel.reduction import ReductionFallbackWarning
    from repro.utils.trace import plcg_overlap_report

    be = get_backend(
        "multiprocess",
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
    n_dev = be.n_shards
    assert jax.process_count() == num_processes, jax.process_count()
    assert n_dev == num_processes * jax.local_device_count(), n_dev
    print(f"[p{process_id}] {be.describe()}", flush=True)
    local = get_backend("local")

    problems = [
        ("stencil2d", Stencil2D5(32, 24)),
        ("fem-sparse", rcm_reorder(random_fem_mesh(0, 400))[0]),
    ]
    for name, op in problems:
        b = jnp.asarray(np.random.default_rng(7).standard_normal(op.n))
        sig = shifts_for_operator(op, 2)
        for method, kw in (("cg", {}), ("plcg", dict(l=2, sigmas=sig))):
            kw = dict(kw, tol=1e-8, maxit=800)
            res_m = be.solve(op, b, method=method, **kw)
            res_l = local.solve(op, b, method=method, **kw)
            hm = np.asarray(res_m.res_history)
            hl = np.asarray(res_l.res_history)
            n0 = float(res_l.norm0)
            m = (hm >= 0) & (hl >= 0)
            assert m.sum() > 5, (name, method, int(m.sum()))
            # Histories are norm0-normalized for comparison: Krylov
            # recurrences amplify reduction-order ULPs chaotically as the
            # residual shrinks (tests/test_distributed.py measures a 0.5
            # relative drift from a single ULP on b), so the contract is
            # a TIGHT head (pre-amplification — a wrong operator or halo
            # breaks here immediately) and a bounded tail.
            diff = np.abs(hm[m] - hl[m]) / n0
            assert diff[:10].max() < 1e-8, (name, method, diff[:10].max())
            assert diff.max() < 5e-2, (name, method, diff.max())
            d_it = abs(int(res_m.iters) - int(res_l.iters))
            assert d_it <= 5, (name, method, int(res_m.iters),
                               int(res_l.iters))
            assert bool(res_m.converged)
            print(f"[p{process_id}] {name}/{method}: iters "
                  f"{int(res_m.iters)} vs local {int(res_l.iters)}, "
                  f"max|dh|/norm0 {diff.max():.2e}", flush=True)

    # ---- staged hop ladder across the real process boundary (§17) -------
    # The ladder must RUN — no capability downgrade, no warning, gauge
    # pinned 0 — with tagged per-hop permutes as the only dot-block wire
    # traffic and histories bitwise vs the single-device virtual-shards
    # ladder oracle (same ring size, same rank-ordered combine).
    op = Stencil2D5(32, 24)
    b = jnp.asarray(np.random.default_rng(7).standard_normal(op.n))
    sig = shifts_for_operator(op, 2)
    stages = 2
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        be_staged = get_backend(
            "multiprocess",
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
            reduction="staged",
            reduction_stages=stages,
        )
    assert type(be_staged).supports_staged_reduction
    assert be_staged.reduction_mode == "staged", be_staged.reduction_mode
    assert be_staged.reduction_fallback is None
    assert be_staged.reduction_cfg is not None
    assert not any(isinstance(w.message, ReductionFallbackWarning)
                   for w in caught), [str(w.message) for w in caught]
    g = default_registry().get("backend_reduction_fallback")
    assert g is not None
    assert g.value(labels={"backend": "multiprocess"}) == 0.0
    assert be_staged.cross_process_edges() == num_processes
    assert be_staged.hop_wire() == "gloo", be_staged.hop_wire()

    kw = dict(method="plcg", l=2, sigmas=sig, tol=1e-8, maxit=800)
    res_s = be_staged.solve(op, b, **kw)
    oracle = get_backend("local", reduction="staged",
                         virtual_shards=n_dev, reduction_stages=stages)
    res_o = oracle.solve(op, b, **kw)
    hs, ho = np.asarray(res_s.res_history), np.asarray(res_o.res_history)
    assert np.array_equal(hs, ho), np.abs(hs - ho).max()
    assert bool(res_s.converged)

    # fp32 wire payload: both sides round at the start site and Kahan-
    # accumulate at the wait, so cross-process stays bitwise vs the
    # fp32-wire oracle too.
    be_32 = get_backend(
        "multiprocess", coordinator_address=coordinator,
        num_processes=num_processes, process_id=process_id,
        reduction="staged", reduction_stages=stages,
        reduction_dtype=jnp.float32)
    or_32 = get_backend("local", reduction="staged", virtual_shards=n_dev,
                        reduction_stages=stages,
                        reduction_dtype=jnp.float32)
    h32s = np.asarray(be_32.solve(op, b, **kw).res_history)
    h32o = np.asarray(or_32.solve(op, b, **kw).res_history)
    assert np.array_equal(h32s, h32o), np.abs(h32s - h32o).max()

    # Compiled staged solve: ZERO dot-block all-reduces on the wire —
    # only tagged hop permutes, one logical start per window.
    bspec = jax.ShapeDtypeStruct((op.n,), jnp.float64)
    rep = plcg_overlap_report(be_staged, op, bspec, l=2, window=4,
                              sigmas=sig)
    assert rep.n_collectives == 0, rep.n_collectives
    assert min(rep.reduce_hops_per_window.values()) >= 1, \
        rep.reduce_hops_per_window
    assert max(rep.staged_starts_per_window.values()) == 1, \
        rep.staged_starts_per_window
    print(f"[p{process_id}] staged ladder CROSS-PROCESS: bitwise vs "
          f"virtual-shards oracle (fp64 + fp32 wire), 0 dot-block "
          f"all-reduces, hops/window "
          f"{dict(rep.reduce_hops_per_window)}, "
          f"{be_staged.cross_process_edges()} cross-process edge(s)/hop "
          f"over {be_staged.hop_wire()}", flush=True)

    # ---- instrumented cross-process solve + timeline export (§16) -------
    # Every process runs the SAME instrumented solve (telemetry values
    # are post-psum replicated scalars — no new collectives cross the
    # wire) and exports its own Chrome-trace JSON; the launcher/CI pick
    # the files up as artifacts.
    from repro.obs import Timeline, telemetry_track

    tl = Timeline()
    tl.name_thread(1, 1, "cross-process solve phases")
    with tl.span("plcg[instrumented, cross-process]"):
        res_t = be.solve(op, b, method="plcg", l=2, sigmas=sig, tol=1e-8,
                         maxit=800, telemetry_cap=128)
        jax.block_until_ready(res_t.res_history)
    assert res_t.telemetry is not None
    tel = np.asarray(res_t.telemetry)
    assert (tel[:, 0] >= 0).any(), "telemetry ring never written"
    tl.merge(telemetry_track(res_t.telemetry, l=2))
    tl.meta["parity"] = {
        "process_id": process_id, "num_processes": num_processes,
        "backend": be.name, "reduction_mode": be.reduction_mode,
        "staged_wire": be_staged.hop_wire(),
    }
    path = tl.save(f"TIMELINE_parity_proc{process_id}.json")
    print(f"[p{process_id}] timeline -> {path}", flush=True)

    print(f"[p{process_id}] MULTIPROC-PARITY-OK", flush=True)
    return 0


def study_child(coordinator: str, num_processes: int, process_id: int,
                args) -> int:
    """One rank of one strong-scaling point: measure staged vs monolithic
    seconds/iteration at fixed n, assert ladder parity, extract the
    compiled hop/halo schedule, optionally export this rank's timeline."""
    jax = _child_jax_setup()
    import jax.numpy as jnp
    import numpy as np

    from repro.core.chebyshev import shifts_for_operator
    from repro.linalg import Stencil2D5
    from repro.parallel import get_backend
    from repro.utils.trace import plcg_overlap_report

    kw_be = dict(coordinator_address=coordinator,
                 num_processes=num_processes, process_id=process_id)
    be_mono = get_backend("multiprocess", **kw_be)
    n_dev = be_mono.n_shards
    stages = max(1, min(args.stages, max(n_dev - 1, 1)))
    be_staged = get_backend("multiprocess", **kw_be, reduction="staged",
                            reduction_stages=stages)
    assert be_staged.reduction_mode == "staged"

    op = Stencil2D5(args.nx, args.ny)
    l = args.l
    sig = shifts_for_operator(op, l)
    b = jnp.asarray(np.random.default_rng(7).standard_normal(op.n))
    budgets = (args.budget_lo, args.budget_hi)

    t_mono = _time_per_iter(be_mono, op, b, sig, l, iters=budgets,
                            repeats=args.repeats)
    t_staged = _time_per_iter(be_staged, op, b, sig, l, iters=budgets,
                              repeats=args.repeats)

    # Bitwise ladder parity vs the single-device virtual-shards oracle.
    kw = dict(method="plcg", l=l, sigmas=sig, tol=1e-8, maxit=1200)
    res_s = be_staged.solve(op, b, **kw)
    oracle = get_backend("local", reduction="staged",
                         virtual_shards=n_dev, reduction_stages=stages)
    res_o = oracle.solve(op, b, **kw)
    hs, ho = np.asarray(res_s.res_history), np.asarray(res_o.res_history)
    parity_bitwise = bool(np.array_equal(hs, ho))

    # Compiled staged schedule: the structural columns of the study.
    bspec = jax.ShapeDtypeStruct((op.n,), jnp.float64)
    rep = plcg_overlap_report(be_staged, op, bspec, l=l, window=l + 2,
                              sigmas=sig)

    if args.emit_timelines:
        # Per-rank hop/halo staggering timeline via the §16 exporter:
        # measured host spans + the compiled schedule track (reduction
        # windows vs ladder hops vs halo permutes) + the telemetry ring.
        from repro.obs.timeline import solve_timeline

        tl, _res = solve_timeline(be_staged, op, b, l=l, sigmas=sig,
                                  telemetry_cap=128, tol=1e-8, maxit=1200)
        tl.meta["scaling_study"] = {
            "process_id": process_id, "num_processes": num_processes,
            "n": int(op.n), "stages": stages,
            "wire": be_staged.hop_wire(),
            "cross_process_edges": be_staged.cross_process_edges(),
        }
        path = tl.save(f"TIMELINE_scaling_proc{process_id}.json")
        print(f"[p{process_id}] timeline -> {path}", flush=True)

    row = {
        "procs": num_processes,
        "devices": n_dev,
        "stages": stages,
        "staged_iter_time_s": t_staged,
        "monolithic_iter_time_s": t_mono,
        "staged_over_monolithic": t_staged / t_mono,
        "parity_bitwise": parity_bitwise,
        "staged_allreduces": rep.n_collectives,
        # P=1 has a hopless ladder (0-hop ring): empty window dicts.
        "hops_per_window_min": min(rep.reduce_hops_per_window.values(),
                                   default=0),
        "staged_starts_per_window_max":
            max(rep.staged_starts_per_window.values(), default=0),
        "iters_staged": int(res_s.iters),
        "iters_oracle": int(res_o.iters),
        "wire": be_staged.hop_wire(),
        "cross_process_edges": be_staged.cross_process_edges(),
    }
    if process_id == 0:
        print(STUDY_MARKER + json.dumps(row), flush=True)
    print(f"[p{process_id}] P={num_processes} staged "
          f"{t_staged * 1e6:.0f}us/iter vs mono {t_mono * 1e6:.0f}us/iter "
          f"(x{t_staged / t_mono:.2f}), parity_bitwise={parity_bitwise}, "
          f"allreduces={rep.n_collectives}", flush=True)
    print(f"[p{process_id}] SCALING-OK", flush=True)
    return 0


def chaos_child(coordinator: str, num_processes: int,
                process_id: int) -> int:
    """One rank of the cross-process chaos drill (DESIGN.md §18): run a
    GOVERNED stable p(l)-CG solve over the real staged ladder with a
    seeded reduction-payload fault injected at the dot-block wait, and
    emit a ``CHAOS-GOV`` marker the launcher byte-compares across ranks.

    The injected noise is a value-hash of the post-combine (replicated)
    payload, so every rank perturbs identically and the governor's
    replacement decisions — control flow driven by the perturbed dots —
    stay lockstep SPMD: same replacement count, same residual history,
    bit for bit.  A rank whose governor fired differently would diverge
    at the next collective; the identical markers prove it did not.
    """
    jax = _child_jax_setup()
    import hashlib

    import jax.numpy as jnp
    import numpy as np

    from repro.chaos import ChaosConfig, chaos_ops
    from repro.core import pipelined_cg
    from repro.core.chebyshev import shifts_for_operator
    from repro.linalg import Stencil2D5
    from repro.parallel import get_backend
    from repro.parallel.fabric import touch_heartbeat
    from repro.stability import GovernorConfig
    from repro.stability import model as gov_model

    touch_heartbeat()
    be = get_backend(
        "multiprocess", coordinator_address=coordinator,
        num_processes=num_processes, process_id=process_id,
        reduction="staged", reduction_stages=2)
    assert be.reduction_mode == "staged", be.reduction_mode
    print(f"[p{process_id}] {be.describe()}", flush=True)

    op = Stencil2D5(32, 24)
    b = jnp.asarray(np.random.default_rng(7).standard_normal(op.n))
    sig = shifts_for_operator(op, 2)
    chaos = ChaosConfig(seed=7, payload_rel_amp=1e-5)
    kw = dict(l=2, sigmas=sig, tol=1e-5, maxit=400,
              recurrence="stable", governor=GovernorConfig())

    # Only replicated pieces come back through the shard_map (out_specs
    # P()): the residual history, governor vector and scalars are all
    # post-psum values, identical on every device.
    def fn(ops, bb):
        res = pipelined_cg.solve(chaos_ops(ops, chaos), bb, **kw)
        return res.res_history, res.governor, res.iters, res.converged

    hist, gov, iters, conv = be.run(fn, op, b)
    touch_heartbeat()
    hist, gov = np.asarray(hist), np.asarray(gov)
    repl = int(gov[gov_model.REPL])
    assert bool(conv), "governed chaos solve failed to converge"
    assert repl >= 1, "governor never fired under injected perturbation"
    row = {
        "converged": bool(conv),
        "iters": int(iters),
        "replacements": repl,
        "stagnated": int(gov[gov_model.STAGNATED]),
        "governor_sha": hashlib.sha256(gov.tobytes()).hexdigest(),
        "history_sha": hashlib.sha256(hist.tobytes()).hexdigest(),
    }
    print(CHAOS_MARKER + json.dumps(row, sort_keys=True), flush=True)
    print(f"[p{process_id}] governed chaos solve: iters {row['iters']}, "
          f"{repl} governed replacement(s), history sha "
          f"{row['history_sha'][:16]}", flush=True)
    print(f"[p{process_id}] CHAOS-OK", flush=True)
    return 0


def recovery_child(coordinator: str, num_processes: int, process_id: int,
                   args) -> int:
    """One rank of the kill-a-rank recovery drill (DESIGN.md §19).

    Every rank runs the same checkpointed staged p(l)-CG solve over the
    real process fabric, touching its heartbeat and ticking the
    environment-scripted iteration faults at every drained-ring
    boundary.  On attempt 1 the fault plan kills one rank mid-solve; on
    attempt 2 (clean environment, ``resume=True`` on the shared
    checkpoint directory) the group restores the last snapshot, resumes
    and converges — rank 0 then replays the UNINTERRUPTED local
    virtual-shards oracle of the same segmented config and asserts the
    resumed cross-process history is bitwise identical to it (head from
    the checkpoint, tail recomputed — one history, no seam).
    """
    jax = _child_jax_setup()           # noqa: F841 - configures x64/gloo
    import jax.numpy as jnp
    import numpy as np

    from repro.chaos import install_iteration_faults
    from repro.checkpoint import LAST_RESTORE, CheckpointConfig
    from repro.core.chebyshev import shifts_for_operator
    from repro.linalg import Stencil2D5
    from repro.parallel import get_backend
    from repro.parallel.fabric import install_sigterm_handler, touch_heartbeat

    # A peer death leaves this rank blocked in a collective; the
    # launcher's SIGTERM must turn that into a prompt, distinct-status
    # exit instead of a watchdog-escalated SIGKILL.
    install_sigterm_handler()
    touch_heartbeat()
    faults = install_iteration_faults(process_id)

    be = get_backend(
        "multiprocess", coordinator_address=coordinator,
        num_processes=num_processes, process_id=process_id,
        reduction="staged", reduction_stages=args.stages)
    assert be.reduction_mode == "staged", be.reduction_mode
    n_dev = be.n_shards
    print(f"[p{process_id}] attempt {args.attempt}: {be.describe()}, "
          f"faults armed={faults.armed}", flush=True)

    op = Stencil2D5(32, 24)            # the parity/chaos drill problem
    b = jnp.asarray(np.random.default_rng(7).standard_normal(op.n))
    sig = shifts_for_operator(op, args.l)
    # Staged + UNFUSED is the bitwise-elastic configuration: fused
    # iterations compile different (per-substrate) contraction orders,
    # so their cross-substrate parity is certified, not bitwise
    # (DESIGN.md §19 honesty notes).
    kw = dict(l=args.l, sigmas=sig, tol=1e-10, maxit=400,
              fused_iteration=False)

    def on_boundary(upd: int) -> None:
        # ``upd`` = global solution updates (boundaries land at exact
        # multiples of ``every`` updates; plcg's post-restart ring
        # refill advances tot but not upd).
        touch_heartbeat()
        if faults.kill_at_iter is not None and upd >= faults.kill_at_iter:
            # Last words before the scripted death: which boundary this
            # rank died at, for the launcher's recomputed-iters metric.
            print(RECOVERY_KILL + json.dumps(
                {"rank": process_id, "upd": int(upd), "t": time.time()}),
                flush=True)
        faults.tick(upd)

    cfg = CheckpointConfig(every=args.every, directory=args.ckpt_dir,
                           keep=3, resume=True, on_boundary=on_boundary)
    res = be.solve(op, b, method="plcg", checkpoint=cfg, **kw)
    hist = np.asarray(res.res_history)
    resumed_tot = resumed_upd = 0
    if LAST_RESTORE:
        resumed_tot = int(LAST_RESTORE[-1].meta["tot"])
        resumed_upd = int(LAST_RESTORE[-1].meta["upd"])
        print(RECOVERY_RESUMED + json.dumps(
            {"rank": process_id, "tot": resumed_tot, "upd": resumed_upd,
             "t": time.time(),
             "path": os.path.basename(LAST_RESTORE[-1].path)}), flush=True)
    assert bool(res.converged), "recovery solve failed to converge"

    if process_id == 0:
        # Uninterrupted oracle: the SAME segmented config (same
        # effective replacement cadence) on the local virtual-shards
        # ladder, never killed, never restored.  directory=None keeps
        # the segmented drive without persisting.
        oracle = get_backend("local", reduction="staged",
                             virtual_shards=n_dev,
                             reduction_stages=args.stages)
        res_o = oracle.solve(op, b, method="plcg",
                             checkpoint=CheckpointConfig(every=args.every),
                             **kw)
        ho = np.asarray(res_o.res_history)
        parity = bool(hist.shape == ho.shape and np.array_equal(hist, ho))
        row = {
            "attempt": args.attempt,
            "procs": num_processes,
            "devices": n_dev,
            "resumed_tot": resumed_tot,
            "resumed_upd": resumed_upd,
            "iters": int(res.iters),
            "iters_oracle": int(res_o.iters),
            "converged": bool(res.converged),
            "parity_bitwise": parity,
        }
        print(RECOVERY_MARKER + json.dumps(row), flush=True)
        assert parity, (
            "resumed cross-process history diverged from the "
            f"uninterrupted local oracle (max |dh| = "
            f"{np.abs(hist - ho).max() if hist.shape == ho.shape else 'shape'})")
    print(f"[p{process_id}] RECOVERY-OK", flush=True)
    return 0


def recovery(args) -> int:
    """Kill-a-rank recovery drill launcher (DESIGN.md §19).

    Attempt 1 ships a seeded iteration-indexed kill plan for one rank
    (``repro.chaos``); the launcher's watchdog converts the death into
    a typed :class:`FabricProcessError`, ``run_resilient`` tears the
    group down and respawns a clean fabric on a fresh coordinator port;
    attempt 2 resumes from the shared checkpoint directory and must
    converge with a residual history BITWISE equal to the uninterrupted
    local virtual-shards oracle.  Emits a ``RECOVERY-RESULT`` JSON line
    (detection/respawn seconds, recomputed iterations, parity bit) that
    benchmarks/recovery_bench.py turns into the gated
    ``BENCH_recovery.json``.
    """
    from repro.chaos import ChaosConfig

    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="repro-recovery-")
    plan_env = ChaosConfig(
        seed=7, kill_rank=args.kill_rank,
        kill_rank_at_iter=args.kill_at).fault_plan().env()
    t_attempt: dict[int, float] = {}

    def attempt_env(attempt: int) -> dict:
        # Called right before each fabric launch: timestamping here is
        # what separates detection (death -> teardown done) from
        # respawn (relaunch -> solve resumed).  The kill plan is armed
        # on the FIRST attempt only; the respawn runs clean.
        t_attempt[attempt] = time.time()
        return dict(plan_env) if attempt == 1 else {}

    def argv(coordinator: str, k: int, p: int, a: int) -> list[str]:
        return [sys.executable, os.path.abspath(__file__),
                "--coordinator", coordinator,
                "--num-processes", str(p),
                "--process-id", str(k),
                "--recovery-child",
                "--ckpt-dir", ckpt_dir,
                "--every", str(args.every),
                "--l", str(args.l), "--stages", str(args.stages),
                "--attempt", str(a)]

    try:
        rr = run_resilient(argv, args.num_processes, max_failures=1,
                           env=_fabric_env(args.devices_per_process),
                           attempt_env=attempt_env, timeout_s=args.timeout)
    except FabricError as e:
        print(f"[recovery] FAILED: {e}")
        return 1

    for out in rr.result.outputs:
        sys.stdout.write(out)
    if len(rr.failures) != 1:
        print(f"[recovery] FAILED (expected exactly 1 scripted rank "
              f"failure, saw {len(rr.failures)})")
        return 1
    if not all("RECOVERY-OK" in o for o in rr.result.outputs):
        print("[recovery] FAILED (missing rank RECOVERY-OK marker)")
        return 1

    def rows(outputs, marker):
        found = []
        for out in outputs:
            found += [json.loads(ln[len(marker):])
                      for ln in out.splitlines() if ln.startswith(marker)]
        return found

    # The kill marker rides on the FAILED attempt's harvested outputs.
    kills = rows(getattr(rr.failures[0], "outputs", []), RECOVERY_KILL)
    resumed = rows(rr.result.outputs, RECOVERY_RESUMED)
    results = rows(rr.result.outputs, RECOVERY_MARKER)
    if not (kills and resumed and results):
        print(f"[recovery] FAILED (markers missing: kills={len(kills)} "
              f"resumed={len(resumed)} results={len(results)})")
        return 1
    kill = kills[-1]
    res0 = next(r for r in resumed if r["rank"] == 0)
    row = results[-1]

    # Solution-update units throughout: boundaries land at exact
    # multiples of ``every`` updates, so losing at most one interval
    # means recomputed <= every exactly.
    recomputed = int(kill["upd"]) - int(res0["upd"])
    detection_s = max(t_attempt[2] - float(kill["t"]), 0.0)
    respawn_s = max(float(res0["t"]) - t_attempt[2], 0.0)
    ok = (row["parity_bitwise"] and row["converged"]
          and 0 < recomputed <= args.every)
    summary = {
        "procs": args.num_processes,
        "devices_per_process": args.devices_per_process,
        "kill_rank": args.kill_rank,
        "kill_upd": int(kill["upd"]),
        "resumed_upd": int(res0["upd"]),
        "recomputed_iters": recomputed,
        "checkpoint_every": args.every,
        "detection_s": detection_s,
        "respawn_s": respawn_s,
        "attempts": rr.attempts,
        "iters": row["iters"],
        "parity_bitwise": int(bool(row["parity_bitwise"])),
        "converged": int(bool(row["converged"])),
    }
    print("RECOVERY-RESULT " + json.dumps(summary))
    print(f"[recovery] killed rank {args.kill_rank} at update "
          f"{kill['upd']}, detected + torn down in {detection_s:.1f}s, "
          f"respawned + resumed from update {res0['upd']} in "
          f"{respawn_s:.1f}s ({recomputed} updates recomputed <= "
          f"every={args.every}), resumed history bitwise vs local "
          f"oracle: {bool(row['parity_bitwise'])}")
    if not ok:
        print("[recovery] FAILED (acceptance gate)")
        return 1
    print(f"[recovery] {args.num_processes} processes x "
          f"{args.devices_per_process} devices: RECOVERY OK")
    return 0


def chaos(num_processes: int, devices_per_process: int) -> int:
    """Chaos launcher: every rank must emit the SAME ``CHAOS-GOV`` row —
    the governor fired identically (same count, same iterations, same
    bitwise history) on every process under the injected fault."""
    try:
        res = launch_fabric(
            lambda coord, k: [sys.executable, os.path.abspath(__file__),
                              "--coordinator", coord,
                              "--num-processes", str(num_processes),
                              "--process-id", str(k),
                              "--chaos-child"],
            num_processes, env=_fabric_env(devices_per_process),
            timeout_s=900)
    except FabricError as e:
        print(f"[launcher] FAILED: {e}")
        return 1
    for out in res.outputs:
        sys.stdout.write(out)
    if not all("CHAOS-OK" in o for o in res.outputs):
        print("[launcher] FAILED (missing rank CHAOS-OK marker)")
        return 1
    rows = []
    for k, out in enumerate(res.outputs):
        frag = [ln for ln in out.splitlines()
                if ln.startswith(CHAOS_MARKER)]
        if not frag:
            print(f"[launcher] FAILED (rank {k} emitted no chaos row)")
            return 1
        rows.append(frag[-1])
    if len(set(rows)) != 1:
        print("[launcher] FAILED (governor rows differ across ranks):")
        for k, r in enumerate(rows):
            print(f"  rank {k}: {r}")
        return 1
    row = json.loads(rows[0][len(CHAOS_MARKER):])
    print(f"[launcher] {num_processes} processes x "
          f"{devices_per_process} devices: CHAOS-GOV OK — governor "
          f"fired identically on every rank "
          f"({row['replacements']} replacement(s), "
          f"{row['iters']} iters, coordinator {res.coordinator})")
    return 0


def _fabric_env(devices_per_process: int) -> dict:
    env = dict(
        os.environ,
        XLA_FLAGS=f"--xla_force_host_platform_device_count="
                  f"{devices_per_process}",
        JAX_PLATFORMS="cpu",
        JAX_CPU_COLLECTIVES_IMPLEMENTATION="gloo",
    )
    env.setdefault("PYTHONPATH", "src")
    return env


def launch(num_processes: int, devices_per_process: int) -> int:
    """Default parity mode: one fabric, assert every rank's OK marker."""
    try:
        res = launch_fabric(
            lambda coord, k: [sys.executable, os.path.abspath(__file__),
                              "--coordinator", coord,
                              "--num-processes", str(num_processes),
                              "--process-id", str(k)],
            num_processes, env=_fabric_env(devices_per_process),
            timeout_s=900)
    except FabricError as e:
        print(f"[launcher] FAILED: {e}")
        return 1
    for out in res.outputs:
        sys.stdout.write(out)
    if all("MULTIPROC-PARITY-OK" in o for o in res.outputs):
        print(f"[launcher] {num_processes} processes x "
              f"{devices_per_process} devices: PARITY OK "
              f"(coordinator {res.coordinator}, attempt {res.attempts})")
        return 0
    print("[launcher] FAILED")
    return 1


def study(args) -> int:
    """Strong-scaling sweep: fixed n, 1..N processes, staged vs
    monolithic, aggregated into the gated ``BENCH_scaling.json``."""
    procs_list = [int(p) for p in args.procs.split(",")]
    rows = []
    for p in procs_list:
        try:
            res = launch_fabric(
                lambda coord, k, _p=p: [
                    sys.executable, os.path.abspath(__file__),
                    "--coordinator", coord,
                    "--num-processes", str(_p),
                    "--process-id", str(k),
                    "--study-child",
                    "--nx", str(args.nx), "--ny", str(args.ny),
                    "--l", str(args.l), "--stages", str(args.stages),
                    "--repeats", str(args.repeats),
                    "--budget-lo", str(args.budget_lo),
                    "--budget-hi", str(args.budget_hi),
                ] + (["--emit-timelines"] if _p == max(procs_list) else []),
                p, env=_fabric_env(args.devices_per_process),
                timeout_s=args.timeout)
        except FabricError as e:
            print(f"[study] P={p} FAILED: {e}")
            return 1
        for out in res.outputs:
            sys.stdout.write(out)
        if not all("SCALING-OK" in o for o in res.outputs):
            print(f"[study] P={p} FAILED (missing rank OK marker)")
            return 1
        frag = [ln for ln in res.outputs[0].splitlines()
                if ln.startswith(STUDY_MARKER)]
        assert frag, "study child emitted no row"
        rows.append(json.loads(frag[-1][len(STUDY_MARKER):]))
        print(f"[study] P={p} done (coordinator {res.coordinator}, "
              f"attempt {res.attempts})")

    n = args.nx * args.ny
    multi = [r for r in rows if r["procs"] >= 2]
    payload = {
        "study": {
            "n": n, "nx": args.nx, "ny": args.ny, "l": args.l,
            "stages_requested": args.stages,
            "procs": procs_list,
            "devices_per_process": args.devices_per_process,
            "repeats": args.repeats,
            "iter_budgets": [args.budget_lo, args.budget_hi],
            "wall_clock_basis": (
                "compiled XLA CPU ranks over gloo TCP loopback; "
                "strong scaling at fixed n — NOT the paper's Cori "
                "fabric (see DESIGN.md §17 for what is and is not "
                "comparable)"),
        },
        "rows": rows,
        # Gated structural columns (deterministic on any machine):
        "scaling_parity_bitwise": int(all(r["parity_bitwise"]
                                          for r in rows)),
        "scaling_staged_allreduces_max": max(r["staged_allreduces"]
                                             for r in rows),
        "scaling_hops_per_window_min": min(
            (r["hops_per_window_min"] for r in multi), default=0),
        "scaling_staged_starts_max": max(
            (r["staged_starts_per_window_max"] for r in rows), default=0),
    }
    t1 = next((r for r in rows if r["procs"] == 1), None)
    for r in rows:
        p = r["procs"]
        payload[f"staged_iter_time_p{p}_s"] = r["staged_iter_time_s"]
        payload[f"monolithic_iter_time_p{p}_s"] = r["monolithic_iter_time_s"]
        if p >= 2:
            payload[f"staged_over_monolithic_p{p}"] = \
                r["staged_over_monolithic"]
        if t1 is not None:
            payload[f"staged_speedup_p{p}"] = \
                t1["staged_iter_time_s"] / r["staged_iter_time_s"]
            payload[f"monolithic_speedup_p{p}"] = \
                t1["monolithic_iter_time_s"] / r["monolithic_iter_time_s"]
    for k, v in payload.items():
        if k not in ("rows", "study"):
            print(f"{k}: {v}")
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--coordinator", type=str, default=None)
    ap.add_argument("--num-processes", type=int, default=2)
    ap.add_argument("--process-id", type=int, default=None)
    ap.add_argument("--devices-per-process", type=int, default=None)
    # ---- scaling study ----
    ap.add_argument("--study", action="store_true",
                    help="run the strong-scaling study (launcher mode)")
    ap.add_argument("--study-child", action="store_true")
    # ---- chaos drill (DESIGN.md §18) ----
    ap.add_argument("--chaos", action="store_true",
                    help="run the cross-process governed chaos drill "
                         "(launcher mode)")
    ap.add_argument("--chaos-child", action="store_true")
    # ---- recovery drill (DESIGN.md §19) ----
    ap.add_argument("--recovery", action="store_true",
                    help="run the kill-a-rank checkpoint/restore drill "
                         "(launcher mode)")
    ap.add_argument("--recovery-child", action="store_true")
    ap.add_argument("--ckpt-dir", type=str, default=None)
    ap.add_argument("--every", type=int, default=20,
                    help="checkpoint interval (solution updates)")
    ap.add_argument("--kill-rank", type=int, default=1)
    ap.add_argument("--kill-at", type=int, default=35,
                    help="kill the rank at the first boundary reaching "
                         "this iteration")
    ap.add_argument("--attempt", type=int, default=1)
    ap.add_argument("--procs", type=str, default="1,2,4",
                    help="comma-separated process counts for --study")
    ap.add_argument("--nx", type=int, default=96)
    ap.add_argument("--ny", type=int, default=64)
    ap.add_argument("--l", type=int, default=2)
    ap.add_argument("--stages", type=int, default=2)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--budget-lo", type=int, default=20)
    ap.add_argument("--budget-hi", type=int, default=60)
    ap.add_argument("--timeout", type=float, default=900.0)
    ap.add_argument("--emit-timelines", action="store_true")
    ap.add_argument("--out", type=str, default="BENCH_scaling.json")
    args = ap.parse_args(argv)
    if args.study:
        if args.devices_per_process is None:
            args.devices_per_process = 1     # P ranks == P shards
        return study(args)
    if args.devices_per_process is None:
        small = (args.chaos or args.chaos_child
                 or args.recovery or args.recovery_child)
        args.devices_per_process = 2 if small else 4
    if args.process_id is None:
        if args.chaos:
            return chaos(args.num_processes, args.devices_per_process)
        if args.recovery:
            return recovery(args)
        return launch(args.num_processes, args.devices_per_process)
    if args.chaos_child:
        return chaos_child(args.coordinator, args.num_processes,
                           args.process_id)
    if args.recovery_child:
        return recovery_child(args.coordinator, args.num_processes,
                              args.process_id, args)
    if args.study_child:
        return study_child(args.coordinator, args.num_processes,
                           args.process_id, args)
    return child(args.coordinator, args.num_processes, args.process_id)


if __name__ == "__main__":
    sys.exit(main())
