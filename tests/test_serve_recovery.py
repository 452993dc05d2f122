"""Self-healing serve workers (DESIGN.md §19).

A faulted :class:`SlabWorker` (injected :class:`WorkerFault` here; a
real device/runtime error in prod) must be torn down — never left
half-alive holding slab capacity — and every in-flight column
resubmitted through the retry policy with a fresh SLO window.  The
contract under test:

* all in-flight requests of the dead worker retire CONVERGED after the
  respawn (healing is invisible to the client, just slower);
* the resubmission path is metrics-counted (``worker_deaths``,
  ``resubmitted``) and forensically logged (:class:`DeathEvent`);
* respawn reuses the compiled-program cache — a worker death must not
  pay a recompile;
* the whole sequence is deterministic under :class:`VirtualClock`
  (two identical runs produce identical metrics snapshots);
* exhausted retries shed (typed, accounted) instead of looping forever.
"""

import numpy as np
import pytest

from repro.linalg import Stencil2D5
from repro.parallel import get_backend
from repro.serve import RetryPolicy, SolverService, VirtualClock
from repro.serve.errors import WorkerFault
from repro.serve.scheduler import WORKER_FAULT_TYPES, DeathEvent


def _run(fault_tick, max_retries=3, n_req=4):
    """One full drain with a one-shot WorkerFault at ``fault_tick``."""
    op = Stencil2D5(12, 12)
    state = {"fired": False}

    def injector(tick, worker):
        if tick == fault_tick and not state["fired"]:
            state["fired"] = True
            raise WorkerFault(f"injected at tick {tick}")

    svc = SolverService(get_backend("local"), s=4, method="plcg", l=2,
                        chunk_iters=25, maxit=600, clock=VirtualClock(),
                        retry=RetryPolicy(max_retries=max_retries),
                        fault_injector=injector)
    svc.register_operator("lap", op)
    rng = np.random.default_rng(3)
    ids = [svc.submit("lap", rng.standard_normal(op.n))
           for _ in range(n_req)]
    results = svc.drain()
    return svc, ids, results, state


def test_worker_fault_heals_and_all_requests_converge():
    svc, ids, results, state = _run(fault_tick=2)
    assert state["fired"], "injector never fired"
    # One death, all four in-flight columns resubmitted, none shed.
    assert svc.worker_deaths == 1
    assert svc.resubmitted == 4
    for rid in ids:
        rr = results[rid]
        assert rr.converged and not rr.shed, (rid, rr.shed)
    st = svc.stats()
    assert st["worker_deaths"] == 1 and st["resubmitted"] == 4
    assert st["retired"] == 4 and st["shed"] == 0


def test_death_event_forensics():
    svc, ids, _, _ = _run(fault_tick=2)
    log = svc.scheduler.death_log
    assert len(log) == 1
    ev = log[0]
    assert isinstance(ev, DeathEvent)
    assert ev.tick == 2
    assert sorted(ev.req_ids) == sorted(ids)    # every in-flight column
    assert "injected at tick 2" in ev.reason


def test_respawn_reuses_compiled_programs():
    # The respawned worker must not recompile: the key's program stays
    # in the scheduler's program table across the death, and the run
    # pays exactly as many setup-cache misses (unique compilations) as a
    # fault-free run of the same shape.
    svc, _, _, _ = _run(fault_tick=2)
    assert svc.worker_deaths == 1               # a respawn happened...
    assert len(svc.scheduler._programs) == 1    # ...off the cached program
    svc_clean, _, _, _ = _run(fault_tick=-1)    # never fires
    assert (svc.stats()["setup_cache"]["misses"]
            == svc_clean.stats()["setup_cache"]["misses"])


def test_recovery_is_deterministic_under_virtual_clock():
    svc1, _, _, _ = _run(fault_tick=2)
    svc2, _, _, _ = _run(fault_tick=2)
    assert svc1.metrics_snapshot() == svc2.metrics_snapshot()


def test_exhausted_retries_shed_not_loop():
    svc, ids, results, state = _run(fault_tick=2, max_retries=0)
    assert state["fired"]
    shed = [rid for rid in ids if results[rid].shed]
    assert len(shed) == 4
    assert svc.resubmitted == 0                 # no budget: straight to shed
    assert svc.shed == 4
    assert svc.worker_deaths == 1


def test_worker_fault_is_typed_and_classified():
    # WorkerFault must be catchable as a ServeError AND recognised by the
    # scheduler's fault taxonomy (heal), unlike a programming bug
    # (propagate).
    from repro.serve.errors import ServeError

    assert issubclass(WorkerFault, ServeError)
    assert WorkerFault in WORKER_FAULT_TYPES
    assert not any(issubclass(TypeError, t) for t in WORKER_FAULT_TYPES)


def test_programming_bug_propagates_not_healed():
    op = Stencil2D5(12, 12)

    def injector(tick, worker):
        if tick == 1:
            raise TypeError("a bug, not a fault")

    svc = SolverService(get_backend("local"), s=4, method="plcg", l=2,
                        chunk_iters=25, maxit=600, clock=VirtualClock(),
                        retry=RetryPolicy(max_retries=3),
                        fault_injector=injector)
    svc.register_operator("lap", op)
    svc.submit("lap", np.random.default_rng(0).standard_normal(op.n))
    with pytest.raises(TypeError, match="a bug"):
        svc.drain()


def test_device_runtime_error_propagates_not_shed():
    # A compile or device fault surfaces as a jax runtime error: it must
    # reach the caller, never be healed into resubmit-then-shed.
    import jax

    op = Stencil2D5(12, 12)

    def injector(tick, worker):
        raise jax.errors.JaxRuntimeError("device fault")

    svc = SolverService(get_backend("local"), s=4, method="plcg", l=2,
                        chunk_iters=25, maxit=600, clock=VirtualClock(),
                        retry=RetryPolicy(max_retries=3),
                        fault_injector=injector)
    svc.register_operator("lap", op)
    svc.submit("lap", np.random.default_rng(0).standard_normal(op.n))
    with pytest.raises(jax.errors.JaxRuntimeError, match="device fault"):
        svc.drain()
    assert svc.worker_deaths == 0 and svc.shed == 0


def test_reset_stats_clears_recovery_counters():
    svc, _, _, _ = _run(fault_tick=2)
    assert svc.resubmitted == 4 and svc.worker_deaths == 1
    svc.reset_stats()
    assert svc.resubmitted == 0
    assert svc.stats()["resubmitted"] == 0
