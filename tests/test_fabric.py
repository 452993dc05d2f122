"""Multi-node fabric launcher + multiprocess staged-capability tests
(DESIGN.md §17).

Two layers, both tier-1 (no RUN_MULTIPROCESS gate):

* ``repro.parallel.fabric`` — pure host-side process plumbing, tested
  with throwaway ``python -c`` children so every case runs in seconds:
  clean success, a rank dying mid-run (typed error, survivors killed —
  NOT a hang at the collective's timeout), the wall-clock watchdog, and
  the coordinator-port bind-collision retry.
* the PR 8 fallback removal — ``multiprocess`` now RUNS the staged hop
  ladder instead of downgrading it: capability flag True, no
  ``ReductionFallbackWarning``, the ``backend_reduction_fallback`` gauge
  pinned 0, and the single-process degradation bitwise against the
  ``local`` virtual-shards oracle.  The cross-process version of the
  same assertions lives in scripts/multiprocess_parity.py (CI
  ``multiprocess`` job).
"""

import os
import sys
import time
import warnings

import numpy as np
import pytest

from repro.parallel.fabric import (
    ENV_HEARTBEAT,
    SIGTERM_EXIT_CODE,
    FabricProcessError,
    FabricResult,
    FabricTimeoutError,
    free_port,
    launch_fabric,
    pick_coordinator,
    run_resilient,
    touch_heartbeat,
)


def _argv_script(body: str):
    """child_argv factory: every rank runs ``body`` with COORD/RANK
    interpolated (no jax import — fabric children here are throwaway)."""
    def child_argv(coordinator, k):
        code = body.replace("COORD", coordinator).replace("RANK", str(k))
        return [sys.executable, "-c", code]
    return child_argv


def test_free_port_is_bindable():
    import socket

    port = free_port()
    with socket.socket() as s:
        s.bind(("127.0.0.1", port))     # still free — nothing claimed it
    host, _, p = pick_coordinator().partition(":")
    assert host == "127.0.0.1" and int(p) > 0


def test_launch_fabric_success_collects_all_ranks():
    res = launch_fabric(
        _argv_script("print('rank RANK on COORD ok')"), 3, timeout_s=60,
        poll_s=0.05)
    assert isinstance(res, FabricResult)
    assert res.attempts == 1
    assert len(res.outputs) == 3
    for k, out in enumerate(res.outputs):
        assert f"rank {k} on {res.coordinator} ok" in out


def test_ranks_are_pinned_to_cpu_platform():
    # A parent holding an accelerator must never spawn ranks that try to
    # open it: every rank sees JAX_PLATFORMS=cpu whatever the parent has.
    env = dict(os.environ, JAX_PLATFORMS="tpu")
    res = launch_fabric(
        _argv_script("import os; print('plat', os.environ['JAX_PLATFORMS'])"),
        2, env=env, timeout_s=60, poll_s=0.05)
    assert all("plat cpu" in out for out in res.outputs), res.outputs


@pytest.mark.parametrize("platforms,slices,wire", [
    (("tpu", "tpu"), (0, 0), "ici"),
    (("tpu", "tpu"), (0, 1), "dcn"),
    (("gpu", "gpu"), (0, 0), "nccl"),
    (("cpu", "cpu"), (0, 0), "gloo"),
])
def test_hop_wire_names_the_transport(platforms, slices, wire):
    from types import SimpleNamespace

    from repro.parallel.backends.multiprocess import MultiprocessBackend

    devs = [SimpleNamespace(platform=p, slice_index=s)
            for p, s in zip(platforms, slices)]
    fake = SimpleNamespace(n_processes=2,
                           mesh=SimpleNamespace(devices=np.array(devs)))
    assert MultiprocessBackend.hop_wire(fake) == wire


def test_kill_one_process_raises_typed_error_not_hang():
    # Rank 1 dies almost immediately; rank 0 would sleep far past any
    # reasonable test budget — exactly a rank blocked in a collective
    # whose peer died.  The watchdog must kill it and raise the typed
    # error within ~poll_s of the death, never wait out the sleep.
    body = ("import sys, time\n"
            "if RANK == 1:\n"
            "    print('rank 1 dying', flush=True); sys.exit(3)\n"
            "time.sleep(120)\n")
    t0 = time.monotonic()
    with pytest.raises(FabricProcessError) as ei:
        launch_fabric(_argv_script(body), 2, timeout_s=300, poll_s=0.05)
    elapsed = time.monotonic() - t0
    assert elapsed < 30, f"watchdog took {elapsed:.1f}s — a hang"
    msg = str(ei.value)
    assert "rank 1 of 2 exited 3" in msg
    assert "survivors killed" in msg
    assert "rank 1 dying" in msg          # per-rank output tail attached
    # §18 forensics: every rank's detail line carries its exit status
    # and heartbeat age, so wedged vs dead is readable from the error.
    assert "last heartbeat" in msg
    assert "(exit 3," in msg


def test_wedged_rank_distinguished_from_slow_one():
    # Rank 0 heartbeats once at startup then blocks "in a collective";
    # rank 1 dies after the heartbeat has gone stale.  With a tight
    # wedge threshold the error must report rank 0 as WEDGED (alive but
    # heartbeat-silent) and rank 1 with its exit status.
    body = ("import os, sys, time\n"
            "hb = os.environ.get('" + ENV_HEARTBEAT + "')\n"
            "open(hb, 'a').close(); os.utime(hb, None)\n"
            "if RANK == 1:\n"
            "    time.sleep(1.5); sys.exit(5)\n"
            "time.sleep(120)\n")
    with pytest.raises(FabricProcessError) as ei:
        launch_fabric(_argv_script(body), 2, timeout_s=300, poll_s=0.05,
                      wedge_after_s=0.5)
    msg = str(ei.value)
    assert "rank 1 of 2 exited 5" in msg
    assert "(wedged," in msg              # rank 0: alive, heartbeat stale
    assert "(exit 5," in msg


def test_touch_heartbeat_helper(tmp_path):
    # Outside a fabric: no env var, clean no-op.
    assert touch_heartbeat({}) is None
    # Inside: touches (creates) the assigned file and returns its path.
    p = str(tmp_path / "rank0.hb")
    assert touch_heartbeat({ENV_HEARTBEAT: p}) == p
    import os
    assert os.path.exists(p)


def test_timeout_raises_typed_error_and_kills_group():
    t0 = time.monotonic()
    with pytest.raises(FabricTimeoutError) as ei:
        launch_fabric(_argv_script("import time; time.sleep(120)"), 2,
                      timeout_s=1.0, poll_s=0.05)
    assert time.monotonic() - t0 < 30
    assert "exceeded 1s" in str(ei.value)
    assert "[0, 1]" in str(ei.value)      # both ranks were still running


def test_bind_collision_retries_on_fresh_port(tmp_path):
    # First attempt: rank 0 reports the coordinator bind failure and
    # dies (the parallel-CI port race).  The launcher must relaunch the
    # WHOLE group on a fresh port; second attempt succeeds.  A flag file
    # makes the failure one-shot.
    flag = tmp_path / "collided_once"
    body = (f"import pathlib, sys\n"
            f"flag = pathlib.Path({str(flag)!r})\n"
            f"if RANK == 0 and not flag.exists():\n"
            f"    flag.touch()\n"
            f"    print('RuntimeError: Address already in use')\n"
            f"    sys.exit(1)\n"
            f"print('rank RANK up on COORD')\n")
    res = launch_fabric(_argv_script(body), 2, timeout_s=60, poll_s=0.05)
    assert res.attempts == 2
    assert all("up on" in o for o in res.outputs)
    assert res.coordinator in res.outputs[0]


def test_persistent_bind_collision_exhausts_retries():
    body = ("import sys\n"
            "print('bind address in use: errno: 98'); sys.exit(1)\n")
    with pytest.raises(FabricProcessError, match="persisted through"):
        launch_fabric(_argv_script(body), 1, timeout_s=60, poll_s=0.05,
                      max_port_retries=2)


# ---------------------------------------------------------------------------
# Graceful shutdown: SIGTERM flush handler + SIGKILL escalation (§19).
# ---------------------------------------------------------------------------

def test_sigterm_handler_flushes_before_exit(tmp_path):
    # Rank 1 dies; the launcher SIGTERMs the survivor, whose installed
    # handler must run its flush callbacks (telemetry/timeline in prod —
    # a sentinel file here) before exiting with the distinct 143 status.
    sentinel = tmp_path / "flushed_rank0"
    armed = tmp_path / "armed_rank0"
    # Rank 1 dies only once rank 0 has armed its handler: a SIGTERM that
    # lands while rank 0 is still importing would test nothing.
    body = (f"import os, sys, time\n"
            f"from repro.parallel.fabric import install_sigterm_handler\n"
            f"if RANK == 1:\n"
            f"    t = time.monotonic()\n"
            f"    while not os.path.exists({str(armed)!r}) "
            f"and time.monotonic() - t < 30:\n"
            f"        time.sleep(0.02)\n"
            f"    sys.exit(7)\n"
            f"install_sigterm_handler(\n"
            f"    lambda: open({str(sentinel)!r}, 'w').write('flushed'))\n"
            f"open({str(armed)!r}, 'w').close()\n"
            f"print('handler armed', flush=True)\n"
            f"time.sleep(120)\n")
    import os

    import repro
    src = os.path.dirname(os.path.dirname(os.path.abspath(repro.__file__)))
    with pytest.raises(FabricProcessError, match="rank 1 of 2 exited 7"):
        launch_fabric(_argv_script(body), 2, timeout_s=60, poll_s=0.05,
                      env=dict(os.environ, PYTHONPATH=src),
                      term_grace_s=5.0)
    # The survivor was torn down via SIGTERM within the grace window, so
    # its flush ran — the sentinel proves buffered observability state
    # would have hit disk.
    assert sentinel.exists() and sentinel.read_text() == "flushed"
    assert SIGTERM_EXIT_CODE == 143


def test_sigkill_escalation_for_sigterm_ignoring_rank(tmp_path):
    # A rank that ignores SIGTERM (wedged in native code, masked signal)
    # must not hang teardown: after ``term_grace_s`` the watchdog
    # escalates to SIGKILL and the typed error still surfaces promptly.
    body = ("import signal, sys, time\n"
            "if RANK == 1:\n"
            "    sys.exit(9)\n"
            "signal.signal(signal.SIGTERM, signal.SIG_IGN)\n"
            "print('sigterm ignored', flush=True)\n"
            "time.sleep(120)\n")
    t0 = time.monotonic()
    with pytest.raises(FabricProcessError, match="rank 1 of 2 exited 9"):
        launch_fabric(_argv_script(body), 2, timeout_s=300, poll_s=0.05,
                      term_grace_s=0.5)
    elapsed = time.monotonic() - t0
    assert elapsed < 30, (f"teardown took {elapsed:.1f}s — SIGKILL "
                          "escalation did not fire")


# ---------------------------------------------------------------------------
# run_resilient: respawn-and-resume orchestration (DESIGN.md §19).
# ---------------------------------------------------------------------------

def _resilient_argv(body: str):
    """child_argv factory for run_resilient: COORD/RANK/NPROC/ATTEMPT
    interpolated into a throwaway ``python -c`` child."""
    def child_argv(coordinator, k, num_processes, attempt):
        code = (body.replace("COORD", coordinator).replace("RANK", str(k))
                .replace("NPROC", str(num_processes))
                .replace("ATTEMPT", str(attempt)))
        return [sys.executable, "-c", code]
    return child_argv


def test_run_resilient_respawns_after_one_failure():
    # Attempt 1: rank 1 dies (the drill's killed rank).  run_resilient
    # must tear the fabric down, record the typed failure, and relaunch
    # the FULL group; attempt 2 succeeds.
    body = ("import sys\n"
            "if ATTEMPT == 1 and RANK == 1:\n"
            "    sys.exit(11)\n"
            "print('rank RANK attempt ATTEMPT ok', flush=True)\n")
    rr = run_resilient(_resilient_argv(body), 2, max_failures=1,
                       timeout_s=60, poll_s=0.05)
    assert rr.attempts == 2
    assert len(rr.failures) == 1
    assert isinstance(rr.failures[0], FabricProcessError)
    assert rr.failures[0].failed_rank == 1
    assert rr.procs_per_attempt == [2, 2]       # no shrink: full respawn
    assert isinstance(rr.result, FabricResult)
    assert all("attempt 2 ok" in o for o in rr.result.outputs)


def test_run_resilient_attempt_env_arms_first_attempt_only():
    # The drill pattern: the chaos fault plan is injected via env on
    # attempt 1 ONLY, so the respawned fabric runs clean.
    body = ("import os, sys\n"
            "if os.environ.get('FAULT_ARMED') and RANK == 0:\n"
            "    sys.exit(13)\n"
            "print('rank RANK clean', flush=True)\n")
    seen = []

    def attempt_env(attempt):
        seen.append(attempt)
        return {"FAULT_ARMED": "1"} if attempt == 1 else {}

    rr = run_resilient(_resilient_argv(body), 2, max_failures=1,
                       attempt_env=attempt_env, timeout_s=60, poll_s=0.05)
    assert seen == [1, 2]
    assert rr.attempts == 2 and len(rr.failures) == 1
    assert rr.failures[0].failed_rank == 0
    assert all("clean" in o for o in rr.result.outputs)


def test_run_resilient_shrink_drops_to_min_processes():
    # Degraded-capacity mode: every attempt with >1 rank fails, so the
    # fabric shrinks one rank per failure until it reaches
    # ``min_processes`` and succeeds there.
    body = ("import sys\n"
            "if NPROC > 1:\n"
            "    sys.exit(17)\n"
            "print('rank RANK solo ok', flush=True)\n")
    rr = run_resilient(_resilient_argv(body), 3, max_failures=2,
                       shrink=True, min_processes=1, timeout_s=60,
                       poll_s=0.05)
    assert rr.procs_per_attempt == [3, 2, 1]
    assert rr.attempts == 3 and len(rr.failures) == 2
    assert len(rr.result.outputs) == 1
    assert "solo ok" in rr.result.outputs[0]


def test_run_resilient_exhausted_budget_reraises():
    body = "import sys; sys.exit(19)\n"
    t0 = time.monotonic()
    with pytest.raises(FabricProcessError, match="exited 19"):
        run_resilient(_resilient_argv(body), 2, max_failures=1,
                      timeout_s=60, poll_s=0.05)
    assert time.monotonic() - t0 < 60


# ---------------------------------------------------------------------------
# Fallback removal: multiprocess RUNS the staged ladder (DESIGN.md §17).
# ---------------------------------------------------------------------------

def test_multiprocess_supports_staged_reduction_flag():
    from repro.parallel.backends.multiprocess import MultiprocessBackend

    # THE PR 8 regression guard: the PR 5–7 capability downgrade
    # (supports_staged_reduction = False + warning + monolithic fallback)
    # is gone for good.
    assert MultiprocessBackend.supports_staged_reduction is True


def test_multiprocess_staged_runs_without_fallback():
    import jax.numpy as jnp

    from repro.obs.metrics import default_registry
    from repro.parallel import get_backend
    from repro.parallel.reduction import ReductionFallbackWarning

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        be = get_backend("multiprocess", reduction="staged",
                         reduction_stages=1)
    assert not any(isinstance(w.message, ReductionFallbackWarning)
                   for w in caught), [str(w.message) for w in caught]
    assert be.reduction_mode == "staged"
    assert be.reduction_fallback is None
    assert be.reduction_cfg is not None
    gauge = default_registry().get("backend_reduction_fallback")
    assert gauge is not None
    assert gauge.value(labels={"backend": "multiprocess"}) == 0.0
    # Single-process degradation: no second controller in tier-1, so the
    # wire introspection reports the degenerate case honestly.
    assert be.n_processes == 1
    assert be.hop_wire() == "intra-process"
    assert be.cross_process_edges() == 0
    assert "staged ring dot block" in be.describe()

    # ... and the ladder actually runs: bitwise vs the local
    # virtual-shards oracle at the same ring size and stage count.
    from repro.core.chebyshev import shifts_for_operator
    from repro.linalg import Stencil2D5

    op = Stencil2D5(16, 12)
    b = jnp.asarray(np.random.default_rng(5).standard_normal(op.n))
    sig = shifts_for_operator(op, 2)
    kw = dict(method="plcg", l=2, sigmas=sig, tol=1e-10, maxit=400)
    res = be.solve(op, b, **kw)
    oracle = get_backend("local", reduction="staged",
                         virtual_shards=be.n_shards, reduction_stages=1)
    res_o = oracle.solve(op, b, **kw)
    h, ho = np.asarray(res.res_history), np.asarray(res_o.res_history)
    assert np.array_equal(h, ho)
    assert bool(res.converged)
