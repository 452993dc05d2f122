"""Multi-node fabric launcher: typed process-group life cycle for
``jax.distributed`` jobs (DESIGN.md §17).

The multiprocess backend (``repro.parallel.backends.multiprocess``) is a
*multi-controller* substrate: every rank runs the same program and the
collectives — the staged hop ladder's tagged ppermutes included — block
until every peer participates.  That SPMD discipline has two failure
modes a CI fabric must convert into clean errors instead of hangs:

* **coordinator port collision** — ``jax.distributed.initialize`` binds
  a fixed TCP port; two jobs racing for the same port (parallel CI
  shards) make one of them die at startup.  :func:`launch_fabric`
  allocates a fresh ephemeral port per attempt and RETRIES the whole
  group when a child's output shows a bind failure.
* **peer death** — a rank that dies mid-solve leaves every other rank
  blocked inside a gloo/NCCL collective with no timeout of its own.
  The launcher polls the group; the moment any child exits nonzero it
  kills the survivors and raises :class:`FabricProcessError` (or
  :class:`FabricTimeoutError` when the wall-clock budget runs out) —
  the kill-one-process test in tests/test_fabric.py asserts the error
  arrives in seconds, not at the collective's 900 s budget.

The module is pure host-side process plumbing (subprocess + sockets, no
jax import) so it stays importable — and testable — on any container.
"""

from __future__ import annotations

import dataclasses
import os
import shutil
import signal
import socket
import subprocess
import tempfile
import time
from typing import Callable, Sequence

# Output fragments that identify a coordinator bind collision — the one
# startup failure that is retryable by construction (fresh port, same
# program).  Matched case-insensitively against a dead child's output.
BIND_COLLISION_MARKERS = (
    "address already in use",
    "failed to bind",
    "errno: 98",
    "bind address",
)

# Env var carrying rank k's heartbeat file path.  The launcher sets it
# per rank; children call :func:`touch_heartbeat` at progress points
# (startup, per solve chunk) so the error messages can distinguish a
# WEDGED rank (alive but silent — e.g. blocked in a collective) from a
# dead or merely slow one by the age of its last heartbeat.
ENV_HEARTBEAT = "REPRO_FABRIC_HEARTBEAT"

# Exit status a rank reports when it shut down cleanly on the launcher's
# SIGTERM (128 + SIGTERM, the shell convention) — distinct from a crash
# and from SIGKILL's 137, so post-mortems can tell "peer died, launcher
# tore me down gracefully" from "I am the one that died".
SIGTERM_EXIT_CODE = 143


def install_sigterm_handler(*flushes: Callable[[], None],
                            exit_code: int = SIGTERM_EXIT_CODE) -> None:
    """Child-side graceful-teardown hook: on SIGTERM, run the ``flushes``
    (telemetry ring dumps, ``Timeline.save`` closures, ...) then exit
    with ``exit_code``.

    The launcher's :func:`_kill_all` sends SIGTERM first and escalates
    to SIGKILL after a grace period — installing this handler is what
    turns a survivor's teardown from hard data loss into a flushed,
    distinct-status exit (DESIGN.md §19).  Flush errors are swallowed:
    a failing flush must not block the group teardown.
    """

    def _on_term(signum, frame):
        for fn in flushes:
            try:
                fn()
            except Exception:
                pass
        os._exit(exit_code)

    signal.signal(signal.SIGTERM, _on_term)


def touch_heartbeat(environ=None) -> str | None:
    """Child-side progress marker: touch the heartbeat file the launcher
    assigned this rank (``ENV_HEARTBEAT``).  No-op (returns None) when
    running outside a fabric; cheap enough to call per chunk."""
    env = os.environ if environ is None else environ
    path = env.get(ENV_HEARTBEAT)
    if not path:
        return None
    with open(path, "a"):
        os.utime(path, None)
    return path


def _heartbeat_age(path: str | None, now: float, spawned: float) -> float:
    """Seconds since the rank last touched its heartbeat file; falls back
    to time-since-spawn when the rank never touched it."""
    if path:
        try:
            return max(now - os.path.getmtime(path), 0.0)
        except OSError:
            pass
    return max(now - spawned, 0.0)


def _rank_status(code: int | None, hb_age: float, wedge_after_s: float
                 ) -> str:
    """One human line per rank: exit status + heartbeat age.  ``wedged``
    means alive but heartbeat-silent past the threshold — the signature
    of a rank blocked in a collective whose peer died."""
    if code is None:
        state = "wedged" if hb_age > wedge_after_s else "running"
    else:
        state = f"exit {code}"
    return f"{state}, last heartbeat {hb_age:.1f}s ago"


class FabricError(RuntimeError):
    """Base class for multi-process fabric failures."""


class FabricTimeoutError(FabricError):
    """The process group exceeded its wall-clock budget: at least one
    rank was still running (typically blocked inside a collective whose
    peer never arrived) when the launcher's watchdog fired.  Survivors
    are killed before this is raised — no orphan ranks."""


class FabricProcessError(FabricError):
    """A rank exited nonzero (or was killed) while its peers were still
    running.  The launcher kills the survivors — who would otherwise
    hang in their next collective waiting for the dead peer — and
    reports which rank failed plus the tail of every rank's output."""


@dataclasses.dataclass
class FabricResult:
    """Outputs of one successful fabric run."""

    outputs: list[str]            # per-rank combined stdout/stderr
    coordinator: str              # "host:port" the group actually used
    attempts: int                 # 1 + bind-collision retries


def free_port(host: str = "127.0.0.1") -> int:
    """One ephemeral port, currently free.  Inherently racy — another
    process may claim it before the coordinator binds — which is why
    :func:`launch_fabric` retries bind collisions instead of trusting
    this value."""
    with socket.socket() as s:
        s.bind((host, 0))
        return s.getsockname()[1]


def pick_coordinator(host: str = "127.0.0.1") -> str:
    return f"{host}:{free_port(host)}"


def _tail(text: str, n: int = 2000) -> str:
    return text[-n:] if len(text) > n else text


def _kill_all(procs: Sequence[subprocess.Popen],
              grace_s: float = 2.0) -> list[str]:
    """Tear down survivors and drain outputs: SIGTERM every live rank
    (letting :func:`install_sigterm_handler` flush telemetry/timeline
    buffers and exit with a distinct status), then escalate to SIGKILL
    for whoever is still alive after ``grace_s``.  Idempotent: the
    launcher's ``finally`` re-runs it after the error paths already
    have."""
    live = [p for p in procs if p.poll() is None]
    for p in live:
        try:
            p.terminate()
        except OSError:
            pass
    deadline = time.monotonic() + max(grace_s, 0.0)
    while live and time.monotonic() < deadline:
        live = [p for p in live if p.poll() is None]
        if live:
            time.sleep(0.05)
    for p in live:
        try:
            p.kill()
        except OSError:
            pass
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=30)
        except (subprocess.TimeoutExpired, ValueError, OSError):
            out = ""                # already drained / stream closed
        outs.append(out or "")
    return outs


def _looks_like_bind_collision(output: str) -> bool:
    low = output.lower()
    return any(m in low for m in BIND_COLLISION_MARKERS)


def launch_fabric(
    child_argv: Callable[[str, int], list[str]],
    num_processes: int,
    *,
    env: dict | None = None,
    timeout_s: float = 900.0,
    poll_s: float = 0.2,
    max_port_retries: int = 3,
    host: str = "127.0.0.1",
    wedge_after_s: float = 5.0,
    term_grace_s: float = 2.0,
) -> FabricResult:
    """Run one multi-controller process group to completion.

    ``child_argv(coordinator, process_id)`` builds rank k's argv; every
    rank is spawned with ``env`` (default: the launcher's environment)
    with ``JAX_PLATFORMS=cpu`` forced, plus a per-rank
    ``ENV_HEARTBEAT`` file path (stdout+stderr merged,
    text mode).  Children that call :func:`touch_heartbeat` at progress
    points get per-rank "last heartbeat N s ago" lines in every fabric
    error — a surviving rank whose heartbeat is older than
    ``wedge_after_s`` is reported ``wedged`` (alive but stuck, the
    blocked-collective signature) rather than merely ``running``.
    The launcher supervises:

    * all ranks exit 0 → :class:`FabricResult` with per-rank outputs;
    * any rank exits nonzero → survivors killed; if the dead rank's
      output shows a coordinator bind collision
      (``BIND_COLLISION_MARKERS``) the whole group relaunches on a
      fresh port, up to ``max_port_retries`` times; otherwise
      :class:`FabricProcessError`;
    * ``timeout_s`` elapses → survivors killed, :class:`FabricTimeoutError`.

    The watchdog property under test in tests/test_fabric.py: killing
    one rank mid-run produces a typed error within ~``poll_s`` of the
    death, never a hang at the full ``timeout_s``.
    """
    if num_processes < 1:
        raise ValueError(f"num_processes must be >= 1, got {num_processes}")
    last_outputs: list[str] = []
    # Ranks are CPU/gloo processes by design.  Pin their platform: on a
    # host with an accelerator, children inheriting the parent's
    # environment would each try to open the chip the parent holds.
    base_env = {**(os.environ if env is None else env),
                "JAX_PLATFORMS": "cpu"}
    for attempt in range(1, max_port_retries + 2):
        coordinator = pick_coordinator(host)
        hb_dir = tempfile.mkdtemp(prefix="repro-fabric-hb-")
        hb_paths = [os.path.join(hb_dir, f"rank{k}.hb")
                    for k in range(num_processes)]
        spawned = time.time()
        procs = [
            subprocess.Popen(
                child_argv(coordinator, k),
                env={**base_env, ENV_HEARTBEAT: hb_paths[k]},
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            )
            for k in range(num_processes)
        ]
        deadline = time.monotonic() + timeout_s

        def statuses(codes):
            now = time.time()
            return [
                _rank_status(codes[k],
                             _heartbeat_age(hb_paths[k], now, spawned),
                             wedge_after_s)
                for k in range(num_processes)
            ]

        try:
            while True:
                codes = [p.poll() for p in procs]
                if all(c == 0 for c in codes):
                    outs = [p.communicate()[0] or "" for p in procs]
                    return FabricResult(outputs=outs,
                                        coordinator=coordinator,
                                        attempts=attempt)
                dead = [(k, c) for k, c in enumerate(codes)
                        if c is not None and c != 0]
                if dead:
                    # Snapshot status BEFORE killing survivors: the exit
                    # codes and heartbeat ages at detection time are the
                    # diagnosis, not the post-kill wreckage.
                    stat = statuses(codes)
                    outs = _kill_all(procs, term_grace_s)
                    last_outputs = outs
                    k0, c0 = dead[0]
                    if _looks_like_bind_collision(outs[k0]):
                        # Relaunch the group on a fresh port; when this
                        # was the last allowed attempt the for-loop ends
                        # and the persisted-collision error below fires.
                        break
                    detail = "\n".join(
                        f"--- rank {k} ({stat[k]}) ---\n{_tail(outs[k])}"
                        for k in range(num_processes))
                    err = FabricProcessError(
                        f"rank {k0} of {num_processes} exited {c0} while "
                        f"peers were running (coordinator {coordinator}); "
                        f"survivors killed to avoid a collective hang\n"
                        f"{detail}")
                    # Full (undisplayed) outputs ride on the error so a
                    # recovery supervisor can harvest child markers —
                    # checkpoint paths, kill iterations — post-mortem.
                    err.outputs = outs
                    err.failed_rank = k0
                    raise err
                if time.monotonic() > deadline:
                    stat = statuses(codes)
                    outs = _kill_all(procs, term_grace_s)
                    running = [k for k, c in enumerate(codes) if c is None]
                    err = FabricTimeoutError(
                        f"fabric of {num_processes} rank(s) exceeded "
                        f"{timeout_s:.0f}s (ranks {running} still running, "
                        f"coordinator {coordinator}); group killed\n"
                        + "\n".join(
                            f"--- rank {k} ({stat[k]}) ---\n{_tail(o)}"
                            for k, o in enumerate(outs)))
                    err.outputs = outs
                    err.failed_rank = running[0] if running else None
                    raise err
                time.sleep(poll_s)
        finally:
            _kill_all(procs, term_grace_s)
            shutil.rmtree(hb_dir, ignore_errors=True)
    raise FabricProcessError(
        f"coordinator bind collision persisted through "
        f"{max_port_retries} port retries\n"
        + "\n".join(f"--- rank {k} ---\n{_tail(o)}"
                    for k, o in enumerate(last_outputs)))


@dataclasses.dataclass
class RecoveryResult:
    """Outcome of a :func:`run_resilient` supervision."""

    result: FabricResult          # the attempt that completed
    attempts: int                 # fabric launches, including the last
    failures: list[FabricError]   # one per failed attempt, in order
    procs_per_attempt: list[int]  # group size of each attempt


def run_resilient(
    child_argv: Callable[[str, int, int, int], list[str]],
    num_processes: int,
    *,
    max_failures: int = 1,
    shrink: bool = False,
    min_processes: int = 1,
    env: dict | None = None,
    attempt_env: Callable[[int], dict] | None = None,
    **launch_kw,
) -> RecoveryResult:
    """Elastic fabric supervisor (DESIGN.md §19 recovery state machine).

    Runs ``launch_fabric`` and, on :class:`FabricProcessError` /
    :class:`FabricTimeoutError` (a dead or wedged rank — survivors are
    already torn down by the launcher), RESPAWNS a fresh process group:
    a new coordinator port, new gloo/NCCL rendezvous, and — because each
    child rebuilds its backend from the operator — a fresh partition of
    the problem via the existing ``PartitionPlan`` machinery.  Children
    that checkpoint (``CheckpointConfig(..., resume=True)`` on a shared
    directory) resume the solve from the last snapshot instead of from
    zero; ``multiprocess_parity.py --recovery`` is the end-to-end drill.

    ``child_argv(coordinator, process_id, num_processes, attempt)``
    builds rank k's argv — the extended signature (vs ``launch_fabric``)
    is what lets a shrunk regroup tell its children the new world size.
    ``shrink=True`` drops one rank per failure (never below
    ``min_processes``) — elastic downsizing for hardware that stays
    dead.  ``attempt_env(attempt)`` merges attempt-specific variables
    (e.g. a chaos plan armed only on the first attempt) over ``env``.
    Exhausting ``max_failures`` re-raises the last fabric error.
    """
    failures: list[FabricError] = []
    procs_hist: list[int] = []
    procs = num_processes
    for attempt in range(1, max_failures + 2):
        procs_hist.append(procs)
        aenv = dict(os.environ if env is None else env)
        if attempt_env is not None:
            aenv.update(attempt_env(attempt))
        p, a = procs, attempt

        def argv(coordinator: str, k: int, _p=p, _a=a) -> list[str]:
            return child_argv(coordinator, k, _p, _a)

        try:
            result = launch_fabric(argv, procs, env=aenv, **launch_kw)
            return RecoveryResult(result=result, attempts=attempt,
                                  failures=failures,
                                  procs_per_attempt=procs_hist)
        except (FabricProcessError, FabricTimeoutError) as e:
            failures.append(e)
            if attempt > max_failures:
                raise
            if shrink and procs > min_processes:
                procs -= 1
    raise AssertionError("unreachable")
