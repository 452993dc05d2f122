"""Multi-process reduction backend: ``jax.distributed`` + explicit
collective axis, with the staged hop ladder running over REAL process
boundaries (DESIGN.md §3/§14/§17).

One JAX process per host (the paper's MPI rank), glued into a single
logical mesh by ``jax.distributed.initialize``.  After initialization
``jax.devices()`` spans every process, so the same shard_map machinery as
the single-process backend applies — the fused dot block's ``lax.psum``
now crosses host boundaries exactly like the paper's MPI_Iallreduce over
the world communicator.

Launch one process per host, all with the same coordinator::

    # host k of K:
    be = get_backend(
        "multiprocess",
        coordinator_address="10.0.0.1:1234",
        num_processes=K, process_id=k,
        reduction="staged", reduction_stages=2,
    )
    res = be.solve(op, b, method="plcg", l=3, sigmas=sig)

Single-process degradation: with no coordinator and one process, the
backend spans the local devices only (identical to ``shard_map``) — this
keeps the code path importable and testable in single-host CI containers
where no second process exists.

Cross-process hop transport (DESIGN.md §17)
-------------------------------------------
``reduction="staged"`` runs the SAME hop ladder as ``shard_map``
(``repro.parallel.reduction``): hop k of the ring allgather is one
``lax.ppermute`` inside a ``REDUCE_TAG{k}`` scope — a pure
point-to-point neighbour message, the tag being the wire protocol's hop
identity.  What this backend adds is the wire those hops ride:

* the ring permutation ``(i, i+1 mod P)`` is laid out over the GLOBAL
  device order, which jax keeps contiguous per process — so with R
  processes exactly R of the ring edges cross a process boundary every
  hop (``cross_process_edges``), and each crossing is one tagged
  point-to-point transfer on the ``jax.distributed`` transport: NCCL
  when the ranks hold GPUs, the gloo TCP backend on CPU hosts (selected
  by :func:`_configure_collectives` before initialization);
* compiled staged solves carry ZERO dot-block all-reduces across the
  wire — only tagged hop permutes plus the HALO_TAG traffic they
  stagger against, asserted across real process boundaries by
  scripts/multiprocess_parity.py and reproduced bitwise against the
  single-device ``virtual_shards`` ladder oracle (the PR 5 invariant,
  now crossing the wire: rank-ordered combine is transport-independent).

The ``supports_staged_reduction = False`` downgrade this backend carried
through PR 5–7 (and its ``ReductionFallbackWarning`` path) is GONE: the
ladder's static hop schedule — every rank executes the same ppermute
sequence with the same tags — is exactly the access pattern gloo's
connected-pair transport guarantees, which the cross-process bitwise
parity proves per CI run.  The ``backend_reduction_fallback`` gauge now
pins 0 for this backend (tests/test_fabric.py).

Batched multi-RHS serving (DESIGN.md §11) is inherited wholesale from
``ShardMapBackend``: ``solve_batched`` / ``make_slab_program`` stage the
same vmapped per-column programs, and the slab's (2l+1, s) dot-block
payload rides the cross-host wire exactly once per iteration — as ONE
psum (monolithic) or one ladder of per-hop messages (staged) — however
many requests are in flight.  The fused-iteration superkernel and the
donated slab state (DESIGN.md §13) are likewise inherited.
"""

from __future__ import annotations

import jax

from repro.parallel.backends.shard_map import ShardMapBackend
from repro.parallel.distributed import make_solver_mesh

# jax.distributed.initialize may only run once per process; repeated
# get_backend("multiprocess", coordinator_address=...) calls (the natural
# registry usage) must not re-initialize.
_DISTRIBUTED_INITIALIZED = False


def _configure_collectives() -> None:
    """Select the cross-process transport BEFORE ``initialize``.

    GPU ranks get NCCL automatically from jax.distributed; CPU ranks
    need the gloo TCP collectives backend for cross-host ppermute/psum
    (the default shared-memory CPU collectives cannot cross hosts).
    Setting the config after initialization is a no-op, hence this runs
    first (idempotent).
    """
    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def _ensure_initialized(**kwargs) -> None:
    global _DISTRIBUTED_INITIALIZED
    if _DISTRIBUTED_INITIALIZED:
        return
    _configure_collectives()
    try:
        jax.distributed.initialize(**kwargs)
    except RuntimeError as e:
        # Already initialized outside this module (user code, launcher):
        # adopt that runtime rather than failing.
        if "already" not in str(e).lower():
            raise
    _DISTRIBUTED_INITIALIZED = True


class MultiprocessBackend(ShardMapBackend):
    name = "multiprocess"

    # The staged ring ladder runs for real on this backend since
    # DESIGN.md §17: tagged per-hop ppermutes over the jax.distributed
    # transport (NCCL / gloo), bitwise vs the single-device ladder
    # oracle across real process boundaries.  (ReductionBackend defaults
    # this to True; restated here because its absence WAS the PR 5–7
    # capability downgrade.)
    supports_staged_reduction = True

    def __init__(
        self,
        coordinator_address: str | None = None,
        num_processes: int | None = None,
        process_id: int | None = None,
        local_device_ids=None,
        n_shards: int | None = None,
        jit: bool = True,
        reduction: str = "monolithic",
        reduction_stages: int = 2,
        reduction_dtype=None,
    ):
        if coordinator_address is not None:
            # Multi-controller mode: every process must execute the same
            # program; initialize() blocks until the full job is up.
            # Idempotent — a second backend instance adopts the existing
            # distributed runtime instead of re-initializing.
            _ensure_initialized(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
                local_device_ids=local_device_ids,
            )
        elif (num_processes or 1) > 1:
            raise ValueError(
                "multiprocess backend with num_processes > 1 needs a "
                "coordinator_address (jax.distributed.initialize)"
            )
        self.n_processes = num_processes or jax.process_count()
        # Global mesh: jax.devices() spans all processes after initialize.
        mesh = make_solver_mesh(n_shards, devices=jax.devices())
        super().__init__(mesh=mesh, jit=jit, reduction=reduction,
                         reduction_stages=reduction_stages,
                         reduction_dtype=reduction_dtype)

    # ------------------------------------------------- wire introspection --
    def hop_wire(self) -> str:
        """What carries one tagged ladder hop between ranks: ``"ici"``
        (TPU ranks in one slice, the chips' own interconnect), ``"dcn"``
        (TPU ranks across slices), ``"nccl"`` (GPU ranks), ``"gloo"``
        (CPU ranks over TCP), or ``"intra-process"`` when the whole mesh
        lives in this process (single-controller degradation — no wire
        at all)."""
        if self.n_processes <= 1:
            return "intra-process"
        devs = list(self.mesh.devices.flat)
        platforms = {d.platform for d in devs}
        if platforms == {"tpu"}:
            slices = {getattr(d, "slice_index", 0) for d in devs}
            return "dcn" if len(slices) > 1 else "ici"
        if platforms & {"gpu", "cuda", "rocm"}:
            return "nccl"
        if platforms == {"cpu"}:
            return "gloo"
        raise ValueError(f"no known hop wire for platforms {platforms}")

    def cross_process_edges(self) -> int:
        """Ring edges of the hop ladder that cross a process boundary —
        the per-hop count of REAL point-to-point wire transfers.  The
        mesh's device order is contiguous per process, so this equals
        the process count whenever more than one process participates
        (every rank's last device forwards to the next rank's first)."""
        devs = list(self.mesh.devices.flat)
        p = len(devs)
        return sum(
            devs[i].process_index != devs[(i + 1) % p].process_index
            for i in range(p)) if p > 1 else 0

    def describe(self) -> str:
        base = (
            f"multiprocess (jax.distributed, {self.n_processes} "
            f"process(es), {self.n_shards} global device(s), axis "
            f"'{self.axis}')"
        )
        if self.reduction_cfg is not None:
            cfg = self.reduction_cfg
            base += (
                f" staged ring dot block: {cfg.n_hops} hops / "
                f"{cfg.stages} stage(s), {self.cross_process_edges()} "
                f"cross-process edge(s)/hop over {self.hop_wire()}"
            )
        return base
