"""Staged ring reduction: the fused dot block as an explicit ladder of
``lax.ppermute`` hops the SOLVER advances (DESIGN.md §14).

The monolithic path hands the (2l+1, s) dot-block payload to one
``lax.psum`` and hopes XLA's scheduler hoists it across the l-iteration
in-flight window.  The paper's mechanism is stronger than a hope: the
global reduction may take up to l iterations to complete
(arXiv:1801.04728), and the Cori runs (arXiv:1905.06850) win by
staggering reduction *phases* against SPMV and halo traffic.  This
module makes the reduction's progress structural:

  * ``staged_start``   — local partials parked in a (P, K, ...) gather
                         buffer, own slot filled (the MPI_Iallreduce
                         post; no wire traffic yet);
  * ``staged_advance`` — ONE ladder step: the scheduled ring hops of
                         that step move neighbour partials one shard
                         around the ring (``REDUCE_TAG``-tagged
                         ``ppermute``; interleaves with ``HALO_TAG``
                         traffic inside the open window);
  * ``staged_wait``    — run whatever steps the solver has not yet
                         advanced, then reduce the gathered partials IN
                         RANK ORDER (the MPI_Wait + combine).

The ladder is a ring ALLGATHER of raw per-shard partials — the P-1 hops
only move data; all arithmetic happens at the wait, summing the P
partials in ascending shard order.  Two properties fall out:

1.  **Stage-count invariance.**  ``stages`` only groups the P-1 hops
    into advance steps (scheduling); the summation the wait performs is
    identical for every stage count, so residual histories are bitwise
    identical across ladder configurations.
2.  **Monolithic parity.**  The rank-ordered sum reproduces the
    deterministic linear reduction order of XLA's CPU all-reduce, so
    staged and monolithic runs agree BITWISE on stencil operators
    (asserted in tests/test_distributed.py; FEM meshes follow the PR 3
    tight-head/bounded-tail convention because their local partials
    already differ at ULP level between substrates).

Mixed precision (``payload_dtype=jnp.float32``): partials are rounded to
fp32 *once* at the start site — every wire hop then carries half the
bytes — and the wait accumulates the gathered fp32 partials into an
fp64 compensated (Kahan) sum, so the squashed-payload error stays at
one fp32 rounding per shard partial instead of growing with P
(DESIGN.md §14 error bound; bounded-tail parity in
tests/test_reduction.py / test_distributed.py).

The local backend runs the same arithmetic as an eager *ladder oracle*
(``oracle_solver_ops``): the vector is split into ``virtual_shards``
contiguous slices whose partials fill the gather buffer directly — no
wire, identical summation tree — which makes a single-device run the
bitwise reference for a staged mesh run of the same shard count.
"""

from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.types import REDUCE_TAG, dot_block_rows


class ReductionFallbackWarning(UserWarning):
    """A backend silently CANNOT run the requested staged ring ladder and
    downgraded to the monolithic all-reduce.  Arithmetic is still honoured
    — only the overlap mechanism is lost — but a scaling study run under
    this warning is not measuring what it thinks it is, hence a real
    warning (and a ``backend_reduction_fallback`` gauge on the default
    metrics registry) rather than just an attribute."""


@dataclasses.dataclass(frozen=True)
class StagedConfig:
    """Shape of one staged ring reduction.

    ``n_shards`` is the ring size P; ``stages`` groups the P-1 allgather
    hops into that many advance steps (``hop_groups``); ``payload_dtype``
    is the wire dtype (None = the solver dtype); a payload narrower than
    the solver dtype switches the wait to fp64 compensated accumulation.
    ``axis`` is the mesh axis name (None = the local eager oracle).
    """

    n_shards: int
    stages: int = 2
    payload_dtype: Any = None
    axis: str | None = None

    def __post_init__(self):
        if self.n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {self.n_shards}")
        if not (1 <= self.stages <= max(self.n_shards - 1, 1)):
            raise ValueError(
                f"stages must be in [1, {max(self.n_shards - 1, 1)}] "
                f"for {self.n_shards} shards, got {self.stages}")

    @property
    def n_hops(self) -> int:
        """Wire hops of one reduction: the P-1 ring-allgather permutes."""
        return self.n_shards - 1

    def wire_dtype(self, solver_dtype) -> Any:
        return solver_dtype if self.payload_dtype is None \
            else jnp.dtype(self.payload_dtype)

    def compensated(self, solver_dtype) -> bool:
        """fp64-compensated wait accumulation when the wire narrows."""
        wire = self.wire_dtype(solver_dtype)
        return jnp.dtype(wire).itemsize < jnp.dtype(solver_dtype).itemsize


def hop_groups(n_shards: int, stages: int) -> list[list[int]]:
    """Partition the ring's ``n_shards - 1`` hop indices into ``stages``
    contiguous advance steps, earlier steps no smaller than later ones
    (ceil-split) so the ladder front-loads while the window is widest."""
    n_hops = n_shards - 1
    groups: list[list[int]] = []
    start = 0
    for step in range(stages):
        size = math.ceil((n_hops - start) / (stages - step))
        groups.append(list(range(start, start + size)))
        start += size
    assert start == n_hops, (n_shards, stages, groups)
    return groups


# --------------------------------------------------------------------------
# Distributed ladder (inside shard_map).
# --------------------------------------------------------------------------

def staged_start(partials: jax.Array, cfg: StagedConfig) -> jax.Array:
    """Park this shard's dot-block partials in a fresh gather buffer.

    ``partials`` is the local (K,)/(K, s) contribution; the handle is a
    (P, K[, s]) buffer in the wire dtype with the own-rank slot filled —
    the posted-but-unprogressed Iallreduce.  No collective is issued
    here: the wire traffic is the solver-advanced hops.
    """
    wire = cfg.wire_dtype(partials.dtype)
    buf = jnp.zeros((cfg.n_shards,) + partials.shape, wire)
    r = lax.axis_index(cfg.axis)
    return lax.dynamic_update_index_in_dim(
        buf, partials.astype(wire), r, axis=0)


def staged_advance(handle: jax.Array, step: int,
                   cfg: StagedConfig) -> jax.Array:
    """Run advance step ``step`` of the ladder: its scheduled ring hops.

    Hop k forwards the partial received k hops ago one shard up the ring
    and files it under its origin rank, so after all P-1 hops every shard
    holds every partial.  Each hop is one ``ppermute`` in a
    ``REDUCE_TAG{k}`` scope — the unit the overlap tracer counts and the
    thing that interleaves with HALO_TAG traffic in the schedule.
    Steps outside the ladder (``step >= stages``) are a no-op so solvers
    can advance unconditionally at every pipeline age.
    """
    if step >= cfg.stages or cfg.n_shards == 1:
        return handle
    p = cfg.n_shards
    ring = [(i, (i + 1) % p) for i in range(p)]
    r = lax.axis_index(cfg.axis)
    for k in hop_groups(p, cfg.stages)[step]:
        with jax.named_scope(f"{REDUCE_TAG}{k}"):
            send = lax.dynamic_index_in_dim(
                handle, jnp.mod(r - k, p), axis=0, keepdims=False)
            recv = lax.ppermute(send, cfg.axis, ring)
            handle = lax.dynamic_update_index_in_dim(
                handle, recv, jnp.mod(r - k - 1, p), axis=0)
    return handle


def ordered_reduce(gathered: jax.Array, out_dtype,
                   compensated: bool) -> jax.Array:
    """Sum the (P, K[, s]) gathered partials over shard rank 0..P-1.

    The explicit rank-ascending add chain is the determinism anchor: it
    is the same order on every shard (all shards hold identical buffers
    after the allgather), the same order the eager local oracle uses,
    and — measured, tests/test_reduction.py — the order XLA's CPU
    all-reduce applies, which is what makes staged-vs-monolithic stencil
    histories bitwise.  ``compensated`` switches to Kahan accumulation
    in ``out_dtype`` (the fp32-payload path: one compensated fp64 sum of
    P fp32 partials, DESIGN.md §14).
    """
    if not compensated:
        acc = gathered[0].astype(out_dtype)
        for k in range(1, gathered.shape[0]):
            acc = acc + gathered[k].astype(out_dtype)
        return acc
    acc = jnp.zeros(gathered.shape[1:], out_dtype)
    comp = jnp.zeros(gathered.shape[1:], out_dtype)
    for k in range(gathered.shape[0]):
        y = gathered[k].astype(out_dtype) - comp
        t = acc + y
        comp = (t - acc) - y
        acc = t
    return acc


def staged_wait(handle: jax.Array, advanced: int, cfg: StagedConfig,
                out_dtype) -> jax.Array:
    """Finish the ladder and combine (MPI_Wait).

    ``advanced`` is how many advance steps the solver already ran on
    this handle (p(l)-CG: l-1; a blocking start+wait: 0).  The remaining
    steps execute here — back-to-back, the modeled 'wait stall' of
    ``launch.autotune`` — then the gathered partials reduce in rank
    order.
    """
    for step in range(advanced, cfg.stages):
        handle = staged_advance(handle, step, cfg)
    return ordered_reduce(handle, out_dtype,
                          cfg.compensated(out_dtype))


# --------------------------------------------------------------------------
# Wiring into SolverOps (distributed + local-oracle forms).
# --------------------------------------------------------------------------

def staged_ops_pieces(cfg: StagedConfig, solver_dtype=None) -> dict:
    """The ``SolverOps.create`` override kwargs for a staged substrate.

    ``start`` computes local partials with the SAME row-sum expression
    as every other substrate (``types.dot_block_rows``) and parks them;
    ``advance``/``wait`` drive the ladder; ``handle_zeros`` tells the
    solver what an in-flight D-ring slot looks like ((P, K) wire-dtype);
    ``combine_partials`` is the superkernel's entry: identical ladder on
    VMEM-accumulated partials (DESIGN.md §13/§14).
    """
    def start(mat, vec):
        return staged_start(dot_block_rows(mat, vec), cfg)

    def advance(handle, step):
        return staged_advance(handle, step, cfg)

    def wait(handle, advanced=0):
        # out dtype: the solver dtype the partials were rounded from.
        out = handle.dtype if cfg.payload_dtype is None else _SOLVER_DTYPE(
            solver_dtype)
        return staged_wait(handle, advanced, cfg, out)

    def handle_zeros(shape, dtype):
        return jnp.zeros((cfg.n_shards,) + tuple(shape),
                         cfg.wire_dtype(dtype))

    def combine_partials(partials):
        return staged_start(partials, cfg)

    return dict(dot_block_start=start, dot_block_advance=advance,
                dot_block_wait=wait, handle_zeros=handle_zeros,
                combine_partials=combine_partials)


def _SOLVER_DTYPE(solver_dtype):
    if solver_dtype is None:
        # The widest float this runtime supports (f64 under x64, f32
        # otherwise) — matches the solvers' default b.dtype in this repo.
        return jax.dtypes.canonicalize_dtype(jnp.float64)
    return jnp.dtype(solver_dtype)


# --------------------------------------------------------------------------
# Eager local oracle (single device, no wire).
# --------------------------------------------------------------------------

def oracle_start(mat: jax.Array, vec: jax.Array,
                 cfg: StagedConfig) -> jax.Array:
    """Local partials of all ``n_shards`` virtual slices at once.

    The vector axis splits into P contiguous slices — the same row
    blocks the mesh partition owns — and each slice's partial is the
    same ``dot_block_rows`` expression a shard evaluates, so the gather
    buffer matches the distributed ladder's final buffer bitwise and
    ``ordered_reduce`` finishes identically (the oracle property,
    tests/test_reduction.py)."""
    p = cfg.n_shards
    n = vec.shape[0]
    if n % p:
        raise ValueError(f"oracle needs n divisible by virtual shards "
                         f"({n} % {p})")
    wire = cfg.wire_dtype(vec.dtype)
    nl = n // p
    if mat.shape[0] == 1:
        # A one-row block is a vector dot.  A shard's (1, nl) block
        # compiles to a reduce over a 1-D vector; slicing (1, n) here
        # instead keeps a (1, 128)-tiled 2-D operand on the TPU, which
        # sums in another order.  Slice the 1-D vectors.
        row = mat[0]
        parts = [dot_block_rows(row[None, r * nl:(r + 1) * nl],
                                vec[r * nl:(r + 1) * nl]).astype(wire)
                 for r in range(p)]
        return jnp.stack(parts, axis=0)
    mats = mat.reshape(mat.shape[0], p, nl)
    vecs = vec.reshape(p, nl)
    parts = [dot_block_rows(mats[:, r, :], vecs[r]).astype(wire)
             for r in range(p)]
    return jnp.stack(parts, axis=0)


def oracle_partials(partials: jax.Array, cfg: StagedConfig) -> jax.Array:
    """Oracle ``combine_partials``.  The unfused path's partials come
    from :func:`oracle_start`, already one per virtual shard: they are
    the gather buffer.  The superkernel's single device has ONE partial
    — file it as the full gather buffer (slice splitting happens inside
    the superkernel's own accumulation, which the oracle cannot redo),
    zero elsewhere."""
    if partials.ndim == 2:                   # (P, K) from oracle_start
        return partials
    wire = cfg.wire_dtype(partials.dtype)
    buf = jnp.zeros((cfg.n_shards,) + partials.shape, wire)
    return buf.at[0].set(partials.astype(wire))


def oracle_ops_pieces(cfg: StagedConfig, solver_dtype=None) -> dict:
    """``SolverOps.create`` overrides for the local eager ladder oracle.

    ``advance`` is an eager no-op inside the tagged scope (no wire on one
    device, but the tracer still sees the step structure), ``wait`` runs
    the identical ordered/compensated reduce.
    """
    def start(mat, vec):
        return oracle_start(mat, vec, cfg)

    def advance(handle, step):
        if step >= cfg.stages:
            return handle
        with jax.named_scope(f"{REDUCE_TAG}{step}"):
            return handle

    def wait(handle, advanced=0):
        out = handle.dtype if cfg.payload_dtype is None else _SOLVER_DTYPE(
            solver_dtype)
        return ordered_reduce(handle, out, cfg.compensated(out))

    def handle_zeros(shape, dtype):
        return jnp.zeros((cfg.n_shards,) + tuple(shape),
                         cfg.wire_dtype(dtype))

    return dict(dot_block_start=start, dot_block_advance=advance,
                dot_block_wait=wait, handle_zeros=handle_zeros,
                dot_block_partials=start,
                combine_partials=lambda p_: oracle_partials(p_, cfg))


def resolve_backend_reduction(backend, reduction: str, stages: int,
                              dtype, n_shards: int,
                              axis: str | None) -> StagedConfig | None:
    """Shared reduction-request resolution for backend constructors.

    Validates the mode, clamps ``stages`` into the ladder's [1, P-1]
    range, honours the backend's ``supports_staged_reduction``
    capability flag (declining backends DOWNGRADE to monolithic and
    record why), and sets ``reduction_mode`` / ``reduction_fallback``
    on the backend.  Returns the StagedConfig to thread through the
    solver ops, or None for the monolithic psum — ONE copy of this
    policy, so local / shard_map / multiprocess can never diverge.
    """
    if reduction == "monolithic":
        backend.reduction_mode = "monolithic"
        backend.reduction_fallback = None
        return None
    if reduction != "staged":
        raise ValueError(
            f"unknown reduction mode {reduction!r} "
            "(want 'monolithic' or 'staged')")
    if not type(backend).supports_staged_reduction:
        # Explicit capability fallback: the request is honoured
        # arithmetically by the monolithic psum; the flag records that
        # no ladder ran — surfaced three ways (attribute, structured
        # warning, default-registry gauge) so it cannot pass unnoticed
        # in a scaling study (DESIGN.md §16).  No in-tree backend
        # declines any more — multiprocess runs the ladder over real
        # process boundaries since DESIGN.md §17 — but the policy stays
        # for out-of-tree backends registered via register_backend.
        backend.reduction_mode = "monolithic"
        backend.reduction_fallback = (
            f"backend {backend.name!r} does not support the staged "
            "ring ladder; dot block downgraded to the monolithic "
            "all-reduce")
        warnings.warn(backend.reduction_fallback,
                      ReductionFallbackWarning, stacklevel=2)
        from repro.obs.metrics import default_registry
        default_registry().gauge(
            "backend_reduction_fallback",
            "1 = staged reduction request downgraded to monolithic",
            label_names=("backend",)).labels(backend=backend.name).set(1)
        return None
    backend.reduction_mode = "staged"
    backend.reduction_fallback = None
    # Pin the gauge at 0 for granted requests: "no fallback happened" is
    # an asserted invariant of the cross-process fabric (DESIGN.md §17,
    # tests/test_fabric.py), so it must be observable, not just absent.
    from repro.obs.metrics import default_registry
    default_registry().gauge(
        "backend_reduction_fallback",
        "1 = staged reduction request downgraded to monolithic",
        label_names=("backend",)).labels(backend=backend.name).set(0)
    n_shards = max(n_shards, 1)
    stages = max(1, min(stages, max(n_shards - 1, 1)))
    return StagedConfig(n_shards=n_shards, stages=stages,
                        payload_dtype=dtype, axis=axis)


def oracle_solver_ops(op, prec, cfg: StagedConfig):
    """Full single-device SolverOps running the eager ladder oracle —
    the staged analogue of ``SolverOps.local`` (DESIGN.md §14).

    ``cfg.n_shards`` is the VIRTUAL shard count: the dot block splits
    into that many contiguous slices whose partials fill the gather
    buffer directly, so a staged mesh run of the same shard count is
    reproduced bitwise without any wire.  Used by the local backend
    (``reduction="staged"``) and as the shape oracle for staged slab
    programs."""
    from repro.core.types import SolverOps
    from repro.kernels.ops import fused_iteration_factory
    from repro.linalg.preconditioners import inverse_diagonal

    pfun = (lambda v: v) if prec is None else (lambda v: prec.apply(v))
    return SolverOps.create(
        apply_a=lambda v: op.apply(v),
        prec=pfun,
        dot_block=dot_block_rows,
        prec_diag=inverse_diagonal(prec),
        fused_iter_factory=fused_iteration_factory(op, prec),
        **oracle_ops_pieces(cfg),
    )


# --------------------------------------------------------------------------
# Wire accounting (the reduce_bench metrics, DESIGN.md §14).
# --------------------------------------------------------------------------

def hop_payload_bytes(l: int, s: int = 1, dsize: int = 8) -> int:
    """Bytes ONE ladder hop carries: the full (2l+1)[, s] dot block in
    the wire dtype — the message size that sits on the latency-bound
    wire each hop (the fp32 option halves exactly this)."""
    return (2 * l + 1) * max(s, 1) * dsize


def reduction_wire_bytes(n_shards: int, l: int, s: int = 1,
                         dsize: int = 8) -> int:
    """Total bytes one shard sends per staged reduction: P-1 hops x the
    hop payload.  Honest accounting: a ring allgather of raw partials
    ships more TOTAL bytes than a bandwidth-optimal tree all-reduce —
    the regime this subsystem targets is latency-bound (tiny K), where
    per-hop payload and hop count dominate, not aggregate bytes."""
    return (n_shards - 1) * hop_payload_bytes(l, s, dsize)
