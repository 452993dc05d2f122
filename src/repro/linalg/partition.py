"""Distributed partitioning of unstructured sparse operators
(DESIGN.md §12).

The structured stencils get their halo for free — one boundary plane per
neighbour.  A general :class:`~repro.linalg.sparse.SparseOp` needs the
same thing *computed*: which of my rows do my neighbours reference, and
where do their values land in my local gather?  This module turns an
operator into a :class:`PartitionPlan`:

1.  **Order** — a bandwidth-reducing RCM pass
    (``sparse.rcm_permutation``) so that contiguous row blocks are a good
    partition: after ordering, the remote columns of shard ``i``
    concentrate in the few adjacent shards (exactly the role the domain
    decomposition plays in the paper's MPI runs).
2.  **Split** — ``n_shards`` contiguous row blocks of ``nxl = n/S`` rows.
3.  **Index sets** — per shard and per hop distance ``h`` (1..hops,
    where ``hops = ceil(bandwidth / nxl)``), the *send sets*: the local
    row indices shard ``i±h`` actually references, padded to the global
    max so every shard ships fixed-size buffers (shard_map needs uniform
    shapes).  Column indices of the local ELL blocks are remapped into
    the *extended local vector*  ``[own rows | recv-from-prev (hops
    slabs) | recv-from-next (hops slabs)]``, so the shard-level SpMV is:
    gather send buffers → one ``lax.ppermute`` per (direction, hop) —
    the MPI neighbour send/recv — → one local ELL product.  No global
    gather; RCM keeps ``hops`` at 1 for mesh-like matrices, the
    multi-hop path is the correctness fallback for wide-bandwidth rows.

The ppermutes are tagged with ``HALO_TAG`` so the overlap tracer
(``repro.utils.trace``) can verify they are scheduled *inside* the
in-flight reduction windows — the paper's Iallreduce/neighbour-exchange
staggering, measured on compiled HLO (DESIGN.md §12).

Plans are memoized by operator fingerprint (:func:`plan_for`); the
serving layer's :class:`repro.serve.cache.SetupCache` fronts the same
cache with its own hit/miss stats.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.types import HALO_TAG
from repro.linalg.sparse import (
    SparseOp,
    bandwidth,
    ell_rowsum,
    permute_spd,
    rcm_permutation,
)


@dataclasses.dataclass(frozen=True)
class PartitionPlan:
    """Static per-shard data for a distributed unstructured SpMV.

    All per-shard arrays are stacked on a leading shard axis (sharded by
    ``P(axis)`` under shard_map) and padded to uniform sizes.

    cols : (S, nxl, w) int32 — ELL column slots remapped into the
        extended local vector [0, nxl + 2*hops*max_send).
    vals : (S, nxl, w) — ELL values (padded slots 0.0).
    send_up : (S, hops, max_send) int32 — local rows shard i ships to
        shard i+h (hop slab h-1); send_dn symmetrically to i-h.
    perm : (n,) int64 — global ordering used (``perm[new] = old``);
        identity when the operator was pre-ordered.
    """

    n_shards: int
    n: int
    nxl: int
    hops: int
    max_send: int
    cols: jax.Array
    vals: jax.Array
    send_up: jax.Array
    send_dn: jax.Array
    perm: np.ndarray
    band: int                      # post-ordering bandwidth (diagnostics)

    @property
    def inv_perm(self) -> np.ndarray:
        inv = np.empty_like(self.perm)
        inv[self.perm] = np.arange(self.perm.size)
        return inv

    @property
    def identity_perm(self) -> bool:
        return bool((self.perm == np.arange(self.perm.size)).all())

    def neighbor_bytes(self, dsize: int = 8) -> int:
        """Per-iteration halo SEND bytes of one shard (both directions,
        all hops; receives overlap on a full-duplex link) — the term the
        autotuner cost model folds in
        (``launch.autotune.model_iteration_time``'s ``neighbor_bytes``,
        DESIGN.md §12).  Same convention as the structured operators:
        a Stencil2D5 shard reports 2*ny*dsize (one plane per direction),
        matching ``timing_model.stencil_kernel_times``'s
        per-direction ``halo_elems`` with its built-in 2x multiplier."""
        return 2 * self.hops * self.max_send * dsize

    def occupancy(self) -> float:
        """Useful fraction of ELL slots (1.0 = no padding waste)."""
        v = np.asarray(self.vals)
        return float(np.count_nonzero(v) / v.size)

    def halo_rows_fraction(self) -> float:
        """Halo rows shipped per shard relative to rows owned."""
        return 2.0 * self.hops * self.max_send / self.nxl


def partition_spd(op: SparseOp, n_shards: int) -> PartitionPlan:
    """Build the :class:`PartitionPlan` for ``op`` over ``n_shards``.

    Requires ``op.n % n_shards == 0`` (the mesh generators take arbitrary
    node counts — pad there).  The hop count is ``ceil(band / nxl)`` with
    ``band`` the post-RCM bandwidth; mesh-like matrices order to
    ``hops == 1`` (the structured-stencil regime), anything wider pays
    proportionally more ppermutes but stays correct.
    """
    n = op.n
    assert n % n_shards == 0, (
        f"unstructured partition needs n % n_shards == 0 (n={n}, "
        f"S={n_shards}); pad the mesh generator's node count")
    if op.ordered or n_shards == 1:
        perm = np.arange(n, dtype=np.int64)
        oop = op
    else:
        perm = rcm_permutation(op)
        oop = permute_spd(op, perm, ordered=True)
    nxl = n // n_shards
    band = bandwidth(oop)
    hops = min(max(-(-band // nxl), 1), n_shards - 1) if n_shards > 1 else 1

    cols = np.asarray(oop.cols)
    vals = np.asarray(oop.vals)
    w = oop.w
    nz = vals != 0.0
    starts = np.arange(n_shards) * nxl

    # --- send sets: which of shard s's rows does shard s±h touch? -------
    def _referenced(reader: int, owner: int) -> np.ndarray:
        """Column indices (local to ``owner``) that ``reader`` references."""
        rlo, rhi = starts[reader], starts[reader] + nxl
        olo, ohi = starts[owner], starts[owner] + nxl
        c = cols[rlo:rhi][nz[rlo:rhi]]
        c = c[(c >= olo) & (c < ohi)]
        return np.unique(c) - olo

    empty = np.empty(0, dtype=np.int64)
    send_up = [[_referenced(s + h, s) if s + h < n_shards else empty
                for h in range(1, hops + 1)] for s in range(n_shards)]
    send_dn = [[_referenced(s - h, s) if s - h >= 0 else empty
                for h in range(1, hops + 1)] for s in range(n_shards)]
    max_send = max(
        1, max((len(a) for row in send_up + send_dn for a in row),
               default=1))

    # --- remap ELL columns into the extended local vector ----------------
    # Layout per shard: [own rows (nxl) | from-prev hop 1..hops |
    # from-next hop 1..hops], each halo slab max_send wide.  from-prev
    # slab h-1 holds the up(h)-send buffer of shard s-h, so a column
    # owned by s-h maps to nxl + (h-1)*max_send + its position in
    # send_up[s-h][h-1]; symmetrically for s+h via send_dn[s+h][h-1].
    ext = nxl + 2 * hops * max_send
    cols_l = np.zeros((n_shards, nxl, w), dtype=np.int32)
    vals_l = np.zeros((n_shards, nxl, w))
    for s in range(n_shards):
        lo, hi = starts[s], starts[s] + nxl
        c = cols[lo:hi].astype(np.int64)
        v = vals[lo:hi]
        rnz = v != 0.0
        local = (c >= lo) & (c < hi)
        out = np.zeros_like(c)
        out[local] = c[local] - lo
        covered = local | ~rnz
        for h in range(1, hops + 1):
            if s - h >= 0:
                olo = starts[s - h]
                m = rnz & (c >= olo) & (c < olo + nxl)
                pos = np.searchsorted(send_up[s - h][h - 1], c[m] - olo)
                out[m] = nxl + (h - 1) * max_send + pos
                covered |= m
            if s + h < n_shards:
                olo = starts[s + h]
                m = rnz & (c >= olo) & (c < olo + nxl)
                pos = np.searchsorted(send_dn[s + h][h - 1], c[m] - olo)
                out[m] = nxl + (hops + h - 1) * max_send + pos
                covered |= m
        assert covered.all(), "halo remap missed a referenced column"
        assert (out[rnz] < ext).all()
        cols_l[s] = out
        vals_l[s] = v

    def _pad(sets):
        a = np.zeros((n_shards, hops, max_send), dtype=np.int32)
        for s in range(n_shards):
            for h in range(hops):
                idx = sets[s][h]
                a[s, h, :len(idx)] = idx
        return a

    dtype = oop.vals.dtype
    return PartitionPlan(
        n_shards=n_shards, n=n, nxl=nxl, hops=hops, max_send=max_send,
        cols=jnp.asarray(cols_l), vals=jnp.asarray(vals_l, dtype=dtype),
        send_up=jnp.asarray(_pad(send_up)), send_dn=jnp.asarray(_pad(send_dn)),
        perm=perm, band=band,
    )


# --------------------------------------------------------------------------
# Shard-level apply (runs INSIDE shard_map).
# --------------------------------------------------------------------------

def halo_exchange(x_local: jax.Array, send_up: jax.Array,
                  send_dn: jax.Array, axis: str) -> jax.Array:
    """Extended local vector via the precomputed send sets.

    One ``lax.ppermute`` per (direction, hop) — the MPI neighbour
    send/recv — wrapped in the ``HALO_TAG`` scope so the overlap tracer
    can locate the exchanges in the compiled schedule and assert they
    ride inside the in-flight reduction windows (DESIGN.md §12).
    ``ppermute`` yields zeros where no peer exists, which is exactly the
    empty halo at the domain ends.
    """
    hops, max_send = send_up.shape
    with jax.named_scope(HALO_TAG):
        n = lax.axis_size(axis)
        slabs = [x_local]
        from_prev, from_next = [], []
        for h in range(1, hops + 1):
            up_buf = x_local[send_up[h - 1]]   # rows shard i+h needs
            dn_buf = x_local[send_dn[h - 1]]   # rows shard i-h needs
            if n > h:
                from_prev.append(lax.ppermute(
                    up_buf, axis, [(i, i + h) for i in range(n - h)]))
                from_next.append(lax.ppermute(
                    dn_buf, axis, [(i, i - h) for i in range(h, n)]))
            else:
                z = jnp.zeros((max_send,), x_local.dtype)
                from_prev.append(z)
                from_next.append(z)
        return jnp.concatenate(slabs + from_prev + from_next)


def apply_local(x_local: jax.Array, cols: jax.Array, vals: jax.Array,
                send_up: jax.Array, send_dn: jax.Array, axis: str,
                use_kernel: bool = False) -> jax.Array:
    """Shard-level unstructured SpMV: halo exchange + local ELL product."""
    xe = halo_exchange(x_local, send_up, send_dn, axis)
    if use_kernel:
        from repro.kernels import ops as kops

        return kops.ell_spmv_apply(xe, cols, vals)
    # ell_rowsum (not .sum) so this rounds bitwise-identically to the
    # single-device SparseOp.apply — see sparse.ell_rowsum.
    return ell_rowsum(vals.astype(x_local.dtype), xe[cols])


def emulate_partitioned_apply(plan: PartitionPlan,
                              xp: np.ndarray) -> np.ndarray:
    """Pure-numpy reference of halo_exchange + apply_local (the oracle
    the partition tests compare against): gather each shard's send sets,
    'ppermute' them by array slicing, ELL-multiply.  ``xp`` must already
    be in the plan's ordering (``x[plan.perm]``)."""
    cols = np.asarray(plan.cols)
    vals = np.asarray(plan.vals)
    su = np.asarray(plan.send_up)
    sd = np.asarray(plan.send_dn)
    S, nxl, H, ms = plan.n_shards, plan.nxl, plan.hops, plan.max_send
    y = np.zeros(plan.n)
    for s in range(S):
        xl = xp[s * nxl:(s + 1) * nxl]
        fp, fn = [], []
        for h in range(1, H + 1):
            fp.append(xp[(s - h) * nxl:(s - h + 1) * nxl][su[s - h, h - 1]]
                      if s - h >= 0 else np.zeros(ms))
            fn.append(xp[(s + h) * nxl:(s + h + 1) * nxl][sd[s + h, h - 1]]
                      if s + h < S else np.zeros(ms))
        xe = np.concatenate([xl] + fp + fn)
        y[s * nxl:(s + 1) * nxl] = (vals[s] * xe[cols[s]]).sum(axis=1)
    return y


# --------------------------------------------------------------------------
# Plan memoization (the serving layer's SetupCache fronts this).
# --------------------------------------------------------------------------

_PLAN_CACHE: dict[tuple, PartitionPlan] = {}


def plan_for(op: SparseOp, n_shards: int) -> PartitionPlan:
    """Memoized :func:`partition_spd` keyed by operator fingerprint —
    RCM + send-set construction is setup-time numpy work that must be
    paid once per operator, not once per solve (DESIGN.md §11/§12)."""
    from repro.serve.cache import operator_fingerprint

    key = (operator_fingerprint(op), n_shards)
    plan = _PLAN_CACHE.get(key)
    if plan is None:
        plan = partition_spd(op, n_shards)
        _PLAN_CACHE[key] = plan
    return plan
