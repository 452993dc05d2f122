"""Segmented checkpointed solve drivers (DESIGN.md §19).

The cycle-boundary invariant
----------------------------
A p(l)-CG state is only host-snapshotable where the in-flight D ring is
EMPTY: mid-cycle, l reduction handles are in flight, and on a staged
substrate each shard's gather buffer holds a different rotation of the
ladder — per-device state that no host copy can represent.  The solver
already has exactly such points: every interrupt (breakdown restart,
periodic residual replacement, governor-scheduled replacement) re-inits
the cycle with ``ops.handle_zeros`` — a drained ring — and recomputes
the TRUE residual from the current iterate.  Checkpointing therefore
rides the interrupt machinery: ``CheckpointConfig(every=k)`` arms an
effective residual-replacement period of at most ``k`` solution
updates, and the driver snapshots AFTER each interrupt, where

* the ring is drained (no half-arrived handles are persisted — the ring
  is rebuilt as ``handle_zeros`` for whatever substrate restores it);
* every non-vector leaf is genuinely replicated (post-reduction
  scalars), so a host copy is well-defined under shard_map;
* the recorded residual is a clean true-residual recompute, which is
  what restore re-derives for the certification check.

The segmented driver below is bitwise-equivalent to the sequential
``lax.while_loop(cond, body)`` drive of the SAME program: the plain
body is ``cond(needs_interrupt, interrupt, step)``, and the segmented
form runs ``step`` under ``while (cond & ~needs_interrupt)`` then
applies ``interrupt`` on the host side — the identical arithmetic in
the identical order (tests/test_checkpoint.py pins this bitwise, fused
and unfused, single and batched, local and shard_map).

``every=0`` (or ``checkpoint=None``) takes the solvers' untouched
``lax.while_loop`` path — the compiled HLO is byte-identical to the
pre-§19 solver (asserted in tests/test_checkpoint.py).
"""

from __future__ import annotations

import dataclasses
import os
import re
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.format import (CheckpointCertificationError,
                                     CheckpointError, CheckpointMismatchError,
                                     load_checkpoint, save_checkpoint)

_CKPT_RE = re.compile(r"^ckpt_(\d{10})\.npz$")

# Meta keys that must match between a checkpoint and the restoring
# solver — a disagreement is a config mismatch, never a silent resume.
_STRUCT_KEYS = ("kind", "method", "n", "dtype", "treedef", "maxit", "tol",
                "replace_every", "max_restarts", "l", "recurrence",
                "telemetry_cap", "governed", "every")


@dataclasses.dataclass(frozen=True)
class CheckpointConfig:
    """Checkpoint policy for a solve (DESIGN.md §19).

    every:        snapshot at least every ``every`` solution updates
                  (0 disables checkpointing entirely — the solver
                  compiles to its pre-§19 HLO unchanged).  Arming
                  checkpoints forces an effective residual-replacement
                  period of ``min(replace_every or inf, every)``: a
                  checkpoint boundary IS a true-residual replacement.
    directory:    where snapshots go (``ckpt_<tot>.npz``); None keeps
                  the segmented drive without persisting (useful as
                  the uninterrupted oracle for parity tests).  Under
                  multi-process meshes only process 0 writes; the
                  directory must be shared (or replicated) for restore.
    keep:         on-disk snapshots retained (oldest GC'd first).
    resume:       load the latest checkpoint in ``directory`` before
                  solving (no-op when none exists yet).
    certify_rtol: tolerance for the restore-time true-residual
                  certification.  Same-substrate restores reproduce the
                  saved value bitwise; an elastic restore (different
                  shard count) re-reduces the same vectors in a
                  different order, so ULP-level slack is allowed.
    on_boundary:  host callback invoked with the global solution-update
                  count at every segment boundary (before the interrupt
                  is applied) — the fabric drills hang heartbeat touches
                  and deterministic fault injection here.  Updates, not
                  raw iterations: boundaries land at exact multiples of
                  ``every`` updates (plcg's ring-refill iterations after
                  each restart advance ``tot`` but not ``upd``).
    """

    every: int = 0
    directory: str | None = None
    keep: int = 2
    resume: bool = False
    certify_rtol: float = 1e-8
    on_boundary: Callable[[int], None] | None = None

    @property
    def armed(self) -> bool:
        return self.every > 0


# --------------------------------------------------------------------------
# Directory layout.
# --------------------------------------------------------------------------

def checkpoint_path(directory: str, tot: int) -> str:
    return os.path.join(directory, f"ckpt_{tot:010d}.npz")


def list_checkpoints(directory: str) -> list[str]:
    """Checkpoint files in ``directory``, oldest first."""
    if not os.path.isdir(directory):
        return []
    names = sorted(n for n in os.listdir(directory) if _CKPT_RE.match(n))
    return [os.path.join(directory, n) for n in names]


def latest_checkpoint(directory: str) -> str | None:
    paths = list_checkpoints(directory)
    return paths[-1] if paths else None


def _gc(directory: str, keep: int) -> None:
    paths = list_checkpoints(directory)
    for p in paths[:-keep] if keep > 0 else paths:
        try:
            os.remove(p)
        except OSError:
            pass


# --------------------------------------------------------------------------
# State <-> payload.  Leaves are keyed by flatten order ("leaf_NNN"); the
# meta records the treedef string, so a structural change between save
# and restore is a typed mismatch, not an index aliasing bug.
# --------------------------------------------------------------------------

def _flatten_with_mask(state, exclude_mask):
    vals, treedef = jax.tree_util.tree_flatten(state)
    if exclude_mask is None:
        exc = [False] * len(vals)
    else:
        exc, mdef = jax.tree_util.tree_flatten(exclude_mask)
        assert mdef == treedef, "exclude mask must match the state pytree"
    return vals, exc, treedef


def state_payload(state, exclude_mask=None) -> dict[str, np.ndarray]:
    """Flatten a (host-readable) state pytree into named numpy arrays.

    Leaves where ``exclude_mask`` is True are dropped — the restore
    side rebuilds them from its own template (the drained D ring, which
    is substrate-shaped and all zeros at a boundary by construction).
    """
    vals, exc, _ = _flatten_with_mask(state, exclude_mask)
    return {f"leaf_{i:03d}": np.asarray(v)
            for i, (v, e) in enumerate(zip(vals, exc)) if not e}


def state_treedef_str(state) -> str:
    return str(jax.tree_util.tree_structure(state))


def _place_like(template_leaf, value: np.ndarray):
    """Device-place ``value`` with the template leaf's sharding — this
    is what makes restore elastic: the bytes come from the checkpoint,
    the placement from whatever substrate is restoring."""
    if isinstance(template_leaf, jax.Array):
        try:
            return jax.make_array_from_callback(
                value.shape, template_leaf.sharding,
                lambda idx: value[idx])
        except Exception:
            return jnp.asarray(value)
    return jnp.asarray(value)


def state_restore(template, payload: dict[str, np.ndarray],
                  exclude_mask=None):
    """Rebuild a state pytree from ``payload``: excluded leaves come
    from ``template`` (shape-/sharding-correct for the restoring
    substrate), everything else from the checkpoint, shape- and
    dtype-checked against the template."""
    vals, exc, treedef = _flatten_with_mask(template, exclude_mask)
    out = []
    for i, (tv, e) in enumerate(zip(vals, exc)):
        if e:
            out.append(tv)
            continue
        key = f"leaf_{i:03d}"
        if key not in payload:
            raise CheckpointMismatchError(
                f"checkpoint payload is missing {key} "
                f"({len(payload)} stored leaves)")
        a = payload[key]
        tshape, tdtype = tuple(np.shape(tv)), np.asarray(tv).dtype \
            if not isinstance(tv, jax.Array) else tv.dtype
        if isinstance(tv, jax.Array):
            tshape = tuple(tv.shape)
        if tuple(a.shape) != tshape or a.dtype != tdtype:
            raise CheckpointMismatchError(
                f"{key}: stored {a.dtype}{tuple(a.shape)} != expected "
                f"{tdtype}{tshape}")
        out.append(_place_like(tv, a))
    extra = [k for k in payload if k.startswith("leaf_")
             and int(k[5:]) >= len(vals)]
    if extra:
        raise CheckpointMismatchError(
            f"checkpoint payload has unexpected leaves {sorted(extra)}")
    return jax.tree_util.tree_unflatten(treedef, out)


# --------------------------------------------------------------------------
# Per-method hooks.  Only interrupt-capable methods can checkpoint: the
# boundary IS the interrupt.
# --------------------------------------------------------------------------

def exclude_mask(method: str, state):
    """Leaves to drop from the payload: the in-flight D ring for plcg
    (drained at every boundary; its shape is substrate-specific), and
    nothing for pcg (whose state carries no in-flight handles)."""
    m = jax.tree.map(lambda _: False, state)
    if method == "plcg":
        return m._replace(cyc=m.cyc._replace(D=True))
    return m


def iter_count(method: str, state):
    return state.tot if method == "plcg" else state.it


def upd_count(method: str, state):
    return state.upd if method == "plcg" else state.it


def make_rel_fn(method: str, kw: dict) -> Callable:
    """``rel(ops, b, st) -> scalar``: the true relative residual M-norm
    of the state's iterate, recomputed from scratch (r = b - A x,
    z = M^{-1} r, ||r||_M / ||r0||_M).  Evaluated through the SAME ops
    at save and at restore, so a same-substrate restore certifies
    bitwise and an elastic one to reduction-order ULPs."""
    from repro.core.types import dot1

    if method == "plcg":
        def rel(ops, b, st):
            layout = _slab_layout(kw, b.shape[0])
            x = layout.from_row(st.cyc.S[layout.x_row])
            r = b - ops.apply_a(x)
            z = ops.prec(r)
            return jnp.sqrt(jnp.abs(dot1(ops, r, z))) / st.norm0

        return rel
    if method == "pcg":
        from repro.core.ghysels_pcg import X_ROW

        def rel(ops, b, st):
            x = st.S[X_ROW]
            r = b - ops.apply_a(x)
            u = ops.prec(r)
            return jnp.sqrt(jnp.abs(dot1(ops, r, u))) / st.hist[0]

        return rel
    raise KeyError(f"method {method!r} does not support checkpointing "
                   "(no interrupt boundary)")


def _slab_layout(kw: dict, n: int):
    from repro.kernels.fused_iter import SlabLayout

    return SlabLayout.for_solver(
        int(kw["l"]), n, kw.get("recurrence", "ghysels"),
        fused=bool(kw.get("fused_iteration", False)))


def persist_codec(method: str, kw: dict, n: int):
    """``(encode, decode)`` between a solver state and the form a
    checkpoint persists, or None where they are the same.  A p(l)-CG
    slab persists with flat rows (..., NV, n): its (R, 128) row storage
    pads each shard's rows to whole tiles, so the stored form has to
    drop the padding to restore onto another shard count (elastic
    restore).  ``n`` is the vector length one substrate program sees
    (a shard's under shard_map)."""
    if method != "plcg":
        return None
    layout = _slab_layout(kw, n)

    def with_slab(st, f):
        return st._replace(cyc=st.cyc._replace(S=f(st.cyc.S)))

    return (lambda st: with_slab(st, layout.rows_to_flat),
            lambda st: with_slab(st, layout.rows_from_flat))


def effective_kw(method: str, kw: dict, every: int) -> dict:
    """Builder kwargs with the checkpoint cadence folded in.

    Two ``since_rr`` thresholds OR'd in ``needs_interrupt`` equal the
    smaller one, so the effective replacement period is
    ``min(replace_every or inf, every)``.  plcg's restart budget (and
    with it the history length) grows to cover the extra scheduled
    restarts — applied identically by every driver of the same config,
    which is what keeps resumed-vs-uninterrupted histories bitwise.
    """
    if every <= 0:
        raise ValueError(f"checkpoint.every must be > 0 (got {every})")
    kw = dict(kw)
    base = int(kw.get("replace_every", 0) or 0)
    eff = every if base == 0 else min(base, every)
    kw["replace_every"] = eff
    if method == "plcg":
        if eff <= int(kw["l"]):
            raise ValueError(
                f"checkpoint interval {eff} must exceed the pipeline "
                f"depth l={kw['l']} (the ring must refill between "
                "boundaries)")
        maxit = int(kw.get("maxit", 1000))
        kw["max_restarts"] = (int(kw.get("max_restarts", 10))
                              + maxit // eff + 1)
    return kw


def solver_meta(method: str, n: int, dtype, kw: dict, every: int) -> dict:
    """Config identity stored with every snapshot and checked on
    restore (see ``_STRUCT_KEYS``)."""
    return {
        "kind": "solve",
        "method": method,
        "n": int(n),
        "dtype": str(np.dtype(dtype)),
        "maxit": int(kw.get("maxit", 1000)),
        "tol": float(kw.get("tol", 1e-6)),
        "replace_every": int(kw.get("replace_every", 0)),
        "max_restarts": int(kw.get("max_restarts", 10)),
        "l": int(kw.get("l", 0)),
        "recurrence": kw.get("recurrence", "ghysels"),
        "telemetry_cap": int(kw.get("telemetry_cap", 0)),
        "governed": kw.get("governor") is not None,
        "every": int(every),
    }


def check_meta(meta: dict, expect: dict) -> None:
    bad = {k: (meta.get(k), expect.get(k)) for k in _STRUCT_KEYS
           if meta.get(k) != expect.get(k)}
    if bad:
        detail = ", ".join(f"{k}: stored {s!r} != expected {e!r}"
                           for k, (s, e) in sorted(bad.items()))
        raise CheckpointMismatchError(f"checkpoint/config mismatch: {detail}")


# --------------------------------------------------------------------------
# The segmented drive loop — substrate-agnostic.  ``seg``/``interrupt``
# are compiled callables (plain jit locally, shard_map-wrapped jits on a
# mesh); ``cond``/``needs`` read only replicated scalar leaves, so the
# host evaluates them directly (every process takes the same branch —
# the loop is SPMD-safe).
# --------------------------------------------------------------------------

def run_segmented(st, *, cond, needs, seg, interrupt, method: str,
                  cfg: CheckpointConfig,
                  snapshot: Callable[[Any], None] | None):
    while bool(np.asarray(cond(st))):
        st = seg(st)
        if bool(np.asarray(cond(st))):
            # The inner loop only exits with cond still true when an
            # interrupt is due (its cond is ``cond & ~needs``).
            assert bool(np.asarray(needs(st)))
            if cfg.on_boundary is not None:
                cfg.on_boundary(int(np.asarray(upd_count(method, st))))
            st = interrupt(st)
            if snapshot is not None:
                snapshot(st)
    return st


class _Restored:
    """Record of a successful restore (host bookkeeping for drills)."""

    def __init__(self, path: str, meta: dict):
        self.path = path
        self.meta = meta


#: Most recent successful restore in this process (path + meta), for
#: recovery drills that report which iteration they resumed from.
LAST_RESTORE: list[_Restored] = []


def try_restore(template, cfg: CheckpointConfig, expect_meta: dict,
                mask, rel_of_state: Callable[[Any], Any], codec=None):
    """Load + certify the latest checkpoint in ``cfg.directory`` onto
    ``template``'s substrate; returns the template unchanged when no
    checkpoint exists yet.  ``codec`` is the substrate's
    ``(encode, decode)`` of the persisted form (``persist_codec``)."""
    path = latest_checkpoint(cfg.directory) if cfg.directory else None
    if path is None:
        return template
    payload, meta = load_checkpoint(path)
    check_meta(meta, expect_meta)
    if codec is None:
        st = state_restore(template, payload, mask)
    else:
        encode, decode = codec
        st = decode(state_restore(encode(template), payload, mask))
    rel_now = float(np.asarray(rel_of_state(st)))
    rel_saved = float(meta["rel_true"])
    tol = cfg.certify_rtol * max(abs(rel_saved), np.finfo(np.float64).tiny)
    if not abs(rel_now - rel_saved) <= tol:
        raise CheckpointCertificationError(
            f"{path}: true-residual certification failed — recomputed "
            f"rel {rel_now:.17e} vs saved {rel_saved:.17e} "
            f"(rtol {cfg.certify_rtol:g})")
    LAST_RESTORE.append(_Restored(path, meta))
    return st


def make_snapshot_fn(cfg: CheckpointConfig, meta_base: dict, mask,
                     method: str, rel_of_state, gather=None,
                     is_writer: bool = True):
    """Build the per-boundary snapshot callback (None when ``cfg`` has
    no directory).  ``gather`` turns the device state into the
    persisted form (``persist_codec``), fully host-readable on
    distributed substrates."""
    if cfg.directory is None:
        return None
    os.makedirs(cfg.directory, exist_ok=True)

    def snapshot(st):
        # rel BEFORE gathering: one reduction on the live substrate.
        rel = float(np.asarray(rel_of_state(st)))
        host = gather(st) if gather is not None else st
        if not is_writer:
            return
        meta = dict(meta_base)
        tot = int(np.asarray(iter_count(method, host)))
        meta["tot"] = tot
        meta["upd"] = int(np.asarray(upd_count(method, host)))
        meta["rel_true"] = rel
        save_checkpoint(checkpoint_path(cfg.directory, tot),
                        state_payload(host, mask), meta)
        _gc(cfg.directory, cfg.keep)

    return snapshot


# --------------------------------------------------------------------------
# Local (single-substrate) checkpointed solve — entered from
# pipelined_cg.solve / ghysels_pcg.solve when checkpoint.every > 0.
# --------------------------------------------------------------------------

def checkpointed_solve(ops, b, method: str, x0, cfg: CheckpointConfig,
                       kw: dict):
    from repro.core.batched import BUILDERS

    kw = effective_kw(method, kw, cfg.every)
    build_kw = {k: v for k, v in kw.items() if k != "unroll"}
    prog = BUILDERS[method](ops, b, **build_kw)
    if prog.needs_interrupt is None or prog.interrupt is None:
        raise CheckpointError(
            f"method {method!r} exposes no interrupt boundary to "
            "checkpoint at")
    st = prog.init(jnp.zeros_like(b) if x0 is None else x0.astype(b.dtype))
    mask = exclude_mask(method, st)
    rel = make_rel_fn(method, kw)
    rel_j = jax.jit(lambda s: rel(ops, b, s))
    meta_base = solver_meta(method, b.shape[0], b.dtype, kw, cfg.every)
    meta_base["treedef"] = state_treedef_str(st)
    codec = persist_codec(method, kw, b.shape[0])
    if codec is not None:
        codec = tuple(jax.jit(f) for f in codec)
    if cfg.resume:
        st = try_restore(st, cfg, meta_base, mask, rel_j, codec=codec)
    seg = jax.jit(lambda s: jax.lax.while_loop(
        lambda t: prog.cond(t) & ~prog.needs_interrupt(t), prog.step, s))
    interrupt = jax.jit(prog.interrupt)
    snapshot = make_snapshot_fn(cfg, meta_base, mask, method, rel_j,
                                gather=codec[0] if codec else None)
    st = run_segmented(st, cond=prog.cond, needs=prog.needs_interrupt,
                       seg=seg, interrupt=interrupt, method=method,
                       cfg=cfg, snapshot=snapshot)
    return prog.finish(st)


# --------------------------------------------------------------------------
# Batched slab snapshots (DESIGN.md §19).  Slab states are persisted
# as-is at CHUNK boundaries — including in-flight ring slots — so these
# round-trips are same-substrate bitwise only: valid on the local
# backend always, and on distributed slabs only where every leaf is
# host-faithful (monolithic reduction; a staged slab's gather buffers
# are per-device mid-ladder).  The honest scope is documented in §19.
# --------------------------------------------------------------------------

def save_slab_checkpoint(path: str, B, state, meta: dict) -> dict:
    payload = dict(state_payload(state))
    payload["slab_B"] = np.asarray(B)
    meta = dict(meta)
    meta["kind"] = "slab"
    meta["treedef"] = state_treedef_str(state)
    return save_checkpoint(path, payload, meta)


def load_slab_checkpoint(path: str, template_state, expect_meta: dict
                         | None = None):
    """Returns ``(B, state, meta)`` restored onto ``template_state``'s
    substrate; ``expect_meta`` keys (plus kind/treedef) must match."""
    payload, meta = load_checkpoint(path)
    if meta.get("kind") != "slab":
        raise CheckpointMismatchError(
            f"{path}: kind {meta.get('kind')!r} is not a slab checkpoint")
    expect = dict(expect_meta or {})
    expect["treedef"] = state_treedef_str(template_state)
    bad = {k: (meta.get(k), v) for k, v in expect.items()
           if meta.get(k) != v}
    if bad:
        detail = ", ".join(f"{k}: stored {s!r} != expected {e!r}"
                           for k, (s, e) in sorted(bad.items()))
        raise CheckpointMismatchError(f"slab checkpoint mismatch: {detail}")
    if "slab_B" not in payload:
        raise CheckpointMismatchError(f"{path}: no slab_B entry")
    B = jnp.asarray(payload.pop("slab_B"))
    state = state_restore(template_state, payload)
    return B, state, meta
