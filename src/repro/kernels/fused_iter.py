"""Fused-iteration superkernel: the whole p(l)-CG vector phase in ONE
pass over the basis slab (DESIGN.md §13).

The per-iteration hot path of ``repro.core.pipelined_cg`` is, unfused,
~a dozen separate memory-bound passes over the state slab: the
SPMV (K1), the pointwise preconditioner, the pipeline-fill copies, the
2l+2 recurrence AXPYs of K4, the 2l+1 dot products of K5 and the x/p
updates of K6 — each re-reading basis vectors the previous op just
wrote.  This kernel is the deep-pipeline analogue of the kernel fusion
Cornelis/Cools/Vanroose assume for the local phase of p(l)-CG
(arXiv:1801.04728): per row tile, every basis vector is read from HBM
once, every updated row is written once, and the 2l+1 dot-block
*partials* are accumulated in VMEM — the single global reduction that
follows (``SolverOps.start_partials``) carries the same payload as the
unfused ``ops.start`` without touching the slab again.

Division of labour (see ``pipelined_cg.iteration``):

* the *scalar* phase (arrival scatter into G, K2 column correction, K3
  Hessenberg column) runs outside — O(l^2) scalars, no vector traffic;
* this kernel runs the *vector* phase from precomputed ring-row indices
  (``idx``, int32) and scalar coefficients (``scal``), so fused and
  unfused paths evaluate literally the same expressions on the same
  operands — the bitwise-parity contract of tests/test_fused_iter.py.

Tiling: the slab is blocked over its trailing N axis; the SPMV operand
(z ring-top, halo-extended on distributed substrates) rides as a
VMEM-resident side input prepared by the wrapper (one extra vector read
— the distributed halo exchange stays OUTSIDE the kernel, riding the
open reduction windows exactly as before, DESIGN.md §12).  Each grid
step emits its (2l+1,) dot partials into a per-tile output column; the
wrapper chain-sums the tiles (vmap-safe — no cross-grid-step carried
state).  The default is a single column tile: multi-tile runs change
only the dot partial summation ORDER (documented tight-tail behaviour,
same policy as DESIGN.md §12); all row updates stay bitwise regardless
of tiling.

On TPU: ``idx``/``scal`` are scalar-prefetch (SMEM) operands and every
vector is a lane-major (1, N) row read and written through the refs, so
Mosaic lowers the single-tile kernel for the stencil and diagonal
operators wherever that tile fits scoped VMEM (a 256x256 2D stencil on
v5e).  Beyond it the build raises :class:`FusedSlabTooLarge` before
compiling; tiling full-size problems is ROADMAP A3.  The ELL plug-in's
gather has no Mosaic lowering and runs in interpret mode only.

The state slab is input/output-aliased (``input_output_aliases``), so on
TPU the iteration updates the slab in place — no per-iteration state
copy; ``donate_argnums`` at the jit boundaries of the slab programs
extends the same guarantee across chunks (DESIGN.md §13).  Off-TPU the
kernel runs in interpret mode, the repo-wide validation vehicle.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------- layout --

LANES = 128            # a TPU vreg row; a tile is (_SUBLANES, LANES)
_SUBLANES = 8           # a vreg / VMEM tile holds rows in groups of 8


@dataclasses.dataclass(frozen=True)
class SlabLayout:
    """Row map and row storage of the p(l)-CG state slab (NV, *row_shape).

    Rows 0 .. (l+1)*RB-1 hold the l+1 auxiliary-basis ring buffers
    (basis k, ring slot j -> row k*RB + j), followed by the 3-deep u
    ring, the search direction p and the iterate x.  One array, one
    leading row axis — what a ``donate_argnums``'d jit boundary wants.

    Row storage (DESIGN.md §13).  ``tiled`` rows — the unfused path's —
    are (R, 128) with R = 8 * ceil(n / 1024): every row is whole (8, 128)
    TPU tiles, so a row is read and stored as dense vregs and its flat
    view (R * 128,) is the same bytes (no relayout).  The R * 128 - n
    tail entries are zero in every row and stay zero: the vector phase
    is elementwise or a dot, and the operator and preconditioner see
    only ``from_row``'s first n entries, their results padded back by
    ``to_row``.  Flat rows (``tiled=False``, the (NV, N) slab) are the
    superkernel's: its Pallas kernel tiles the trailing N axis, and a
    relayout at its boundary on every iteration would cost what it saves.
    The solver picks the storage from the path (:meth:`for_solver`); it
    is not a setting.

    ``recurrence`` selects the top-basis update of the vector phase
    (DESIGN.md §18): ``"ghysels"`` (default) recurs z^(l) through its own
    independent three-term recurrence (the paper's Alg. 1 line 22);
    ``"stable"`` recurs u first and recomputes z^(l)_{i+1} = M^{-1}
    u_{i+1} from it — the coupled recurrence of Cools/Cornelis/Vanroose
    (arXiv:1902.03100), which pins the auxiliary basis to the u ring so
    local rounding in the z recurrence can no longer drift independently.
    Exactly one pointwise preconditioner apply per iteration either way,
    and the early (pipeline-fill) phase is bitwise identical in both
    modes.  A trace-time choice: both kernel paths branch at build time,
    so the compiled HLO carries only the selected variant.
    """

    l: int
    RB: int
    recurrence: str = "ghysels"
    n: int | None = None       # vector length; None: flat rows of any n
    tiled: bool = False

    @classmethod
    def for_solver(cls, l: int, n: int, recurrence: str = "ghysels",
                   fused: bool = False) -> "SlabLayout":
        """The layout ``pipelined_cg.build`` uses: tiled rows unless the
        vector phase runs through the superkernel."""
        return cls(l=l, RB=max(l + 1, 3), recurrence=recurrence, n=n,
                   tiled=not fused)

    @property
    def row_shape(self) -> tuple[int, ...]:
        if not self.tiled:
            return (self.n,)
        tile = _SUBLANES * LANES
        return (_SUBLANES * -(-self.n // tile), LANES)

    def to_row(self, v: jax.Array) -> jax.Array:
        """A flat n-vector as one slab row (zero tail when tiled)."""
        if not self.tiled:
            return v
        rows, lanes = self.row_shape
        return jnp.pad(v, (0, rows * lanes - self.n)).reshape(rows, lanes)

    def from_row(self, r: jax.Array) -> jax.Array:
        """A slab row as the flat n-vector it stores."""
        if not self.tiled:
            return r
        return r.reshape(-1)[:self.n]

    def row_map(self, f: Callable[[jax.Array], jax.Array]) -> Callable:
        """A map of flat n-vectors (an operator, a block solve) as a map
        of slab rows: it sees ``from_row``, its result goes back through
        ``to_row``."""
        if not self.tiled:
            return f
        return lambda r: self.to_row(f(self.from_row(r)))

    def row_scale(self, diag: jax.Array | float) -> Callable:
        """The diagonal map diag(``diag``) as a map of slab rows: an
        elementwise product, the diagonal laid out as a row (1.0: the
        identity)."""
        if isinstance(diag, float):
            return (lambda r: r) if diag == 1.0 else (lambda r: diag * r)
        return lambda r: self.to_row(diag.astype(r.dtype)) * r

    def rows_to_flat(self, S: jax.Array) -> jax.Array:
        """A slab (..., NV, *row_shape) as flat rows (..., NV, n): the
        substrate-independent form a checkpoint persists."""
        if not self.tiled:
            return S
        return S.reshape(S.shape[:-2] + (-1,))[..., :self.n]

    def rows_from_flat(self, F: jax.Array) -> jax.Array:
        """Inverse of :meth:`rows_to_flat` (the tail comes back zero)."""
        if not self.tiled:
            return F
        rows, lanes = self.row_shape
        pad = [(0, 0)] * (F.ndim - 1) + [(0, rows * lanes - self.n)]
        return jnp.pad(F, pad).reshape(F.shape[:-1] + (rows, lanes))

    @property
    def u_off(self) -> int:
        return (self.l + 1) * self.RB

    @property
    def p_row(self) -> int:
        return self.u_off + 3

    @property
    def x_row(self) -> int:
        return self.u_off + 4

    @property
    def nv(self) -> int:
        return self.u_off + 5

    def zk_row(self, k: int, j):
        """Slab row of basis k's ring slot for iterate index j (traced)."""
        return k * self.RB + jnp.mod(j, self.RB)

    def u_row(self, j):
        return self.u_off + jnp.mod(j, 3)


# Index-vector layout (all entries are PRE-MODDED slab rows except the
# trailing flags).  Built by ``pipelined_cg.iteration``; consumed
# positionally by the kernel, so both sides share these offsets.
def idx_layout(l: int) -> dict[str, int]:
    return {
        "fill": 0,            # l entries : write rows zk(k, i+1)
        "rec_w": l,           # l entries : write rows zk(k, i-l+k+1)
        "rec_a": 2 * l,       # l entries : read  rows zk(k+1, i-l+k+1)
        "rec_b": 3 * l,       # l entries : read  rows zk(k, i-l+k)
        "rec_c": 4 * l,       # l entries : read  rows zk(k, i-l+k-1)
        "z_top": 5 * l,       # zk(l, i)
        "zl_im1": 5 * l + 1,  # zk(l, i-1)
        "z_w": 5 * l + 2,     # zk(l, i+1)   (write)
        "u_i": 5 * l + 3,     # u(i)
        "u_im1": 5 * l + 4,   # u(i-1)
        "u_w": 5 * l + 5,     # u(i+1)       (write)
        "p_im": 5 * l + 6,    # zk(0, i-l)
        "mat_v": 5 * l + 7,   # l entries : dot rows zk(0, i-2l+1+t), t<l
        "mat_z": 6 * l + 7,   # l-1 entries: dot rows zk(l, i-l+2+t), t<l-1
        "f_fill": 7 * l + 6,  # l flags    : pipeline-fill copy masks
        "f_late": 8 * l + 6,  # i >= l
        "f_first": 8 * l + 7,  # i == l
        "f_upd": 8 * l + 8,   # i >= l+1
        "size": 8 * l + 9,
    }


# Scalar-vector layout (solver dtype).
def scal_layout(l: int) -> dict[str, int]:
    return {
        "sig_i": 0,
        "gam_new": 1,
        "d2": 2,
        "dlt_safe": 3,
        "zet_prev": 4,
        "d_prev": 5,
        "eta_new_safe": 6,
        "eta0_safe": 7,
        "c1": 8,              # l entries : sig[k] - gam_new
        "size": 8 + l,
    }


# Telemetry-row layout (solver dtype; DESIGN.md §16).  One row of the
# (cap, K) on-device telemetry ring per iteration — every entry is a
# scalar the iteration ALREADY computed (replicated on distributed
# substrates), so recording it costs one K-wide row store and no
# communication.  Shared between the solver (which writes rows) and
# ``repro.core.types.TelemetrySlab`` / ``repro.obs`` (which decode them),
# the same positional-layout contract as ``idx_layout``/``scal_layout``.
def tel_layout(l: int) -> dict[str, int]:
    return {
        "iter": 0,         # global iteration counter (tot) of this row
        "upd": 1,          # solution updates after this iteration
        "rnorm": 2,        # recursive residual M-norm |zeta| (-1: none)
        "age": 3,          # in-flight reduction handles after this iter
        "breakdown": 4,    # square-root breakdown flag (line 11)
        "restart": 5,      # 1.0 on a restart boundary row
        "replacement": 6,  # 1.0 when the restart was a due residual
                           # replacement (not a breakdown)
        "gap": 7,          # governor's attainable-accuracy gap estimate
                           # (relative units; -1/0 when ungoverned,
                           # DESIGN.md §18)
        "action": 8,       # governor action on this row: 0 none,
                           # 1 gap-arm replacement, 2 patience-arm
                           # replacement, 3 stagnation declared
        "dots": 9,         # 2l+1 entries: the arrived dot block consumed
                           # this iteration (zeros during pipeline fill)
        "size": 9 + (2 * l + 1),
    }


# ------------------------------------------------------------ SPMV tiles --

_I0 = np.int32(0)       # an int32 block index under x64 too


def _row(v: jax.Array) -> jax.Array:
    """A vector as one lane-major (1, len) row — the kernel's layout for
    every vector operand and value."""
    return v.reshape(1, -1)


@dataclasses.dataclass(frozen=True)
class FusedSpmv:
    """Operator plug-in for the superkernel.

    ``prepare(z_top)`` runs OUTSIDE the kernel (halo exchange, reshape)
    and returns the extra operand arrays; ``specs(block_n, n)`` their
    BlockSpecs; ``tile(extras, z_tile, pid, block_n)`` computes the
    (1, block_n) az row tile inside the kernel — written to evaluate
    exactly the same jnp expression as the unfused ``ops.apply_a`` so
    row updates stay bitwise (tests/test_fused_iter.py).
    """

    prepare: Callable[[jax.Array], tuple]
    specs: Callable[[int, int], list]
    tile: Callable[[Sequence, jax.Array, jax.Array, int], jax.Array]


def resident_spmv(expr: Callable[[jax.Array], jax.Array],
                  prepare: Callable[[jax.Array], jax.Array],
                  ext_len: int) -> FusedSpmv:
    """Stencil-style SPMV: the (halo-extended) operand vector is VMEM-
    resident for the whole grid; each tile slices its rows out of the
    full stencil evaluation (a single-tile grid — the bitwise default
    and the only one Mosaic lowers — takes it whole)."""

    def specs(block_n: int, n: int):
        return [pl.BlockSpec((1, ext_len), lambda i, *_: (_I0, _I0))]

    def tile(extras, z_tile, pid, block_n):
        az_full = _row(expr(extras[0][...]))
        if az_full.shape[1] == block_n:
            return az_full
        return jax.lax.dynamic_slice(az_full, (_I0, pid * block_n),
                                     (1, block_n))

    return FusedSpmv(prepare=lambda z: (_row(prepare(z)),), specs=specs,
                     tile=tile)


def diagonal_spmv(d: jax.Array) -> FusedSpmv:
    """A = diag(d): az is elementwise — the tile needs no halo at all."""

    def specs(block_n: int, n: int):
        return [pl.BlockSpec((1, block_n), lambda i, *_: (_I0, i))]

    def tile(extras, z_tile, pid, block_n):
        return extras[0][...].astype(z_tile.dtype) * z_tile

    return FusedSpmv(prepare=lambda z: (_row(d),), specs=specs, tile=tile)


def ell_spmv(cols: jax.Array, vals: jax.Array,
             prepare: Callable[[jax.Array], jax.Array],
             ext_len: int) -> FusedSpmv:
    """Unstructured padded-row ELL rows: cols/vals tile with the rows,
    the (halo-extended) x stays resident for the one gather per tile;
    the row sum uses the explicit add chain of
    ``linalg.sparse.ell_rowsum`` so local and distributed applies keep
    rounding identically (DESIGN.md §12).  Mosaic lowers no general
    gather, so this plug-in runs in interpret mode only."""
    w = cols.shape[1]

    def specs(block_n: int, n: int):
        return [
            pl.BlockSpec((1, ext_len), lambda i, *_: (_I0, _I0)),
            pl.BlockSpec((block_n, w), lambda i, *_: (i, _I0)),
            pl.BlockSpec((block_n, w), lambda i, *_: (i, _I0)),
        ]

    def tile(extras, z_tile, pid, block_n):
        x = extras[0][...][0]
        cols_t = extras[1][...]
        vals_t = extras[2][...].astype(z_tile.dtype)
        gathered = x[cols_t].astype(vals_t.dtype)
        acc = vals_t[..., 0] * gathered[..., 0]
        for s in range(1, w):
            acc = acc + vals_t[..., s] * gathered[..., s]
        return _row(acc)

    return FusedSpmv(prepare=lambda z: (_row(prepare(z)), cols, vals),
                     specs=specs, tile=tile)


# ---------------------------------------------------------------- kernel --

# Mosaic's default scoped-VMEM limit for one kernel on TPU v5e: the
# compiler refuses a kernel whose blocks and temporaries need more.
VMEM_LIMIT_BYTES = 16 * 2**20


class FusedSlabTooLarge(ValueError):
    """The superkernel's slab tile does not fit the chip's VMEM.

    Raised while tracing, before anything compiles: a fused iteration
    never falls back to the unfused path or to the interpreter.  Tiling
    the slab for full-size problems is future work (ROADMAP A3)."""


def tile_vmem_bytes(nv: int, block_n: int, n_tiles: int, dsize: int,
                    extra_blocks: Sequence[Sequence[int]]) -> int:
    """Scoped VMEM the kernel's blocks take: the (NV, block_n) slab tile
    in and out plus every side-input block, rows rounded up to the
    8-sublane tile, double-buffered when the grid has more than one
    step.  (Measured against Mosaic for v5e: a (14, 131072) float32
    single tile with its resident stencil row asks 20.11M of 16M.)"""
    pad = lambda rows: -(-rows // _SUBLANES) * _SUBLANES
    words = 2 * pad(nv) * block_n
    words += sum(pad(shape[0]) * int(np.prod(shape[1:]))
                 for shape in extra_blocks)
    return (1 if n_tiles == 1 else 2) * words * dsize


def build_fused_iteration(
    layout: SlabLayout,
    spmv: FusedSpmv,
    inv_diag: jax.Array | None = None,
    *,
    block_n: int | None = None,
    interpret: bool = False,
) -> Callable:
    """Compile-time assembly of the superkernel for one (operator,
    preconditioner, depth) configuration.

    Returns ``fiter(S, idx, scal) -> (S', partials)``: the full vector
    phase of one p(l)-CG iteration — SPMV + pointwise preconditioner +
    fill copies + K4 recurrences + ring writes + K6 x/p updates + local
    dot-block partials — with the slab read once and written once per
    tile (``input_output_aliases`` pins S' to S's buffer).

    ``idx``/``scal`` ride as scalar-prefetch (SMEM) operands; the kernel
    reads and writes slab rows through the refs at those indices.  Every
    read happens before the first write, so the result is the same
    whether or not the runtime aliases the slab's in and out blocks.

    ``inv_diag`` enables the pointwise (Jacobi) preconditioner tile;
    None means identity.  Block-structured preconditioners have no fused
    path (their block solve is not pointwise) — ``fused_iteration_factory``
    returns None for them and the solver falls back loudly.
    """
    l, nv = layout.l, layout.nv
    IX = idx_layout(l)
    IS = scal_layout(l)
    nd = 2 * l + 1
    has_prec = inv_diag is not None
    if layout.recurrence not in ("ghysels", "stable"):
        raise ValueError(f"unknown recurrence {layout.recurrence!r} "
                         "(want 'ghysels' or 'stable')")
    stable = layout.recurrence == "stable"
    x_row, p_row = layout.x_row, layout.p_row

    def kernel(idx_ref, scal_ref, s_ref, *rest):
        *extra_refs, o_ref, acc_ref = rest
        if has_prec:
            *extra_refs, prec_ref = extra_refs
        pid = pl.program_id(0)
        bn = s_ref.shape[1]

        def ix(name, k=0):
            return idx_ref[IX[name] + k]

        def sc(name, k=0):
            return scal_ref[IS[name] + k]

        def get(row):                        # (1, BN) slab row
            return s_ref[pl.ds(row, 1), :]

        late = ix("f_late") != 0
        z_top = get(ix("z_top"))
        u_i = get(ix("u_i"))
        u_im1 = get(ix("u_im1"))

        # ---- (K1) SPMV + pointwise preconditioner ------------------------
        az = spmv.tile(extra_refs, z_top, pid, bn)
        u_new0 = az - sc("sig_i") * u_i
        u_new = jnp.where(
            late,
            (u_new0 - sc("gam_new") * u_i
             - sc("d2") * u_im1) / sc("dlt_safe"),
            u_new0)
        if stable:
            # Coupled recurrence (arXiv:1902.03100, DESIGN.md §18): the
            # top basis vector is recomputed as M^{-1} u_{i+1} from the
            # freshly recurred u instead of recurring independently.
            # Early iterations are bitwise-unchanged: u_new == u_new0
            # there, so prec(u_new) == the ghysels path's z_new0.
            z_new = prec_ref[...] * u_new if has_prec else u_new
            z_fill = z_new
        else:
            z_new0 = prec_ref[...] * u_new0 if has_prec else u_new0
            zl_im1 = get(ix("zl_im1"))
            z_new = jnp.where(
                late,
                (z_new0 - sc("gam_new") * z_top
                 - sc("d2") * zl_im1) / sc("dlt_safe"),
                z_new0)
            z_fill = z_new0

        # ---- pipeline-fill copies (lines 5-7) ----------------------------
        fills = [jnp.where(ix("f_fill", k) != 0, z_fill, get(ix("fill", k)))
                 for k in range(l)]

        # ---- (K4) stable basis recurrences (masked late) -----------------
        recs = []
        for k in range(l):
            zk1 = get(ix("rec_a", k))
            zm1 = get(ix("rec_b", k))
            zm2 = get(ix("rec_c", k))
            rec = (zk1 + sc("c1", k) * zm1
                   - sc("d2") * zm2) / sc("dlt_safe")
            recs.append(jnp.where(late, rec, get(ix("rec_w", k))))

        # ---- (K5) local dot-block partials -------------------------------
        # Rows i-2l+1..i+1 of G column i+1: the ZK^(0) V-range (last entry
        # freshly recurred), the ZK^(l) Z-range, and z_{i+1} itself.
        rows = [get(ix("mat_v", t)) for t in range(l)] + [recs[0]]
        rows += [get(ix("mat_z", t)) for t in range(l - 1)] + [z_new]
        mat = jnp.concatenate(rows, axis=0)  # (2l+1, BN)

        # ---- (K6) solution/search-direction updates ----------------------
        x_old = s_ref[x_row:x_row + 1, :]
        p_old = s_ref[p_row:p_row + 1, :]
        p_first = s_ref[0:1, :] / sc("eta0_safe")
        p_new = (get(ix("p_im"))
                 - sc("d_prev") * p_old) / sc("eta_new_safe")
        x_new = x_old + sc("zet_prev") * p_old
        do_upd = ix("f_upd") != 0
        is_first = ix("f_first") != 0

        # ---- the one slab write, in the unfused path's order -------------
        o_ref[...] = s_ref[...]
        for k in range(l):
            o_ref[pl.ds(ix("fill", k), 1), :] = fills[k]
        for k in range(l):
            o_ref[pl.ds(ix("rec_w", k), 1), :] = recs[k]
        o_ref[pl.ds(ix("z_w"), 1), :] = z_new
        o_ref[pl.ds(ix("u_w"), 1), :] = u_new
        o_ref[x_row:x_row + 1, :] = jnp.where(do_upd, x_new, x_old)
        o_ref[p_row:p_row + 1, :] = jnp.where(
            is_first, p_first, jnp.where(do_upd, p_new, p_old))

        # Per-tile partials; the wrapper chain-sums tiles (a single tile
        # — the bitwise default — makes the sum the identity).  The
        # expression mirrors types.dot_block_rows exactly: a trailing-
        # axis reduce is bitwise-stable across the interpreter and vmap
        # where a dot_general is not.
        acc_ref[...] = (mat * u_new).sum(axis=1, keepdims=True)

    def fiter(S: jax.Array, idx: jax.Array, scal: jax.Array):
        n = S.shape[1]
        bn = n if block_n is None else block_n
        assert n % bn == 0, (n, bn)
        nb = n // bn
        dtype = jnp.dtype(S.dtype)
        z_top = jax.lax.dynamic_index_in_dim(S, idx[IX["z_top"]], 0,
                                             keepdims=False)
        extras = spmv.prepare(z_top)
        side = spmv.specs(bn, n)
        if has_prec:
            side.append(pl.BlockSpec((1, bn), lambda i, *_: (_I0, i)))
            extras = (*extras, _row(inv_diag.astype(dtype)))
        if not interpret:
            need = tile_vmem_bytes(nv, bn, nb, dtype.itemsize,
                                   [sp.block_shape for sp in side])
            if need > VMEM_LIMIT_BYTES:
                raise FusedSlabTooLarge(
                    f"fused iteration: a ({nv}, {bn}) {dtype.name} slab "
                    f"tile needs {need / 2**20:.1f} MiB of VMEM, over the "
                    f"{VMEM_LIMIT_BYTES / 2**20:.0f} MiB limit (n={n}); "
                    f"run unfused (fused_iteration=False)")
        out, acc = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(nb,),
                in_specs=[pl.BlockSpec((nv, bn), lambda i, *_: (_I0, i)),
                          *side],
                out_specs=[
                    pl.BlockSpec((nv, bn), lambda i, *_: (_I0, i)),
                    pl.BlockSpec((nd, 1), lambda i, *_: (_I0, i)),
                ],
            ),
            out_shape=[
                jax.ShapeDtypeStruct((nv, n), dtype),
                jax.ShapeDtypeStruct((nd, nb), dtype),
            ],
            input_output_aliases={2: 0},     # slab updates in place
            interpret=interpret,
        )(idx.astype(jnp.int32), scal.astype(dtype), S, *extras)
        partials = acc[:, 0]
        for t in range(1, nb):               # static chain over tiles
            partials = partials + acc[:, t]
        return out, partials

    return fiter


def custom_call_hbm_bytes(layout: SlabLayout, n: int, dsize: int = 8,
                          extra_bytes: int = 0, n_tiles: int = 1) -> int:
    """HBM traffic XLA's cost analysis attributes to the compiled
    superkernel on TPU, where a ``pallas_call`` is an opaque custom call:
    operand bytes + result bytes — the slab once in, once out, the
    resident SPMV operand per tile, and the O(l) scalar/partial bundles.
    This is the ``fused_bytes_per_iter`` roofline of DESIGN.md §13; the
    interpret-mode numbers measured off-TPU upper-bound it (the
    interpreter re-materializes kernel-interior temporaries that the
    Mosaic compilation keeps in VMEM)."""
    slab = layout.nv * n * dsize
    idx_scal = (idx_layout(layout.l)["size"] * 4
                + scal_layout(layout.l)["size"] * dsize)
    partials = (2 * layout.l + 1) * dsize
    resident = n_tiles * (n * dsize + extra_bytes)
    return 2 * slab + resident + idx_scal + partials
