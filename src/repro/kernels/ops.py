"""Jit'd public wrappers around the Pallas kernels.

Each wrapper (a) pads shapes to kernel-friendly multiples (zero padding is
exact for every kernel here), (b) picks TPU-aligned block shapes, and
(c) compiles the kernel on TPU and interprets it on CPU (the tests' and
CPU rehearsals' vehicle).  Any other platform raises: no kernel silently
runs interpreted on a device.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _da
from repro.kernels import ell_spmv as _el
from repro.kernels import fused_axpy as _fa
from repro.kernels import fused_dots as _fd
from repro.kernels import fused_iter as _fi
from repro.kernels import stencil_spmv as _ss


def _interpret_default() -> bool:
    platform = jax.default_backend()
    if platform not in ("cpu", "tpu"):
        raise RuntimeError(
            f"the Pallas kernels compile for TPU and are interpreted on "
            f"CPU only; platform {platform!r} has no kernel path")
    return platform == "cpu"


# ------------------------------------------------- fused-iteration factory --

def _local_fused_spmv(op):
    """Single-device :class:`~repro.kernels.fused_iter.FusedSpmv` for the
    operator, mirroring its pure-jnp ``apply`` expression term by term
    (the bitwise contract of the superkernel); None when unsupported."""
    from repro.linalg.operators import (DiagonalOp, Stencil2D5, Stencil3D7,
                                        Stencil3D27)
    from repro.linalg.sparse import SparseOp

    if isinstance(op, DiagonalOp):
        return _fi.diagonal_spmv(op.d)
    if getattr(op, "use_kernel", False):
        # use_kernel operators route the unfused path through the
        # standalone Pallas kernels (whose reductions round differently
        # from the jnp expressions this kernel mirrors); the superkernel
        # subsumes those, but mirroring a kernel inside a kernel is not
        # a thing — no fused path, the solver fails loudly.
        return None
    if isinstance(op, SparseOp):
        return _fi.ell_spmv(op.cols, op.vals, lambda z: z, op.n)
    if isinstance(op, Stencil2D5):
        nx, ny = op.nx, op.ny

        def expr2d(z):
            g = z.reshape(nx, ny)
            p = jnp.pad(g, 1)
            out = (4.0 * g - p[:-2, 1:-1] - p[2:, 1:-1]
                   - p[1:-1, :-2] - p[1:-1, 2:])
            return out.reshape(-1)

        return _fi.resident_spmv(expr2d, lambda z: z, op.n)
    if isinstance(op, Stencil3D7):
        nx, ny, nz, eps_z = op.nx, op.ny, op.nz, op.eps_z

        def expr3d(z):
            g = z.reshape(nx, ny, nz)
            p = jnp.pad(g, 1)
            ez = jnp.asarray(eps_z, dtype=z.dtype)
            out = (
                (4.0 + 2.0 * ez) * g
                - p[:-2, 1:-1, 1:-1] - p[2:, 1:-1, 1:-1]
                - p[1:-1, :-2, 1:-1] - p[1:-1, 2:, 1:-1]
                - ez * p[1:-1, 1:-1, :-2] - ez * p[1:-1, 1:-1, 2:]
            )
            return out.reshape(-1)

        return _fi.resident_spmv(expr3d, lambda z: z, op.n)
    if isinstance(op, Stencil3D27):
        nx, ny, nz, centre = op.nx, op.ny, op.nz, op.centre

        def expr27(z):
            g = z.reshape(nx, ny, nz)
            p = jnp.pad(g, 1)
            out = centre * g
            for di in (-1, 0, 1):
                for dj in (-1, 0, 1):
                    for dk in (-1, 0, 1):
                        order = abs(di) + abs(dj) + abs(dk)
                        if order == 0:
                            continue
                        w = {1: 1.0, 2: 0.5, 3: 0.25}[order]
                        out = out - w * p[1 + di:1 + di + nx,
                                          1 + dj:1 + dj + ny,
                                          1 + dk:1 + dk + nz]
            return out.reshape(-1)

        return _fi.resident_spmv(expr27, lambda z: z, op.n)
    return None


def fused_iteration_factory(op, prec=None):
    """Factory for the fused-iteration superkernel on the LOCAL substrate
    (DESIGN.md §13), or None when the (operator, preconditioner) pair has
    no fused path — unsupported operator kinds, kernel-routed stencils,
    or non-pointwise (block-structured) preconditioners.

    The returned ``factory(layout, interpret=None, block_n=None)`` builds
    the per-iteration vector-phase callable consumed by
    ``pipelined_cg.build(..., fused_iteration=True)``.
    """
    from repro.linalg.preconditioners import inverse_diagonal

    diag = inverse_diagonal(prec)
    if diag is None:
        return None                  # block solves are not pointwise
    inv_diag = None if isinstance(diag, float) else diag
    spmv = _local_fused_spmv(op)
    if spmv is None:
        return None

    def factory(layout, interpret: bool | None = None,
                block_n: int | None = None):
        interp = _interpret_default() if interpret is None else interpret
        return _fi.build_fused_iteration(layout, spmv, inv_diag,
                                         block_n=block_n, interpret=interp)

    return factory


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@partial(jax.jit, static_argnames=("interpret",))
def stencil2d5_apply(g: jax.Array, interpret: bool | None = None) -> jax.Array:
    interpret = _interpret_default() if interpret is None else interpret
    nx, ny = g.shape
    bx = 8
    while bx * 2 <= min(nx, 256) and nx % (bx * 2) == 0:
        bx *= 2
    # lanes pad to the TPU's 128 (the interpreter takes any width)
    nxp = _round_up(nx, bx)
    nyp = _round_up(ny, 8 if interpret else 128)
    gp = jnp.pad(g, ((0, nxp - nx), (0, nyp - ny)))
    out = _ss.stencil2d5(gp, block_x=bx, interpret=interpret)
    return out[:nx, :ny]


@partial(jax.jit, static_argnames=("eps_z", "interpret"))
def stencil3d7_apply(
    g: jax.Array, eps_z: float = 1.0, interpret: bool | None = None
) -> jax.Array:
    interpret = _interpret_default() if interpret is None else interpret
    nx, ny, nz = g.shape
    bx = 8 if nx % 8 == 0 else (4 if nx % 4 == 0 else (2 if nx % 2 == 0 else 1))
    nzp = _round_up(nz, 128 if nz >= 128 else 8)
    gp = jnp.pad(g, ((0, 0), (0, 0), (0, nzp - nz)))
    out = _ss.stencil3d7(gp, eps_z=eps_z, block_x=bx, interpret=interpret)
    return out[:, :, :nz]


@partial(jax.jit, static_argnames=("interpret",))
def ell_spmv_apply(x: jax.Array, cols: jax.Array, vals: jax.Array,
                   interpret: bool | None = None) -> jax.Array:
    """Unstructured padded-row ELL SpMV (DESIGN.md §12).

    ``x`` may be longer than the row count (the distributed path passes
    the halo-extended local vector).  Rows are padded to a block multiple
    with zero-value slots — exact, since padded rows are sliced off."""
    interpret = _interpret_default() if interpret is None else interpret
    r, w = cols.shape
    # the rank-1 output block must be a multiple of the 128-lane tile
    br = min(512, _round_up(r, 128))
    rp = _round_up(r, br)
    colsp = jnp.pad(cols, ((0, rp - r), (0, 0)))
    valsp = jnp.pad(vals, ((0, rp - r), (0, 0)))
    out = _el.ell_spmv(x, colsp, valsp, block_r=br, interpret=interpret)
    return out[:r]


@partial(jax.jit, static_argnames=("interpret",))
def fused_dots(mat: jax.Array, vec: jax.Array, interpret: bool | None = None):
    interpret = _interpret_default() if interpret is None else interpret
    k, n = mat.shape
    bn = min(32768, _round_up(n, 128))
    npad = _round_up(n, bn)
    matp = jnp.pad(mat, ((0, 0), (0, npad - n)))
    vecp = jnp.pad(vec, (0, npad - n))
    return _fd.fused_dots(matp, vecp, block_n=bn, interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def fused_dots_mrhs(mat: jax.Array, vecs: jax.Array,
                    interpret: bool | None = None):
    """(K, N) x (N, S) -> (K, S): the slab dot block, mat streamed once for
    all S right-hand sides (DESIGN.md §11).  The kernel takes the
    right-hand sides lane-major (S, N); N zero-pads to a block multiple."""
    interpret = _interpret_default() if interpret is None else interpret
    k, n = mat.shape
    bn = min(32768, _round_up(n, 128))
    npad = _round_up(n, bn)
    matp = jnp.pad(mat, ((0, 0), (0, npad - n)))
    vecsp = jnp.pad(vecs.T, ((0, 0), (0, npad - n)))
    return _fd.fused_dots_mrhs(matp, vecsp, block_n=bn, interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def fused_axpy3(zk1, zm1, zm2, c1, c2, scale, interpret: bool | None = None):
    interpret = _interpret_default() if interpret is None else interpret
    (n,) = zk1.shape
    bn = min(65536, _round_up(n, 8 * _fa.LANES))
    npad = _round_up(n, bn)
    pad = lambda v: jnp.pad(v, (0, npad - n))
    out = _fa.fused_axpy3(
        pad(zk1), pad(zm1), pad(zm2), c1, c2, scale, block_n=bn,
        interpret=interpret,
    )
    return out[:n]


@partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_attention(
    q: jax.Array,       # (B, H, D)
    k: jax.Array,       # (B, S, Hkv, D)
    v: jax.Array,       # (B, S, Hkv, D)
    kv_len: jax.Array | int,
    block_s: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Single-token GQA decode attention over a (possibly padded) KV cache.
    Returns (B, H, D) in q.dtype."""
    interpret = _interpret_default() if interpret is None else interpret
    b, h, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    sp = _round_up(s, block_s)
    kp = jnp.pad(k, ((0, 0), (0, sp - s), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, sp - s), (0, 0), (0, 0)))
    qg = q.reshape(b, hkv, g, d)
    kt = jnp.transpose(kp, (0, 2, 1, 3))     # (B, Hkv, S, D)
    vt = jnp.transpose(vp, (0, 2, 1, 3))
    ln = jnp.full((1, 1), kv_len, jnp.int32)
    o, m, l = _da.decode_attention_stats(
        qg, kt, vt, ln, block_s=block_s, interpret=interpret
    )
    out = o / jnp.maximum(l, 1e-30)
    return out.reshape(b, h, d).astype(q.dtype)


def decode_attention_stats(q, k, v, kv_len, block_s: int = 512, interpret=None):
    """Unnormalized (o, m, l) for cross-shard split-KV combine."""
    interpret = _interpret_default() if interpret is None else interpret
    b, h, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    sp = _round_up(s, block_s)
    kp = jnp.pad(k, ((0, 0), (0, sp - s), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, sp - s), (0, 0), (0, 0)))
    qg = q.reshape(b, hkv, g, d)
    kt = jnp.transpose(kp, (0, 2, 1, 3))
    vt = jnp.transpose(vp, (0, 2, 1, 3))
    ln = jnp.full((1, 1), kv_len, jnp.int32)
    return _da.decode_attention_stats(
        qg, kt, vt, ln, block_s=block_s, interpret=interpret
    )
