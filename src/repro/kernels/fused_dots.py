"""Pallas kernel for the fused dot-product block — the paper's (K5).

p(l)-CG computes 2l+1 (sym-optimized: l+1) inner products against ONE shared
operand u per iteration (Alg. 1 line 23).  Done naively that is 2l+1 full
HBM passes over u plus one over each basis vector; fused, u is streamed ONCE
and every basis row is read once: arithmetic intensity rises from ~1/8 to
~(K)/(K+1) flop/byte — this kernel makes the local dot contribution
bandwidth-optimal before the single psum.

Layout: mat (K, N) row-major (the K basis vectors), vec (N,) carried
lane-major as (1, N).  Grid over N in blocks; a (K, 1) f32 accumulator
output block is revisited by every grid step (index_map -> (0, 0)),
relying on TPU's sequential grid execution.

Multi-RHS variant (``fused_dots_mrhs``, the serving layer's dot block,
DESIGN.md §11): the same streaming structure against S right-hand-side
rows (S, N) at once — mat is streamed ONCE for all S columns and the
(K, S) accumulator block becomes the local half of the slab's single
amortized allreduce payload.  S = 1 recovers the single-RHS kernel
exactly.  Both operands keep N on the lane axis, so a block costs its
true size in VMEM (an (N, 1) column would be lane-padded 128-fold).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

_NT = (((1,), (1,)), ((), ()))          # contract the shared N (lane) axis


def _fused_dots_kernel(mat_ref, vec_ref, o_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    m = mat_ref[...].astype(jnp.float32)      # (K, BN)
    v = vec_ref[...].astype(jnp.float32)      # (S, BN)
    o_ref[...] += jax.lax.dot_general(
        m, v, _NT, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def fused_dots_mrhs(
    mat: jax.Array, vecs: jax.Array, *, block_n: int = 32768,
    interpret: bool = False
) -> jax.Array:
    """All K*S inner products mat @ vecs.T in one HBM pass over ``mat``.

    mat (K, N), vecs (S, N) -> (K, S).  N must be a multiple of block_n,
    and block_n of 128 (ops.py pads with zeros, which do not change the
    result).
    """
    k, n = mat.shape
    s = vecs.shape[0]
    assert vecs.shape == (s, n), (mat.shape, vecs.shape)
    assert n % block_n == 0, (n, block_n)
    i0 = np.int32(0)
    out = pl.pallas_call(
        _fused_dots_kernel,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((k, block_n), lambda i: (i0, i)),
            pl.BlockSpec((s, block_n), lambda i: (i0, i)),
        ],
        out_specs=pl.BlockSpec((k, s), lambda i: (i0, i0)),
        out_shape=jax.ShapeDtypeStruct((k, s), jnp.float32),
        interpret=interpret,
    )(mat, vecs)
    return out.astype(mat.dtype)


def fused_dots(
    mat: jax.Array, vec: jax.Array, *, block_n: int = 32768,
    interpret: bool = False
) -> jax.Array:
    """All K inner products mat @ vec in one HBM pass.  N must be a multiple
    of block_n (ops.py pads with zeros, which do not change the result)."""
    k, n = mat.shape
    assert vec.shape == (n,)
    out = fused_dots_mrhs(mat, vec[None, :], block_n=block_n,
                          interpret=interpret)
    return out[:, 0]
