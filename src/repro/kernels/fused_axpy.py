"""Pallas kernel for the fused three-term recurrence — the paper's (K4).

Every basis update in p(l)-CG has the same shape (Alg. 1 lines 19-21):

    out = (zk1 + c1 * zm1 + c2 * zm2) * s

As three separate AXPYs this is 9 vector streams through HBM; fused it is 4
(3 reads + 1 write) — a 2.25x cut of the memory-roofline term of the
iteration body.  The vectors stream as (rows, 128) lane tiles; the
three scalars ride in SMEM.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _fused_axpy_kernel(zk1_ref, zm1_ref, zm2_ref, c_ref, o_ref):
    c1 = c_ref[0]
    c2 = c_ref[1]
    s = c_ref[2]
    x = zk1_ref[...].astype(jnp.float32)
    y = zm1_ref[...].astype(jnp.float32)
    z = zm2_ref[...].astype(jnp.float32)
    o_ref[...] = ((x + c1 * y + c2 * z) * s).astype(o_ref.dtype)


def fused_axpy3(
    zk1: jax.Array,
    zm1: jax.Array,
    zm2: jax.Array,
    c1: jax.Array,
    c2: jax.Array,
    scale: jax.Array,
    *,
    block_n: int = 65536,
    interpret: bool = False,
) -> jax.Array:
    """(zk1 + c1*zm1 + c2*zm2) * scale in a single HBM pass.

    1-D inputs of equal length N, N % block_n == 0 and block_n % 1024 ==
    0 (ops.py pads): each grid step streams a (block_n/128, 128) tile."""
    (n,) = zk1.shape
    assert zm1.shape == zm2.shape == (n,)
    assert n % block_n == 0 and block_n % (8 * LANES) == 0, (n, block_n)
    rows, block_rows = n // LANES, block_n // LANES
    coeffs = jnp.stack([jnp.asarray(c1), jnp.asarray(c2),
                        jnp.asarray(scale)]).astype(jnp.float32)
    tile = pl.BlockSpec((block_rows, LANES), lambda i: (i, np.int32(0)))
    as2d = lambda v: v.reshape(rows, LANES)
    return pl.pallas_call(
        _fused_axpy_kernel,
        grid=(rows // block_rows,),
        in_specs=[tile, tile, tile,
                  pl.BlockSpec((3,), lambda i: (np.int32(0),),
                               memory_space=pltpu.SMEM)],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), zk1.dtype),
        interpret=interpret,
    )(as2d(zk1), as2d(zm1), as2d(zm2), coeffs).reshape(n)
