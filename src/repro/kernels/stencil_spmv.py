"""Pallas TPU kernels for stencil SPMV — the paper's (K1) hot spot.

TPU-native rethink of the PETSc CSR SpMV (DESIGN.md §8): the benchmark
matrices are stencils, so instead of gather-bound CSR we tile the *grid*
into VMEM row blocks.  Each program instance loads a contiguous
(BX, ny[, nz]) tile plus its two halos — every load is contiguous and
(8,128)-tileable, no gathers.

Block-shape guidance (ops.py enforces): BX multiple of 8, trailing dim
padded to a multiple of 128.  The 2D kernel reads its halo rows straight
from ``g`` through 8-row blocks (the TPU's sublane tile), so no halo
array is built in HBM.  Index maps return int32 whatever
``jax_enable_x64`` says (Mosaic refuses 64-bit block indices).
VMEM footprint per program:
  2D : (2·BX + 2·8 + 2·BX) · ny · 4 B  — double-buffered g tile, the two
       halo tiles and out
  3D : ~5 · BX · ny · nz · 4 B
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_I0 = np.int32(0)       # an int32 block index under x64 too
_HALO = 8               # halo rows travel as one sublane tile


# ---------------------------------------------------------------- 2D 5-pt --

def _stencil2d5_kernel(g_ref, up_ref, dn_ref, o_ref):
    i = pl.program_id(0)
    g = g_ref[...]                       # (BX, ny)
    bx, ny = g.shape
    zero = jnp.zeros((), g.dtype)
    row = jax.lax.broadcasted_iota(jnp.int32, g.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, g.shape, 1)
    # neighbour rows across the block edges (zero at the domain edges)
    up = jnp.where(i > 0, up_ref[_HALO - 1:_HALO, :], zero)     # (1, ny)
    dn = jnp.where(i < pl.num_programs(0) - 1, dn_ref[0:1, :], zero)
    roll = lambda shift, axis: pltpu.roll(g, np.int32(shift), axis)
    above = jnp.where(row == 0, up, roll(1, 0))                 # i-1
    below = jnp.where(row == bx - 1, dn, roll(bx - 1, 0))       # i+1
    left = jnp.where(col == 0, zero, roll(1, 1))                # j-1
    right = jnp.where(col == ny - 1, zero, roll(ny - 1, 1))     # j+1
    o_ref[...] = 4.0 * g - above - below - left - right


def stencil2d5(g: jax.Array, *, block_x: int = 256, interpret: bool = False):
    """5-point Laplacian on an (nx, ny) grid, homogeneous Dirichlet BCs.

    The wrapper (ops.py) guarantees nx % block_x == 0 and block_x % 8 ==
    0.  Block i reads, besides its own rows, the 8-row tile just above
    it (whose last row is its upper halo) and the one just below (whose
    first row is its lower halo); the kernel zeroes them at the domain
    boundary.
    """
    nx, ny = g.shape
    assert nx % block_x == 0 and block_x % _HALO == 0, (nx, block_x)
    nb = nx // block_x
    per = block_x // _HALO               # halo tiles per row block
    last = nx // _HALO - 1

    return pl.pallas_call(
        _stencil2d5_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_x, ny), lambda i: (i, _I0)),
            pl.BlockSpec((_HALO, ny),
                         lambda i: (jnp.maximum(i * per - 1, 0), _I0)),
            pl.BlockSpec((_HALO, ny),
                         lambda i: (jnp.minimum((i + 1) * per, last), _I0)),
        ],
        out_specs=pl.BlockSpec((block_x, ny), lambda i: (i, _I0)),
        out_shape=jax.ShapeDtypeStruct((nx, ny), g.dtype),
        interpret=interpret,
    )(g, g, g)


# ---------------------------------------------------------------- 3D 7-pt --

def _stencil3d7_kernel(eps_z, g_ref, up_ref, dn_ref, o_ref):
    g = g_ref[...]                       # (BX, ny, nz)
    gx = jnp.concatenate([up_ref[...], g, dn_ref[...]], axis=0)
    gy1 = jnp.pad(g[:, :-1, :], ((0, 0), (1, 0), (0, 0)))
    gy2 = jnp.pad(g[:, 1:, :], ((0, 0), (0, 1), (0, 0)))
    gz1 = jnp.pad(g[:, :, :-1], ((0, 0), (0, 0), (1, 0)))
    gz2 = jnp.pad(g[:, :, 1:], ((0, 0), (0, 0), (0, 1)))
    ez = jnp.asarray(eps_z, g.dtype)
    o_ref[...] = (
        (4.0 + 2.0 * ez) * g - gx[:-2] - gx[2:] - gy1 - gy2 - ez * gz1 - ez * gz2
    )


def stencil3d7(
    g: jax.Array, eps_z: float = 1.0, *, block_x: int = 8, interpret: bool = False
):
    """Anisotropic 7-point Laplacian on an (nx, ny, nz) grid (Dirichlet)."""
    nx, ny, nz = g.shape
    assert nx % block_x == 0, (nx, block_x)
    nb = nx // block_x
    gb = g.reshape(nb, block_x, ny, nz)
    zpl = jnp.zeros((1, ny, nz), g.dtype)
    up = jnp.concatenate([zpl, gb[:-1, -1]], axis=0)         # (nb, ny, nz)
    dn = jnp.concatenate([gb[1:, 0], zpl], axis=0)

    return pl.pallas_call(
        functools.partial(_stencil3d7_kernel, eps_z),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_x, ny, nz), lambda i: (i, _I0, _I0)),
            pl.BlockSpec((1, ny, nz), lambda i: (i, _I0, _I0)),
            pl.BlockSpec((1, ny, nz), lambda i: (i, _I0, _I0)),
        ],
        out_specs=pl.BlockSpec((block_x, ny, nz), lambda i: (i, _I0, _I0)),
        out_shape=jax.ShapeDtypeStruct((nx, ny, nz), g.dtype),
        interpret=interpret,
    )(g, up, dn)
