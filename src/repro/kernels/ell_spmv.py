"""Pallas kernel for the padded-row (ELL) unstructured SpMV
(DESIGN.md §12) — the irregular counterpart of ``stencil_spmv``.

Storage is dense-rectangular: ``cols``/``vals`` are (R, W) with W =
max-nnz-per-row and zero-valued padding, so CSR's ragged row pointers
never reach the kernel.  The one irregular access, ``x[cols]``, is an
XLA gather in front of the kernel: Mosaic lowers no general gather from
a VMEM-resident vector (only within-vreg 2-D gathers), so the kernel
cannot hold ``x`` and gather it itself.  The wrapper hands the kernel
the gathered values and ``vals`` slot-major, (W, R): every tile is a
lane-dense (W, BR) block, and the row sum is an explicit add chain over
its W sublane rows (the ``linalg.sparse.ell_rowsum`` order, so kernel
and jnp applies round alike) into a lane-major (1, BR) output block.

The wrapper (ops.py) keeps rows RCM-ordered — the partitioner's
bandwidth reduction (``repro.linalg.partition``) makes consecutive rows
hit near-consecutive x slots, which keeps the gather's HBM reads local.
Off-TPU the kernel runs in interpret mode (the repo-wide validation
vehicle); the pure-jnp oracle is ``kernels.ref.ell_spmv_ref``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


def _ell_spmv_kernel(xg_ref, vals_ref, o_ref):
    xg = xg_ref[...]                        # (W, BR) gathered x, slot-major
    vals = vals_ref[...]                    # (W, BR)
    acc = vals[0:1] * xg[0:1]
    for s in range(1, vals.shape[0]):
        acc = acc + vals[s:s + 1] * xg[s:s + 1]
    o_ref[...] = acc                        # (1, BR)


def ell_spmv(
    x: jax.Array,
    cols: jax.Array,
    vals: jax.Array,
    *,
    block_r: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """y[r] = sum_s vals[r, s] * x[cols[r, s]] over (R, W) ELL slots.

    ``R`` must be a multiple of ``block_r``, itself a multiple of 128
    (ops.py pads with zero-value rows, exact by construction).  ``x``
    may be LONGER than R — the distributed path passes the extended
    local vector [own | halo] (``repro.linalg.partition.apply_local``).
    """
    r, w = cols.shape
    assert vals.shape == (r, w), (vals.shape, cols.shape)
    assert r % block_r == 0, (r, block_r)
    xg = x[cols.T].astype(vals.dtype)       # (W, R): the one gather
    tile = pl.BlockSpec((w, block_r), lambda i: (np.int32(0), i))
    out = pl.pallas_call(
        _ell_spmv_kernel,
        grid=(r // block_r,),
        in_specs=[tile, tile],
        out_specs=pl.BlockSpec((1, block_r), lambda i: (np.int32(0), i)),
        out_shape=jax.ShapeDtypeStruct((1, r), vals.dtype),
        interpret=interpret,
    )(xg, vals.T)
    return out[0]
