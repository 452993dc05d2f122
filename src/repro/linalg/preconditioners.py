"""Preconditioners M^{-1} for the CG family.

The paper pairs p(l)-CG with *limited-communication* preconditioners
(block Jacobi / no-overlap DDM — §1: "The argument for a longer pipeline
use case is stronger for preconditioners that use limited communication").
We provide:

  IdentityPrec  — unpreconditioned.
  JacobiPrec    — pointwise diagonal scaling.
  BlockJacobi   — contiguous row blocks, each solved with a precomputed
                  dense inverse of the block's diagonal sub-matrix.  For
                  grid-ordered stencil operators the blocks are (block-)
                  tridiagonal; one block per "processor" is the paper's
                  setup.  Application is a batched (nb, b, b) @ (nb, b)
                  matmul — MXU-friendly and communication-free, the TPU
                  equivalent of the per-rank ILU block solves on Cori.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.linalg.operators import LinearOperator


class Preconditioner:
    def apply(self, x: jax.Array) -> jax.Array:  # M^{-1} x
        raise NotImplementedError

    def __call__(self, x: jax.Array) -> jax.Array:
        return self.apply(x)


@dataclasses.dataclass(frozen=True)
class IdentityPrec(Preconditioner):
    def apply(self, x: jax.Array) -> jax.Array:
        return x


@dataclasses.dataclass(frozen=True)
class JacobiPrec(Preconditioner):
    inv_diag: jax.Array

    @staticmethod
    def from_operator(op: LinearOperator) -> "JacobiPrec":
        return JacobiPrec(inv_diag=1.0 / op.diag())

    def apply(self, x: jax.Array) -> jax.Array:
        return self.inv_diag.astype(x.dtype) * x


def inverse_diagonal(prec) -> jax.Array | float | None:
    """M^{-1} as its diagonal where it is diagonal: 1.0 for no
    preconditioner or the identity, ``inv_diag`` for Jacobi; None for a
    block solve.  A diagonal M^{-1} acts on any vector storage by an
    elementwise product (the superkernel's tile, the p(l)-CG slab's
    (R, 128) rows)."""
    if prec is None or isinstance(prec, IdentityPrec):
        return 1.0
    if isinstance(prec, JacobiPrec):
        return prec.inv_diag
    return None


@dataclasses.dataclass(frozen=True)
class BlockJacobi(Preconditioner):
    """Block-Jacobi with precomputed dense block inverses.

    inv_blocks: (nb, b, b) — inverse of each diagonal block of A.
    """

    inv_blocks: jax.Array

    @staticmethod
    def from_operator(op: LinearOperator, block_size: int,
                      coupling_reach: int | None = None) -> "BlockJacobi":
        """Extract diagonal blocks by probing A with COLORED block-local
        basis vectors.

        Probing every block simultaneously would alias cross-block
        couplings that land at the same intra-block offset (e.g. the
        Laplacian's -1 at column r±ny) into the extracted blocks; colored
        probing activates only every ``n_colors``-th block so that all
        blocks within the operator's coupling reach of an active block are
        silent.  Cost: ``n_colors * block_size`` operator applications
        (independent of n).

        coupling_reach: max |i-j| with A[i,j] != 0.  Defaults to
        ``block_size`` (nearest-neighbour blocks — correct for the
        grid-ordered stencils here when the block spans >= one grid
        line), except for unstructured :class:`~repro.linalg.sparse.
        SparseOp` operators, whose true (post-RCM) bandwidth is measured
        instead — probing an irregular matrix with the stencil default
        would silently alias cross-block couplings into the extracted
        blocks (DESIGN.md §12).
        """
        n = op.n
        assert n % block_size == 0, (n, block_size)
        nb = n // block_size
        if coupling_reach is None:
            from repro.linalg.sparse import SparseOp, bandwidth

            reach = bandwidth(op) if isinstance(op, SparseOp) \
                else block_size
        else:
            reach = coupling_reach
        n_colors = min((reach + block_size - 1) // block_size + 2, nb)
        cols = []
        for j in range(block_size):
            col = jnp.zeros((nb, block_size))
            for c in range(n_colors):
                e = jnp.zeros((nb, block_size))
                e = e.at[c::n_colors, j].set(1.0)
                ae = op.apply(e.reshape(-1)).reshape(nb, block_size)
                col = col.at[c::n_colors].set(ae[c::n_colors])
            cols.append(col)
        blocks = jnp.stack(cols, axis=-1)  # (nb, b, b): rows×cols within block
        inv = jnp.linalg.inv(blocks.astype(jnp.float64))
        return BlockJacobi(inv_blocks=inv)

    def apply(self, x: jax.Array) -> jax.Array:
        nb, b, _ = self.inv_blocks.shape
        y = jnp.einsum(
            "nij,nj->ni", self.inv_blocks.astype(x.dtype), x.reshape(nb, b)
        )
        return y.reshape(-1)


def spd_check_blockjacobi(op: LinearOperator, block_size: int) -> bool:
    """Sanity helper (tests): block-Jacobi of an SPD matrix is SPD."""
    bj = BlockJacobi.from_operator(op, block_size)
    w = np.linalg.eigvalsh(np.asarray(bj.inv_blocks, dtype=np.float64))
    return bool((w > 0).all())
