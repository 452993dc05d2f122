"""Plain reference of the 5-point Dirichlet Laplacian on an nx x ny grid
(PETSc KSP tutorial ex2's matrix: 4 on the diagonal, -1 for each of the
four neighbours, zero Dirichlet boundary), row-major unknown order.

Written here from the definition, independent of the program under test.
``apply_np`` is the float64 host operator that decides ``correct``;
``apply_jnp`` is the same formula in jax.numpy, which the traffic
generator uses to form b = A u on the device.  Each takes the
configuration's ``problem`` entry for the grid's sizes.
"""

from __future__ import annotations

import numpy as np


def apply_np(x: np.ndarray, problem: dict) -> np.ndarray:
    """A x in float64 on the host."""
    g = np.asarray(x, np.float64).reshape(problem["nx"], problem["ny"])
    p = np.pad(g, 1)
    return (4.0 * g - p[:-2, 1:-1] - p[2:, 1:-1]
            - p[1:-1, :-2] - p[1:-1, 2:]).reshape(-1)


def apply_jnp(x, problem: dict):
    """A x in the dtype of ``x``, traceable under jit."""
    import jax.numpy as jnp

    g = x.reshape(problem["nx"], problem["ny"])
    p = jnp.pad(g, 1)
    return (4 * g - p[:-2, 1:-1] - p[2:, 1:-1]
            - p[1:-1, :-2] - p[1:-1, 2:]).reshape(-1)


def true_rel_residual(b: np.ndarray, x: np.ndarray, problem: dict) -> float:
    """||b - A x|| / ||b|| in float64."""
    b = np.asarray(b, np.float64)
    r = b - apply_np(x, problem)
    return float(np.linalg.norm(r) / np.linalg.norm(b))
