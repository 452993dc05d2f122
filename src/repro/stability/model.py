"""Attainable-accuracy gap model + governor state layout (DESIGN.md §18).

Deep pipelines trade synchronization for rounding-error amplification:
the recursive residual of p(l)-CG drifts away from the true residual
``b - A x`` as local rounding errors are propagated through the
multi-term basis recurrences (the attainable-accuracy analysis of
Cools et al., arXiv:1804.02962).  The *governor* tracks a cheap upper
bound on that drift — the predicted true-vs-recursive residual **gap**
— using ONLY scalars the solver already holds in its scalar phase (the
arrived 2l+1 dot block and the freshly formed Hessenberg entries), so
detection costs zero extra reductions and zero vector traffic.

Two detection arms, both evaluated on replicated scalar state:

* **gap arm** — the accumulated gap estimate crosses into the residual:
  ``safety * gap >= rnorm/norm0``.  The recursive residual can no
  longer be distinguished from its own rounding noise, so the governor
  schedules a residual replacement (cycle re-init from the current
  iterate, which recomputes ``b - A x`` in clean arithmetic).  The
  estimate is not purely modeled: every restart MEASURES the actual
  true-vs-recursive discrepancy (the restart recomputes the true
  residual M-norm anyway) and converts it into a per-iteration drift
  RATE that floors the next cycle's gap growth, so a solver whose
  reductions are corrupted beyond the first-order eps model
  (``repro.chaos``) is caught on the first restart and governed at an
  adaptive replacement period afterwards.
* **patience arm** — the relative recursive residual has not improved
  by ``improve_ratio`` for ``patience`` solution updates: flat
  stagnation the gap model cannot see (e.g. catastrophic corruption
  that keeps the recursion bouncing around a floor).

A governed solve certifies convergence against the TRUE residual: the
recursive residual reaching tol schedules a *verification* replacement
instead of terminating, and only a replacement whose measured true
residual is below tol sets ``converged`` (the sequential solver's
"lucky breakdown" check).  A governed result therefore never reports a
converged flag its true residual does not back — the silent
false-convergence mode of corrupted deep pipelines is structurally
closed (tests/test_stability.py).

Replacements that keep failing to improve the true residual
(``demote_after`` consecutive fruitless replacements) flip the terminal
``STAGNATED`` flag: the solve stops early with a typed diagnosis
instead of silently burning ``maxit`` (``repro.stability.governor``
then demotes the pipeline depth or raises :class:`StagnationError`).

Everything in this module is pure jnp on small scalars — importable
from the solver core without cycles, and property-testable in
isolation (tests/test_stability_properties.py).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------- slots --
# The governor's state is one flat (N_SLOTS,) float vector carried in the
# solver state (``_State.gov``) — a leaf, not a pytree, so vmap/shard_map
# treat it exactly like the other replicated scalars.
GAP = 0          # accumulated relative true-vs-recursive residual gap
BEST = 1         # best rnorm/norm0 seen so far (patience reference)
BEST_UPD = 2     # solution-update count when BEST last improved
DUE = 3          # pending action code: 0 none, 1 gap arm, 2 patience arm
REPL = 4         # governor-triggered residual replacements so far
FRUITLESS = 5    # consecutive replacements without true-residual progress
STAGNATED = 6    # terminal: demote_after fruitless replacements (0/1)
LAST_REL = 7     # true rnorm/norm0 recorded at the last replacement
RATE = 8         # measured per-iteration gap growth from the last cycle
N_SLOTS = 9

# Telemetry "action" column codes (kernels.fused_iter.tel_layout).
ACTION_NONE = 0.0
ACTION_GAP_REPLACE = 1.0
ACTION_PATIENCE_REPLACE = 2.0
ACTION_STAGNATED = 3.0


@dataclasses.dataclass(frozen=True)
class GovernorConfig:
    """Stability-governor policy knobs (DESIGN.md §18).

    ``safety``        gap-arm trigger margin: act when
                      ``safety * gap >= rnorm/norm0``.
    ``patience``      solution updates without an ``improve_ratio``
                      improvement before the patience arm fires;
                      0 (default) auto-resolves to ``max(32, 8l)`` —
                      several pipeline refills, wide enough that the
                      plateaus of an ordinary converging CG run never
                      trip it (a plateau still improves a few percent
                      per window; genuine stagnation improves nothing).
    ``improve_ratio`` "improved" means rel residual < ratio * best;
                      0.99 accepts any 1% improvement per window.
    ``demote_after``  consecutive fruitless replacements before the
                      solve is declared stagnated (terminal).
    ``eps``           unit roundoff seeding the gap model; None uses
                      the solve dtype's machine epsilon.  The seed only
                      matters until the first restart measures the real
                      discrepancy.
    ``kappa``         gap-model scale factor (operator-conditioning
                      fudge; 1.0 is the plain first-order model).
    ``verify_only``   arm only the verification: the recursive residual
                      reaching tol schedules a true-residual check, and
                      only a check that meets tol converges.  The gap
                      and patience arms never fire and the solve never
                      stagnates, so replacements come from
                      ``replace_every`` alone — the float32 policy,
                      where the gap arm fires every few dozen updates
                      and near-tol checks improve by less than
                      ``improve_ratio``.
    """

    safety: float = 4.0
    patience: int = 0
    improve_ratio: float = 0.99
    demote_after: int = 3
    eps: float | None = None
    kappa: float = 1.0
    verify_only: bool = False

    def resolved_patience(self, l: int) -> int:
        return int(self.patience) if self.patience > 0 else max(32, 8 * l)

    def resolved_eps(self, dtype) -> float:
        return float(jnp.finfo(dtype).eps) if self.eps is None else float(self.eps)


def gov_init(dtype) -> jax.Array:
    """Initial governor vector: gap 0, BEST = 1 (rel residual starts at
    1 by definition), LAST_REL = 1, everything else 0."""
    g = jnp.zeros((N_SLOTS,), dtype)
    return g.at[BEST].set(1.0).at[LAST_REL].set(1.0)


def gap_step(gap, gam_new, d2, dlt_safe, basis, eps, kappa=1.0):
    """One first-order update of the accumulated gap estimate.

    Per late iteration the local rounding error injected into the
    recursive residual is O(eps) times the magnitude of the recurrence
    coefficients applied to the basis — here summarized as

        amp   = (1 + |gam_new| + |d2|) / |dlt_safe|
        gap' = gap + kappa * eps * amp * max(basis, 1)

    with ``basis`` the current basis-vector scale (the solver feeds
    ``sqrt(|G(col,col)|)`` from the already-arrived dot block).  The
    estimate is deliberately one-sided: it only ever GROWS — monotone
    non-decreasing in ``gap`` and monotone in each magnitude input —
    which is the property the governor's trigger logic relies on
    (tests/test_stability_properties.py).
    """
    denom = jnp.abs(dlt_safe)
    denom = jnp.where(denom == 0, jnp.ones_like(denom), denom)
    amp = (1.0 + jnp.abs(gam_new) + jnp.abs(d2)) / denom
    return gap + kappa * eps * amp * jnp.maximum(basis, 1.0)
