"""vector_phase_ms_per_update: device time of the p(l)-CG vector phase
(scope ``plcg_vector``: the stencil, the recurrences, the slab's row
stores, the dot block's local partials, the reduction's start) per
solution update of the traced solves, a mean over the chips used, in ms.
Nothing to read where the program carries no phase scopes."""


def read(run):
    p = run.phases
    if p is None or not p.scoped or not run.traced_updates:
        return None
    return 1e3 * p.phase_s.get("plcg_vector", 0.0) / run.traced_updates
