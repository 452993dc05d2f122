"""scalar_phase_us_per_update: device time of the p(l)-CG scalar phase
(scope ``plcg_scalar``: the wait on the dot block's reduction and the
scalar recurrences) per solution update of the traced solves, a mean
over the chips used, in us. Nothing to read where the program carries no
phase scopes."""


def read(run):
    p = run.phases
    if p is None or not p.scoped or not run.traced_updates:
        return None
    return 1e6 * p.phase_s.get("plcg_scalar", 0.0) / run.traced_updates
