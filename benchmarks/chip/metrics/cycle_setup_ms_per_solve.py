"""cycle_setup_ms_per_solve: device time of the p(l)-CG cycle set-up
(scopes ``plcg_init`` and ``plcg_restart``: the first cycle, each
restart, the answer's finish) per traced solve, a mean over the chips used, in
ms.  Nothing to read where the program carries no phase scopes."""


def read(run):
    p = run.phases
    if p is None or not p.scoped or not run.traced:
        return None
    return 1e3 * (p.phase_s.get("plcg_init", 0.0)
                  + p.phase_s.get("plcg_restart", 0.0)) / len(run.traced)
