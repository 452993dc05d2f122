"""updates_per_solve: mean p(l)-CG solution updates (SolveResult.iters)
over the window's solves — the solver layer's work per solve."""


def read(run):
    return run.updates / len(run.solves) if run.solves else None
