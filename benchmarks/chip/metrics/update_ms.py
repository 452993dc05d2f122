"""update_ms: the window's solve time over its solution updates, in ms —
the time one p(l)-CG update costs, restarts and refills included."""


def read(run):
    if not run.updates:
        return None
    return 1e3 * sum(s.seconds for s in run.solves) / run.updates
