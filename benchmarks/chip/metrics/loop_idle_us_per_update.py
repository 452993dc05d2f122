"""loop_idle_us_per_update: time the device is idle inside the solver
program (window time no leaf op covers, inside an ``XLA Modules`` event)
per solution update of the traced solves, a mean over the chips used, in
us."""


def read(run):
    p = run.phases
    if p is None or not run.traced_updates:
        return None
    return 1e6 * p.loop_idle_s / run.traced_updates
