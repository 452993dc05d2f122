"""host_idle_ms_per_solve: time the device is idle between programs (the
host's dispatch, the read of each solve's counts, the next call) per
traced solve, a mean over the chips used, in ms."""


def read(run):
    p = run.phases
    if p is None or not run.traced:
        return None
    return 1e3 * p.host_idle_s / len(run.traced)
