"""device_idle_share: 1 - busy / window from the profiler trace of the
window, averaged over the chips used, in %."""


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
