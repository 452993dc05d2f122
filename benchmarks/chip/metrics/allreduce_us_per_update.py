"""allreduce_us_per_update: device time of the all-reduce operations
(start, done and synchronous forms) per solution update, averaged over
the chips used, in microseconds.  Nothing to read without all-reduces."""


def read(run):
    t = run.trace
    if t is None or not run.updates or not t.allreduce_s:
        return None
    return 1e6 * t.allreduce_s / run.updates
