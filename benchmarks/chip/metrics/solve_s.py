"""solve_s: the window's host wall time over the solves certified in it,
the time a user waits for one certified solution."""


def read(run):
    return run.window_s / run.certified if run.certified else None
