"""setup_s: host seconds from process start to the window's start —
imports, chip init, operator, right-hand-side pool and compile."""


def read(run):
    return run.setup_s
