"""halo_us_per_update: time the stencil's halo exchange between chips is in
flight (the union of the ops under ``halo_xchg``: issue, wait and wire)
per solution update of the traced solves, a mean over the chips used, in
us. Nothing to read on one chip, where no op exchanges a halo."""


def read(run):
    p = run.phases
    if p is None or p.halo_s <= 0 or not run.traced_updates:
        return None
    return 1e6 * p.halo_s / run.traced_updates
