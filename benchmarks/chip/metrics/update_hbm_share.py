"""update_hbm_share: the least HBM traffic of one p(l)-CG update
(work.plcg_update_min_bytes) over what the chip's HBM peak moves in the
device time per update, in %.  The device time is the trace's busy time
(the union of its leaf ops) over the updates of the traced solves.  One
chip only: a shard's vectors on several chips may stay in on-chip memory,
where the HBM peak is no bound."""

from work import plcg_update_min_bytes


def read(run):
    t = run.trace
    if t is None or t.busy_s <= 0 or not run.traced_updates \
            or run.peaks is None or run.chips != 1:
        return None
    per_update_s = t.busy_s / run.traced_updates
    need = plcg_update_min_bytes(run.n, run.l, run.itemsize)
    return 100.0 * need / (per_update_s * run.peaks["hbm_bytes_per_s"])
