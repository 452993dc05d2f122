"""The minimum-bytes count against a hand count, and the peaks table."""

import pytest

from peaks import PEAKS, peaks_for
from work import plcg_update_min_bytes, plcg_vectors_read, plcg_vectors_written

# Hand count of one steady-state iteration i, vectors by (basis, index):
# reads                                               writes
# l=1: z1_i z1_{i-1} | z0_{i-1} z0_{i-2} | p x          z1_{i+1} z0_i | p x
# l=2: z2_i z2_{i-1} | z1_{i-1} z1_{i-2} z0_{i-2} z0_{i-3} | p x
#                                               z2_{i+1} z1_i z0_{i-1} | p x
#      (dots: v_{i-3..i-1} and z2_{i..i+1}, all read or written above)
# l=3: z3_i z3_{i-1} | z2_{i-1} z2_{i-2} z1_{i-2} z1_{i-3} z0_{i-3} z0_{i-4}
#      | dots add v_{i-5} (v_{i-5..i-2}; z3_{i-1..i+1} counted) | p x
#                                  z3_{i+1} z2_i z1_{i-1} z0_{i-2} | p x
HAND = {1: (6, 4), 2: (8, 5), 3: (11, 6)}


@pytest.mark.parametrize("l", sorted(HAND))
def test_vector_counts_match_hand_count(l):
    assert (plcg_vectors_read(l), plcg_vectors_written(l)) == HAND[l]


@pytest.mark.parametrize("l", sorted(HAND))
def test_min_bytes_small_n(l):
    reads, writes = HAND[l]
    assert plcg_update_min_bytes(24, l, 4) == 24 * 4 * (reads + writes)
    assert plcg_update_min_bytes(768, l, 2) == 768 * 2 * (reads + writes)


def test_depth_must_be_positive():
    with pytest.raises(ValueError):
        plcg_update_min_bytes(8, 0, 4)


def test_v5e_peaks():
    p = peaks_for("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes"] == 16e9
    assert all(v.get("source") for v in PEAKS.values())


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("TPU v99 imaginary")


def _hbm_share_run(trace, chips=1):
    from types import SimpleNamespace

    from harness import RunRecord, Solve
    from harness import load_module, metric_path
    solves = [Solve(iters=100, restarts=1, converged=True, seconds=9.0)]
    rec = RunRecord(chips=chips, n=1000, l=2, itemsize=4, tol=2e-4,
                    peaks=peaks_for("TPU v5 lite"), setup_s=1.0,
                    window_s=10.0, solves=solves,
                    trace=None if trace is None
                    else SimpleNamespace(busy_s=trace, window_s=10.0))
    return load_module(metric_path("update_hbm_share")).read(rec)


def test_hbm_share_reads_device_time():
    """Least bytes over the trace's busy time per update, not the host's
    solve time: 13 vectors of 4000 B over 1 ms per update at 819 GB/s."""
    share = _hbm_share_run(trace=0.1)
    assert share == pytest.approx(100 * 13 * 4000 / (1e-3 * 819e9))


@pytest.mark.parametrize("trace,chips", [(None, 1), (0.0, 1), (0.1, 4)])
def test_hbm_share_silent_without_a_reading(trace, chips):
    assert _hbm_share_run(trace, chips) is None
