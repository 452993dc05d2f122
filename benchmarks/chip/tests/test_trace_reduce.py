"""The trace reduction on a synthetic two-chip trace with a known answer."""

import pytest
from jax.profiler import ProfileData

from trace_reduce import gaps, leaves, op_name, summarize, union_length

NS = 1000  # picoseconds per nanosecond


def _line(lid, name, events):
    evs = "\n".join(
        f"events {{ metadata_id: {m} offset_ps: {(s - 1000) * NS} "
        f"duration_ps: {(e - s) * NS} }}" for m, s, e in events)
    return f"lines {{ id: {lid} name: \"{name}\" timestamp_ns: 1000\n{evs}\n}}"


def _plane(pid, name, lines, names):
    meta = "\n".join(f"event_metadata {{ key: {k} value {{ id: {k} "
                     f"name: \"{v}\" }} }}" for k, v in names.items())
    return f"planes {{ id: {pid} name: \"{name}\"\n" + "\n".join(lines) \
        + f"\n{meta}\n}}"


# Events are named by their HLO text, as a TPU trace names them.
OPS = {1: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
       2: "%psum.3 = f32[1,5]{1,0} all-reduce(f32[1,5]{1,0} %d), "
          "channel_id=1, replica_groups={{0,1}}",
       3: "%all-reduce-start.2 = f32[5]{0} all-reduce-start(f32[5]{0} %d)",
       4: "%while.7 = (f32[8]{0}) while((f32[8]{0}) %t), body=%b",
       5: "%cond.2 = (f32[8]{0}) conditional(s32[] %c, (f32[8]{0}) %t)"}
# chip 0: leaf ops busy 2000 + 1000 + 3000 = 6000 ns under a while and a
# conditional that contain them; idle [3000,4000], [5000,6000],
# [9000,11000].  chip 1: [1000,5000] and, on its async line,
# [4500,6000], an all-reduce start; its event at 12000 lies outside the
# window.  Busy counts the leaves of the op line only: 4000 ns.
CHIP0 = [(4, 1000, 9000), (5, 1000, 5000), (1, 1000, 3000), (2, 4000, 5000),
         (1, 6000, 9000)]
CHIP1 = [(1, 1000, 5000), (1, 12000, 13000)]
CHIP1_ASYNC = [(3, 4500, 6000)]
HOST = {1: "bench.window", 2: "bench.solve", 3: "host.sync"}
HOST_EVENTS = [(1, 1000, 11000), (2, 1000, 8500), (2, 8500, 11000)]


def _trace(window=True):
    host = HOST_EVENTS if window else HOST_EVENTS[1:]
    return ProfileData.from_text_proto("\n".join([
        _plane(1, "/device:TPU:0", [_line(1, "XLA Ops", CHIP0)], OPS),
        _plane(2, "/device:TPU:1", [_line(1, "XLA Ops", CHIP1),
                                    _line(2, "Async XLA Ops", CHIP1_ASYNC),
                                    _line(3, "XLA Modules", [(1, 1000, 13000)])],
               OPS),
        _plane(3, "/host:CPU", [_line(1, "main", host),
                                _line(2, "sync", [(3, 9200, 10800)])], HOST),
    ]))


def test_op_name_and_leaves():
    assert op_name(OPS[4]) == "while.7"
    assert op_name("copy.1") == "copy.1"
    ev = [("w", 0, 10), ("c", 0, 6), ("a", 0, 2), ("b", 3, 6), ("d", 7, 10)]
    assert sorted(n for n, _, _ in leaves(ev)) == ["a", "b", "d"]


def test_union_and_gaps():
    iv = [(5, 8), (0, 2), (1, 3), (7, 9)]
    assert union_length(iv) == 3 + 4
    assert gaps(iv, 0, 12) == [(3, 5), (9, 12)]
    assert gaps(iv, 1, 2) == []


def test_summary_known_answer():
    s = summarize(_trace(), chips=2)
    assert s.chips == 2
    assert s.window_s == pytest.approx(10000e-9)
    assert s.busy_s == pytest.approx((6000 + 4000) / 2 * 1e-9)
    assert s.allreduce_s == pytest.approx((1000 + 1500) / 2 * 1e-9)
    assert s.op_s == pytest.approx({"fusion.1": 4500e-9, "psum.3": 500e-9})
    assert s.idle_gaps == [("host.sync", pytest.approx(2000e-9)),
                           ("bench.solve", pytest.approx(1000e-9)),
                           ("bench.solve", pytest.approx(1000e-9))]
    b = s.breakdown()
    assert [k for k, _ in b["device_ops"]] == ["fusion.1", "psum.3"]
    assert b["idle_gaps"][0][0] == "host.sync"


def test_one_chip_of_two():
    s = summarize(_trace(), chips=1)
    assert s.busy_s == pytest.approx(6000e-9)
    assert s.allreduce_s == pytest.approx(1000e-9)


def test_window_falls_back_to_device_span():
    s = summarize(_trace(window=False), chips=2)
    assert s.window_s == pytest.approx(12000e-9)   # 1000 .. 13000


def test_no_device_plane_raises():
    pd = ProfileData.from_text_proto(
        _plane(3, "/host:CPU", [_line(1, "main", HOST_EVENTS)], HOST))
    with pytest.raises(ValueError, match="no device op line"):
        summarize(pd, chips=1)
