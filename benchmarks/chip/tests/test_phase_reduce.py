"""The phase reduction on a synthetic two-chip trace with a known answer.

The instruction names and op_name paths are those of the v5e programs of
the two cells (laplace2d on one chip, laplace2d-4chip on four): chip 0
runs one-chip ops, chip 1 the four-chip program's halo exchange and
psum.  Both chips have an ``XLA Modules`` line.
"""

import pytest
from jax.profiler import ProfileData

from phase_reduce import classify, op_paths, readings, summarize
from trace_reduce import summarize as trace_summarize

NS = 1000  # picoseconds per nanosecond
LOOP = "jit(<lambda>)/jit(<lambda>)/while/body/plcg_control/cond/"
ITER = LOOP + "branch_0_fun/"
MESH = "jit(fn)/shard_map/while/body/plcg_control/cond/branch_0_fun/"

# name -> (HLO text of the instruction, its op_name or None)
INSTR = {
    "fusion.7": ("f32[4000000]{0} fusion(f32[4000000]{0} %p), kind=kLoop",
                 "jit(<lambda>)/jit(<lambda>)/plcg_init/sub"),
    "fusion.13": ("f32[] fusion(f32[2,5]{1,0} %d), kind=kLoop",
                  ITER + "plcg_scalar/cond/branch_1_fun/glred_wait/slice"),
    "constant_dynamic-slice_fusion.1": (
        "f32[5,4000000]{1,0} fusion(f32[14,4000000]{1,0} %s), kind=kLoop",
        ITER + "plcg_vector/dots/dynamic_slice"),
    "broadcast_select_fusion.4": (
        "f32[4000000]{0} fusion(f32[4000000]{0} %a), kind=kLoop",
        ITER + "plcg_vector/recur/jit(_where)/select_n"),
    "dynamic_update_slice.7": (
        "f32[14,4000000]{1,0} dynamic-update-slice(f32[14,4000000]{1,0} %s)",
        ITER + "plcg_vector/fill/dynamic_update_slice"),
    "fusion.15": ("s32[] fusion(s32[] %u), kind=kLoop",
                  ITER + "plcg_control/slice"),
    "fusion.20": ("f32[4000000]{0} fusion(f32[14,4000000]{1,0} %s), "
                  "kind=kLoop", LOOP + "branch_1_fun/plcg_restart/slice"),
    "copy.202": ("f32[14,4000000]{1,0} copy(f32[14,4000000]{1,0} %s)", None),
    # XLA joins the op_names of instructions it merges with ';'.
    "squeeze.157": ("f32[4000000]{0} reshape(f32[1,4000000]{1,0} %z)",
                    ITER + "plcg_vector/spmv/reshape;while/body/plcg_control"
                    "/cond/branch_0_fun/plcg_vector/spmv/squeeze"),
    "collective-permute-start.1": (
        "(f32[1,2000]{1,0}, f32[1,2000]{1,0}) collective-permute-start("
        "f32[1,2000]{1,0} %g), source_target_pairs={{0,1},{1,2},{2,3}}",
        MESH + "plcg_vector/spmv/halo_xchg/ppermute"),
    "collective-permute-done.1": (
        "f32[1,2000]{1,0} collective-permute-done((f32[1,2000]{1,0}, "
        "f32[1,2000]{1,0}) %cps)", MESH + "plcg_vector/spmv/halo_xchg/ppermute"),
    "psum.18": ("f32[5]{0} all-reduce(f32[5]{0} %d), channel_id=1",
                MESH + "plcg_vector/glred_start/psum"),
    "while.3": ("(f32[14,4000000]{1,0}) while((f32[14,4000000]{1,0}) %t), "
                "condition=%c, body=%b", "jit(<lambda>)/jit(<lambda>)/while"),
}


def _hlo(instr=INSTR):
    lines = ["HloModule jit__lambda_, is_scheduled=true", "",
             "ENTRY %main.1 (p: f32[4000000]) -> f32[4000000] {"]
    for name, (text, path) in instr.items():
        meta = f', metadata={{op_name="{path}" stack_frame_id=5}}' \
            if path else ""
        lines.append(f"  %{name} = {text}{meta}")
    return "\n".join(lines + ["}", ""])


HLO = _hlo()
# A program without phase scopes: the same instructions, their op_names
# cut back to the primitive's.
PLAIN = _hlo({n: (t, p and p.split("/")[-1]) for n, (t, p) in INSTR.items()})

# Trace events are named by the instruction's HLO text, as a TPU's are.
EVENTS = {k: f"%{name} = {INSTR[name][0]}" for k, name in enumerate(
    INSTR, start=1)}
EVENTS[20] = "%copy_to_host.3 = f32[8]{0} copy(f32[8]{0} %x)"  # not ours
ID = {v.split(" = ")[0].lstrip("%"): k for k, v in EVENTS.items()}
MODULES = {30: "jit__lambda_(11)"}


def _ev(name, s, e):
    return (ID[name], s, e)


# chip 0: two solves, modules [1000, 9000] and [12000, 20000], window
# [1000, 21000].  Leaf ops: init 1000, scalar 400, vector 6900 (dots 4000,
# recur 2000, fill 500, spmv 400), control 1000, restart 1000, unscoped
# 400 (an XLA copy, and an op of another program) -> busy 10700.  Gaps in
# a module: 300 before dynamic_update_slice.7, 400 before copy.202,
# 500 before fusion.13, 2600 before fusion.15, and 1000 + 500 at the
# ends of the two modules; between modules 3000 + 1000.
CHIP0 = [_ev("while.3", 1500, 8800),
         _ev("fusion.7", 1000, 1500), _ev("fusion.13", 1500, 1700),
         _ev("constant_dynamic-slice_fusion.1", 1700, 3700),
         _ev("broadcast_select_fusion.4", 3700, 4700),
         _ev("dynamic_update_slice.7", 5000, 5500),
         _ev("fusion.15", 5500, 6000), _ev("fusion.20", 6000, 7000),
         _ev("copy.202", 7400, 7600), _ev("squeeze.157", 7600, 8000),
         _ev("fusion.7", 12000, 12500),
         _ev("constant_dynamic-slice_fusion.1", 12500, 14500),
         _ev("fusion.13", 15000, 15200),
         _ev("broadcast_select_fusion.4", 15200, 16200),
         _ev("copy_to_host.3", 16200, 16400), _ev("fusion.15", 19000, 19500)]
CHIP0_MODULES = [(30, 1000, 9000), (30, 12000, 20000)]
# chip 1: one module [1000, 10000].  Leaf ops: init 1000, vector 2500
# (halo wait 300, dots 2000, psum 200), restart 1000 -> busy 4500; its
# last op lies outside the window.  Its async line holds the halo's start
# [1800, 2300]: with the wait, halo time 500.  Idle: 500 + 4000 in the
# module, 11000 after it.
CHIP1 = [_ev("fusion.7", 1000, 2000),
         _ev("collective-permute-done.1", 2000, 2300),
         _ev("constant_dynamic-slice_fusion.1", 2300, 4300),
         _ev("psum.18", 4300, 4500), _ev("fusion.20", 5000, 6000),
         _ev("fusion.15", 22000, 23000)]
CHIP1_ASYNC = [_ev("collective-permute-start.1", 1800, 2300)]
CHIP1_MODULES = [(30, 1000, 10000)]
HOST = {1: "bench.window", 2: "bench.solve"}
HOST_EVENTS = [(1, 1000, 21000), (2, 1000, 10500), (2, 10500, 21000)]


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


def _trace(modules=True):
    def device(pid, ops, mods, asyn=()):
        lines = [_line(1, "XLA Ops", ops), _line(2, "Async XLA Ops", asyn)]
        if modules:
            lines.append(_line(3, "XLA Modules", mods))
        return _plane(pid, f"/device:TPU:{pid - 1}", lines,
                      {**EVENTS, **MODULES})

    return ProfileData.from_text_proto("\n".join([
        device(1, CHIP0, CHIP0_MODULES),
        device(2, CHIP1, CHIP1_MODULES, CHIP1_ASYNC),
        _plane(3, "/host:CPU", [_line(1, "main", HOST_EVENTS)], HOST),
    ]))


def test_classify_takes_the_innermost_phase():
    paths = op_paths(HLO)
    assert classify(paths["fusion.13"]) == ("plcg_scalar", None, False)
    assert classify(paths["constant_dynamic-slice_fusion.1"]) \
        == ("plcg_vector", "dots", False)
    assert classify(paths["squeeze.157"]) == ("plcg_vector", "spmv", False)
    assert classify(paths["collective-permute-start.1"]) \
        == ("plcg_vector", "spmv", True)
    assert classify(paths["psum.18"]) == ("plcg_vector", "other", False)
    assert classify(paths["fusion.20"]) == ("plcg_restart", None, False)
    assert "copy.202" not in paths
    assert classify(paths.get("copy.202")) == ("unscoped", None, False)
    assert classify(paths["while.3"]) == ("unscoped", None, False)


def test_phase_summary_known_answer():
    s = summarize(_trace(), 2, HLO)
    ns = 1e-9
    assert s.chips == 2
    assert s.window_s == pytest.approx(20000 * ns)
    assert s.busy_s == pytest.approx((10700 + 4500) / 2 * ns)
    assert s.phase_s == pytest.approx({
        "plcg_init": 1000 * ns, "plcg_scalar": 200 * ns,
        "plcg_vector": 4700 * ns, "plcg_control": 500 * ns,
        "plcg_restart": 1000 * ns, "unscoped": 200 * ns})
    assert s.sub_s == pytest.approx({
        "plcg_vector/dots": 3000 * ns, "plcg_vector/recur": 1000 * ns,
        "plcg_vector/spmv": 350 * ns, "plcg_vector/fill": 250 * ns,
        "plcg_vector/other": 100 * ns})
    assert s.halo_s == pytest.approx(250 * ns)
    assert s.loop_idle_s == pytest.approx((5300 + 4500) / 2 * ns)
    assert s.host_idle_s == pytest.approx((4000 + 11000) / 2 * ns)
    assert s.loop_gaps == [
        ("plcg_control fusion.15", pytest.approx(2600 * ns)),
        ("program end", pytest.approx(1500 * ns)),
        ("plcg_scalar fusion.13", pytest.approx(500 * ns)),
        ("unscoped copy.202", pytest.approx(400 * ns)),
        ("plcg_vector dynamic_update_slice.7", pytest.approx(300 * ns))]
    assert dict(s.unscoped_ops) == pytest.approx(
        {"copy.202": 100 * ns, "copy_to_host.3": 100 * ns})
    b = s.breakdown()
    assert list(b["phases"])[0] == "plcg_vector"
    assert b["phases"]["plcg_vector/dots"] == pytest.approx(3000 * ns)
    assert b["loop_gaps"][0] == ["plcg_control fusion.15",
                                 pytest.approx(2600 * ns)]


def test_phases_partition_the_trace_reduction():
    """The phases add up to trace_reduce's busy time, and the two idle
    parts to its idle time: the existing readings stand as they were."""
    t = trace_summarize(_trace(), 2)
    for hlo in (HLO, PLAIN):
        s = summarize(_trace(), 2, hlo)
        assert s.busy_s == pytest.approx(t.busy_s)
        assert sum(s.phase_s.values()) == pytest.approx(t.busy_s)
        assert sum(s.sub_s.values()) == pytest.approx(
            s.phase_s.get("plcg_vector", 0.0))
        assert s.loop_idle_s + s.host_idle_s == pytest.approx(
            t.window_s - t.busy_s)
    assert t.allreduce_s == pytest.approx(100e-9)
    assert t.idle_gaps[0] == ("bench.solve", pytest.approx(4000e-9))


def test_readings_per_update_and_solve():
    s = summarize(_trace(), 2, HLO)
    r = readings(s, updates=10, solves=2)
    assert r == pytest.approx({
        "vector_phase_ms_per_update": 4700e-9 * 1e3 / 10,
        "scalar_phase_us_per_update": 200e-9 * 1e6 / 10,
        "cycle_setup_ms_per_solve": 2000e-9 * 1e3 / 2,
        "halo_us_per_update": 250e-9 * 1e6 / 10,
        "loop_idle_us_per_update": 4900e-9 * 1e6 / 10,
        "host_idle_ms_per_solve": 7500e-9 * 1e3 / 2})
    # Idle per update and per solve give back the idle share.
    idle = (r["loop_idle_us_per_update"] * 1e-6 * 10
            + r["host_idle_ms_per_solve"] * 1e-3 * 2)
    assert idle / s.window_s == pytest.approx(1 - s.busy_s / s.window_s)


def test_program_without_scopes_reads_idle_only():
    s = summarize(_trace(), 2, PLAIN)
    assert not s.scoped
    assert s.phase_s == pytest.approx({"unscoped": 7600e-9})
    assert sorted(readings(s, updates=10, solves=2)) == [
        "host_idle_ms_per_solve", "loop_idle_us_per_update"]


def test_no_module_line_counts_all_idle_as_host():
    s = summarize(_trace(modules=False), 2, HLO)
    assert s.loop_idle_s == 0.0
    assert s.host_idle_s == pytest.approx(20000e-9 - 7600e-9)
    assert s.loop_gaps == []


def test_one_chip_of_two():
    s = summarize(_trace(), 1, HLO)
    assert s.busy_s == pytest.approx(10700e-9)
    assert s.halo_s == 0.0
    assert readings(s, updates=10, solves=2).get("halo_us_per_update") \
        is None
