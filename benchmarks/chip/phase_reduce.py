"""From a profiler trace of the window and the compiled solver program's
text to device time per p(l)-CG phase, halo-exchange time, and the
device's idle time split into in-program and between-program time.

The program puts one phase scope on the instructions it emits
(``plcg_scalar``, ``plcg_vector``, ``plcg_control``, ``plcg_restart``,
``plcg_init``), and XLA keeps it in each instruction's ``metadata
op_name``.  A trace event names the instruction it ran, so joining event
names to the program text gives each op its phase on the trace's own
clock.  The join is the benchmark's own; it reads the program only
through the text of the compiled program.

* an instruction's phase: the innermost ``plcg_*`` component of its
  op_name (of the first path, where XLA joined several with ';').  A
  fusion carries its root's op_name, so its whole time goes to its root's
  phase, whatever its operands' scopes were.  Ops with no phase scope
  (copies and bounds checks XLA adds itself) and ops of other programs
  are ``unscoped``;
* a vector sub-phase: the component right under the innermost
  ``plcg_vector`` where it is one of ``SUBPHASES``, else ``other``;
* ``phase_s``: leaf-op device time per phase in the window (leaf ops as
  ``trace_reduce`` defines them, so the phases sum to its busy time);
* ``halo_s``: the union of the leaf and asynchronous ops under
  ``halo_xchg`` (the halo exchange's issue, wait and wire time);
* ``loop_idle_s``: window time no leaf op covers inside an event of the
  chip's ``XLA Modules`` line, the device inside a program and idle;
  ``host_idle_s``: the rest of the idle time, between programs.  The two
  add up to the window less the busy time.

Every figure is a mean over the chips used; ``loop_gaps`` is chip 0's.
"""

from __future__ import annotations

import bisect
import dataclasses
import re

from trace_reduce import (DEVICE_PLANE, WINDOW_SPAN, device_ops, gaps,
                          host_spans, op_name, union_length)

PHASES = ("plcg_scalar", "plcg_vector", "plcg_control", "plcg_restart",
          "plcg_init")
VECTOR = "plcg_vector"
SUBPHASES = ("spmv", "recur", "fill", "dots", "xp")
UNSCOPED = "unscoped"
HALO = "halo_xchg"
MODULES_LINE = "XLA Modules"
TOP = 10

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*")
_OPNAME = re.compile(r'op_name="([^"]*)"')


def op_paths(hlo_text: str) -> dict[str, list[str]]:
    """Instruction name -> the components of its op_name's first path,
    for every instruction of the program text that has one."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        om = m and _OPNAME.search(line)
        if om:
            out[m.group(1)] = om.group(1).split(";")[0].split("/")
    return out


def classify(path: list[str] | None) -> tuple[str, str | None, bool]:
    """(phase, vector sub-phase or None, under ``halo_xchg``) of one
    op_name path; ``None`` is an op the program text does not name."""
    if not path:
        return UNSCOPED, None, False
    halo = HALO in path
    at = [i for i, c in enumerate(path) if c in PHASES]
    if not at:
        return UNSCOPED, None, halo
    phase = path[at[-1]]
    sub = None
    if phase == VECTOR:
        nxt = path[at[-1] + 1] if at[-1] + 1 < len(path) else ""
        sub = nxt if nxt in SUBPHASES else "other"
    return phase, sub, halo


@dataclasses.dataclass
class PhaseSummary:
    window_s: float
    busy_s: float                   # union of the leaf ops, mean over chips
    phase_s: dict[str, float]       # phase (and UNSCOPED) -> seconds
    sub_s: dict[str, float]         # "plcg_vector/<sub>" -> seconds
    halo_s: float
    loop_idle_s: float
    host_idle_s: float
    loop_gaps: list[tuple[str, float]]   # the longest sums first; chip 0
    unscoped_ops: list[tuple[str, float]]  # the longest unscoped ops first
    chips: int

    @property
    def scoped(self) -> bool:
        """Whether the program carried phase scopes at all."""
        return any(self.phase_s.get(p, 0.0) > 0 for p in PHASES)

    def breakdown(self) -> dict:
        phases = dict(sorted(self.phase_s.items(), key=lambda kv: -kv[1]))
        phases.update(sorted(self.sub_s.items(), key=lambda kv: -kv[1]))
        return {"phases": phases,
                "loop_gaps": [[k, v] for k, v in self.loop_gaps]}


def module_spans(pd) -> dict[int, list[tuple[int, int]]]:
    """chip index -> the (start_ns, end_ns) of its ``XLA Modules`` events."""
    out = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        for line in plane.lines:
            if line.name == MODULES_LINE:
                out[int(m.group(1))] = [(int(ev.start_ns), int(ev.end_ns))
                                        for ev in line.events]
    return out


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _inside(gap, spans, starts):
    """The parts of ``gap`` that lie inside the merged ``spans``."""
    s, e = gap
    out = []
    for ms, me in spans[max(bisect.bisect_right(starts, s) - 1, 0):]:
        if ms >= e:
            break
        a, b = max(s, ms), min(e, me)
        if b > a:
            out.append((a, b))
    return out


def summarize(pd, chips: int, hlo_text: str) -> PhaseSummary:
    """Phase times of the window's leaf ops, joined by instruction name to
    ``hlo_text``, the compiled solver program's text."""
    paths = op_paths(hlo_text)
    ops = device_ops(pd)
    used = sorted(ops)[:chips]
    if not used:
        raise ValueError("the trace holds no device op line")
    windows = [(s, e) for name, s, e in host_spans(pd) if name == WINDOW_SPAN]
    if windows:
        lo, hi = windows[0]
    else:
        lo = min(s for k in used for _, s, _ in ops[k][0])
        hi = max(e for k in used for _, _, e in ops[k][0])
    modules = module_spans(pd)
    kinds: dict[str, tuple[str, str | None, bool]] = {}

    def kind(text):
        name = op_name(text)
        if name not in kinds:
            kinds[name] = classify(paths.get(name))
        return name, kinds[name]

    def clip(events):
        return sorted(((n, max(s, lo), min(e, hi)) for n, s, e in events
                       if e > lo and s < hi), key=lambda ev: ev[1:])

    busy = halo = loop_idle = host_idle = 0.0
    phase_s: dict[str, float] = {}
    sub_s: dict[str, float] = {}
    unscoped: dict[str, float] = {}
    loop_gaps: dict[str, float] = {}
    for k in used:
        leaf, asyn = clip(ops[k][0]), clip(ops[k][1])
        iv = [(s, e) for _, s, e in leaf]
        busy += union_length(iv)
        for n, s, e in leaf:
            name, (phase, sub, _) = kind(n)
            phase_s[phase] = phase_s.get(phase, 0.0) + (e - s)
            if sub is not None:
                key = f"{VECTOR}/{sub}"
                sub_s[key] = sub_s.get(key, 0.0) + (e - s)
            if phase == UNSCOPED:
                unscoped[name] = unscoped.get(name, 0.0) + (e - s)
        halo += union_length([(s, e) for n, s, e in leaf + asyn
                              if kind(n)[1][2]])
        spans = _merge([(max(s, lo), min(e, hi))
                        for s, e in modules.get(k, []) if e > lo and s < hi])
        starts = [s for s, _ in spans]
        leaf_starts = [s for _, s, _ in leaf]
        for gap in gaps(iv, lo, hi):
            inside = _inside(gap, spans, starts)
            length = sum(b - a for a, b in inside)
            loop_idle += length
            host_idle += (gap[1] - gap[0]) - length
            if k != used[0]:
                continue
            for a, b in inside:
                j = bisect.bisect_left(leaf_starts, b)
                if j < len(leaf) and leaf[j][1] == b:
                    name, (phase, _, _) = kind(leaf[j][0])
                    label = f"{phase} {name}"
                else:
                    label = "program end"
                loop_gaps[label] = loop_gaps.get(label, 0.0) + (b - a)
    scale = 1e-9 / len(used)
    return PhaseSummary(
        window_s=(hi - lo) * 1e-9, busy_s=busy * scale,
        phase_s={p: v * scale for p, v in phase_s.items()},
        sub_s={p: v * scale for p, v in sub_s.items()},
        halo_s=halo * scale, loop_idle_s=loop_idle * scale,
        host_idle_s=host_idle * scale,
        loop_gaps=[(k, v * 1e-9) for k, v in sorted(
            loop_gaps.items(), key=lambda kv: -kv[1])[:TOP]],
        unscoped_ops=[(k, v * scale) for k, v in sorted(
            unscoped.items(), key=lambda kv: -kv[1])[:TOP]],
        chips=len(used))


def readings(ps: PhaseSummary, updates: int, solves: int) -> dict[str, float]:
    """The per-layer readings of one traced window: per update or per
    solve of the window.  A phase reading is left out where the program
    carries no phase scopes, the halo reading where no op is a halo
    exchange."""
    out = {}
    if updates and ps.scoped:
        out["vector_phase_ms_per_update"] = \
            1e3 * ps.phase_s.get("plcg_vector", 0.0) / updates
        out["scalar_phase_us_per_update"] = \
            1e6 * ps.phase_s.get("plcg_scalar", 0.0) / updates
    if solves and ps.scoped:
        out["cycle_setup_ms_per_solve"] = 1e3 * (
            ps.phase_s.get("plcg_init", 0.0)
            + ps.phase_s.get("plcg_restart", 0.0)) / solves
    if updates and ps.halo_s > 0:
        out["halo_us_per_update"] = 1e6 * ps.halo_s / updates
    if updates:
        out["loop_idle_us_per_update"] = 1e6 * ps.loop_idle_s / updates
    if solves:
        out["host_idle_ms_per_solve"] = 1e3 * ps.host_idle_s / solves
    return out
