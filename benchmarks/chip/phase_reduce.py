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

import dataclasses
import re

import numpy as np

from trace_reduce import NO_EVENTS, Trace, gaps, op_name, union_length

PHASES = ("plcg_scalar", "plcg_vector", "plcg_control", "plcg_restart",
          "plcg_init")
VECTOR = "plcg_vector"
SUBPHASES = ("spmv", "recur", "fill", "dots", "xp", "prec")
UNSCOPED = "unscoped"
HALO = "halo_xchg"
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


def _spans(events):
    """Merged program spans: (starts, ends, the length before each)."""
    if not len(events):
        z = np.zeros(0, np.int64)
        return z, z, z
    reach = np.maximum.accumulate(events.end)
    first = np.flatnonzero(np.concatenate(
        ([True], events.start[1:] > reach[:-1])))
    starts = events.start[first]
    ends = reach[np.append(first[1:] - 1, len(reach) - 1)]
    return starts, ends, np.concatenate(([0], np.cumsum(ends - starts)))


def _covered_before(x, starts, ends, before):
    """How much of the merged spans lies before each time of ``x``."""
    j = np.searchsorted(starts, x, "right") - 1
    jj = np.maximum(j, 0)
    part = np.clip(x - starts[jj], 0, ends[jj] - starts[jj])
    return np.where(j >= 0, before[jj] + part, 0)


def summarize(trace: Trace, chips: int, hlo_text: str) -> PhaseSummary:
    """Phase times of the window's leaf ops, joined by instruction name to
    ``hlo_text``, the compiled solver program's text."""
    paths = op_paths(hlo_text)
    used = trace.used(chips)
    lo, hi = trace.window(used)
    kinds: dict[str, tuple[str, tuple[str, str | None, bool]]] = {}

    def kind(text):
        if text not in kinds:
            name = op_name(text)
            kinds[text] = name, classify(paths.get(name))
        return kinds[text]

    busy = halo = loop_idle = host_idle = 0
    leaf_t: dict[str, int] = {}        # device time per instruction text
    loop_gaps: dict[str, float] = {}
    for k in used:
        leaf = trace.ops[k].clip(lo, hi)
        asyn = trace.async_ops.get(k, NO_EVENTS).clip(lo, hi)
        busy += union_length(leaf.start, leaf.end)
        for n, t in leaf.time_by_text().items():
            leaf_t[n] = leaf_t.get(n, 0) + t
        exchange = {n for n in leaf.texts + asyn.texts if kind(n)[1][2]}
        if exchange:
            h = (leaf.select(exchange), asyn.select(exchange))
            halo += union_length(np.concatenate([x.start for x in h]),
                                 np.concatenate([x.end for x in h]))
        a, b, nxt = gaps(leaf.start, leaf.end, lo, hi)
        starts, ends, before = _spans(trace.modules.get(k, NO_EVENTS)
                                      .clip(lo, hi))
        if not len(starts):
            host_idle += int((b - a).sum())
            continue
        inside = (_covered_before(b, starts, ends, before)
                  - _covered_before(a, starts, ends, before))
        loop_idle += int(inside.sum())
        host_idle += int((b - a).sum() - inside.sum())
        if k != used[0]:
            continue
        # The part of a gap inside a program that ends where the gap
        # ends is the wait for the op the gap ends at; the rest of its
        # time inside programs ends at a program's end.
        j = np.searchsorted(starts, b, "left") - 1
        jj = np.maximum(j, 0)
        at_op = (j >= 0) & (ends[jj] >= b) & (nxt >= 0)
        wait = np.where(at_op, b - np.maximum(a, starts[jj]), 0)
        by_text = np.bincount(leaf.ids[nxt[at_op]], weights=wait[at_op],
                              minlength=len(leaf.texts))
        for text_id in np.flatnonzero(by_text):
            name, (phase, _, _) = kind(leaf.texts[text_id])
            label = f"{phase} {name}"
            loop_gaps[label] = (loop_gaps.get(label, 0.0)
                              + float(by_text[text_id]))
        end = float(inside.sum() - wait.sum())
        if end > 0:
            loop_gaps["program end"] = loop_gaps.get("program end", 0.0) + end
    phase_s: dict[str, float] = {}
    sub_s: dict[str, float] = {}
    unscoped: dict[str, float] = {}
    for n, t in leaf_t.items():
        name, (phase, sub, _) = kind(n)
        phase_s[phase] = phase_s.get(phase, 0.0) + t
        if sub is not None:
            key = f"{VECTOR}/{sub}"
            sub_s[key] = sub_s.get(key, 0.0) + t
        if phase == UNSCOPED:
            unscoped[name] = unscoped.get(name, 0.0) + t
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
