"""From a JAX profiler trace (``.xplane.pb``) of the window to the numbers
the per-layer metrics and the result's ``breakdown`` read.

The trace holds one plane per chip (``/device:TPU:<k>``) and the host
plane (``/host:CPU``), on one clock.  A chip's ``XLA Ops`` line carries
one event per executed HLO instruction, named by the instruction's text
(``%fusion.3 = f32[...] fusion(...)``); control flow nests: a ``while``
event spans its body's ops, a ``conditional`` its branch's.  Its ``Async
XLA Ops`` line carries the asynchronous starts (copies, collectives).
The host plane's thread lines carry the benchmark's own spans
(``jax.profiler.TraceAnnotation``: ``bench.window``, ``bench.solve``) and
the Python and runtime calls under them.

* an op is named by its instruction name (``fusion.3``);
* busy: the union of the leaf ops of ``XLA Ops`` (those that contain no
  other op) inside the window, per chip: a ``while`` that waits on its
  loop condition is not work;
* window: the ``bench.window`` span (else the span of the device ops);
* all-reduce time: the summed durations of the instructions whose opcode
  is ``all-reduce`` (or its start/done pair), on either line; a psum's
  instruction is named after the psum (``%psum.18 = f32[1,5] all-reduce(``);
* idle gaps: the intervals inside the window in which chip 0 ran no leaf
  op, each labelled with the innermost host span covering its midpoint.

Chip figures are averaged over the chips used.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
WINDOW_SPAN = "bench.window"
ALLREDUCE = re.compile(r" all-reduce(-start|-done)?\(")
TOP = 10


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                   # mean over chips
    allreduce_s: float              # mean over chips
    op_s: dict[str, float]          # op name -> seconds, mean over chips
    idle_gaps: list[tuple[str, float]]   # the longest, first; chip 0
    chips: int

    def breakdown(self) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps]}


def union_length(intervals: list[tuple[int, int]]) -> int:
    """Total length covered by ``intervals`` (start, end), overlaps once."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: list[tuple[int, int]], lo: int, hi: int
         ) -> list[tuple[int, int]]:
    """The sub-intervals of [lo, hi] that no interval covers."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def op_name(text: str) -> str:
    """``fusion.3`` of ``%fusion.3 = f32[8] fusion(...)``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def leaves(events: list[tuple[str, int, int]]) -> list[tuple[str, int, int]]:
    """The events that contain no other event of the list."""
    order = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    parent = [False] * len(order)
    stack: list[int] = []
    for i, (_, s, e) in enumerate(order):
        while stack and order[stack[-1]][2] <= s:
            stack.pop()
        if stack and order[stack[-1]][2] >= e:
            parent[stack[-1]] = True
        stack.append(i)
    return [ev for ev, p in zip(order, parent) if not p]


def _events(line):
    return [(ev.name, int(ev.start_ns), int(ev.end_ns)) for ev in line.events]


def device_ops(pd) -> dict[int, tuple[list, list]]:
    """chip index -> (its leaf ops, its async ops), each a list of
    (instruction text, start_ns, end_ns)."""
    out = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        lines = {line.name: _events(line) for line in plane.lines
                 if line.name in (OPS_LINE, ASYNC_LINE)}
        if OPS_LINE in lines:
            out[int(m.group(1))] = (leaves(lines[OPS_LINE]),
                                    lines.get(ASYNC_LINE, []))
    return out


def host_spans(pd) -> list[tuple[str, int, int]]:
    for plane in pd.planes:
        if plane.name == HOST_PLANE:
            return [ev for line in plane.lines for ev in _events(line)]
    return []


def summarize(pd, chips: int) -> TraceSummary:
    ops = device_ops(pd)
    used = sorted(ops)[:chips]
    if not used:
        seen = {pl.name: [ln.name for ln in pl.lines] for pl in pd.planes}
        raise ValueError(f"the trace holds no device op line: {seen}")
    host = host_spans(pd)
    windows = [(s, e) for name, s, e in host if name == WINDOW_SPAN]
    if windows:
        lo, hi = windows[0]
    else:
        lo = min(s for k in used for _, s, _ in ops[k][0])
        hi = max(e for k in used for _, _, e in ops[k][0])

    def clip(events):
        return [(n, max(s, lo), min(e, hi)) for n, s, e in events
                if e > lo and s < hi]

    busy = allreduce = 0.0
    op_s: dict[str, float] = {}
    for k in used:
        leaf, asyn = clip(ops[k][0]), clip(ops[k][1])
        busy += union_length([(s, e) for _, s, e in leaf])
        for n, s, e in leaf:
            name = op_name(n)
            op_s[name] = op_s.get(name, 0.0) + (e - s)
        allreduce += sum(e - s for n, s, e in leaf + asyn
                         if ALLREDUCE.search(n))
    scale = 1e-9 / len(used)
    idle = sorted(gaps([(s, e) for _, s, e in ops[used[0]][0]], lo, hi),
                  key=lambda g: g[0] - g[1])[:TOP]
    labelled = [(_label(host, (s + e) // 2), (e - s) * 1e-9)
                for s, e in idle]
    return TraceSummary(
        window_s=(hi - lo) * 1e-9, busy_s=busy * scale,
        allreduce_s=allreduce * scale,
        op_s={n: v * scale for n, v in op_s.items()},
        idle_gaps=labelled, chips=len(used))


def _label(host: list[tuple[str, int, int]], t: int) -> str:
    """The innermost host span covering time ``t``."""
    covering = [(e - s, name) for name, s, e in host if s <= t < e]
    return min(covering)[1] if covering else "no host span"


def find_xplane(logdir: str) -> str:
    files = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {logdir}, "
                         f"found {files}")
    return files[0]


class Tracer:
    """The profiler around the window, and the reduction of its trace."""

    def __init__(self, logdir: str):
        self.logdir = logdir

    def start(self) -> None:
        import jax
        jax.profiler.start_trace(self.logdir)

    def stop(self, chips: int) -> TraceSummary:
        import jax
        from jax.profiler import ProfileData

        jax.profiler.stop_trace()
        pd = ProfileData.from_file(find_xplane(self.logdir))
        return summarize(pd, chips)
