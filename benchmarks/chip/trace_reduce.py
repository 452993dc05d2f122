"""From a JAX profiler trace (``.xplane.pb``) of the window to the numbers
the per-layer metrics and the result's ``breakdown`` read.

The trace holds one plane per chip (``/device:TPU:<k>``) and the host
plane (``/host:CPU``), on one clock.  A chip's ``XLA Ops`` line carries
one event per executed HLO instruction, named by the instruction's text
(``%fusion.3 = f32[...] fusion(...)``); control flow nests: a ``while``
event spans its body's ops, a ``conditional`` its branch's.  Its ``Async
XLA Ops`` line carries the asynchronous starts (copies, collectives).
The host plane's thread lines carry the benchmark's own spans
(``jax.profiler.TraceAnnotation``: ``bench.traced``, the part of the
window the profiler holds, and ``bench.solve``) and the Python and
runtime calls under them.

* an op is named by its instruction name (``fusion.3``);
* busy: the union of the leaf ops of ``XLA Ops`` (those that contain no
  other op) inside the window, per chip: a ``while`` that waits on its
  loop condition is not work;
* window: the ``bench.traced`` span (else the span of the device ops);
* all-reduce time: the summed durations of the instructions whose opcode
  is ``all-reduce`` (or its start/done pair), on either line; a psum's
  instruction is named after the psum (``%psum.18 = f32[1,5] all-reduce(``);
* idle gaps: the intervals inside the window in which chip 0 ran no leaf
  op, each labelled with the innermost host span covering its midpoint.

Chip figures are averaged over the chips used.  The trace is read once
(:func:`read`) into a :class:`Trace`, which this reduction and
``phase_reduce``'s join both take.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import time

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
TRACED_SPAN = "bench.traced"
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


class Events:
    """One line's events in the order of their start: each event's text
    (an index into ``texts``: a line runs a few hundred instructions,
    each thousands of times), start and end in ns."""

    def __init__(self, texts: list[str], ids, start, end):
        self.texts = texts
        self.ids = np.asarray(ids, np.int64)
        self.start = np.asarray(start, np.int64)
        self.end = np.asarray(end, np.int64)

    @classmethod
    def of(cls, names: list[str], start, end) -> "Events":
        """From each event's name, start and end, in any order."""
        index: dict[str, int] = {}
        ids = np.array([index.setdefault(n, len(index)) for n in names],
                       np.int64)
        start = np.asarray(start, np.int64)
        end = np.asarray(end, np.int64)
        order = np.lexsort((end, start))
        return cls(list(index), ids[order], start[order], end[order])

    def __len__(self) -> int:
        return len(self.ids)

    def clip(self, lo: int, hi: int) -> "Events":
        """The events cut to [lo, hi], those outside it left out."""
        keep = (self.end > lo) & (self.start < hi)
        return Events(self.texts, self.ids[keep],
                      np.maximum(self.start[keep], lo),
                      np.minimum(self.end[keep], hi))

    def time_by_text(self) -> dict[str, int]:
        """Summed duration of each text's events."""
        t = np.bincount(self.ids, weights=self.end - self.start,
                        minlength=len(self.texts))
        return {n: int(v) for n, v in zip(self.texts, t) if v}

    def select(self, texts) -> "Events":
        """The events whose text is in ``texts``."""
        want = [i for i, n in enumerate(self.texts) if n in texts]
        keep = np.isin(self.ids, want)
        return Events(self.texts, self.ids[keep], self.start[keep],
                      self.end[keep])

NO_EVENTS = Events([], [], [], [])


def union_length(start, end) -> int:
    """Total length covered by the intervals [start, end); overlaps count
    once."""
    start = np.asarray(start, np.int64)
    end = np.asarray(end, np.int64)
    if not len(start):
        return 0
    order = np.argsort(start, kind="stable")
    start, end = start[order], end[order]
    reach = np.maximum.accumulate(end)
    before = np.concatenate(([start[0]], reach[:-1]))
    return int(np.maximum(end - np.maximum(start, before), 0).sum())


def gaps(start, end, lo: int, hi: int):
    """The sub-intervals of [lo, hi] that no interval [start, end) covers,
    the intervals ordered by start: (gap starts, gap ends, the index of
    the interval each gap ends at, or -1 for a gap that ends at hi)."""
    start = np.asarray(start, np.int64)
    end = np.asarray(end, np.int64)
    reach = np.maximum(np.maximum.accumulate(end), lo) if len(end) \
        else np.zeros(0, np.int64)
    before = np.concatenate(([lo], reach[:-1]))
    g = np.flatnonzero(start > before)
    a, b, nxt = before[g], np.minimum(start[g], hi), g
    last = reach[-1] if len(reach) else lo
    if last < hi:
        a, b = np.append(a, last), np.append(b, hi)
        nxt = np.append(nxt, -1)
    keep = b > a
    return a[keep], b[keep], nxt[keep]


def op_name(text: str) -> str:
    """``fusion.3`` of ``%fusion.3 = f32[8] fusion(...)``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def leaves(start, end) -> np.ndarray:
    """Which of the events [start, end) contain no other event."""
    start, end = np.asarray(start), np.asarray(end)
    order = np.lexsort((-end, start))
    ss, ee = start[order].tolist(), end[order].tolist()
    parent = [False] * len(ss)
    stack: list[int] = []
    for i, (s, e) in enumerate(zip(ss, ee)):
        while stack and ee[stack[-1]] <= s:
            stack.pop()
        if stack and ee[stack[-1]] >= e:
            parent[stack[-1]] = True
        stack.append(i)
    leaf = np.ones(len(ss), bool)
    leaf[order[np.asarray(parent, bool)]] = False
    return leaf


def _events(line, keep_leaves: bool = False) -> Events:
    evs = list(line.events)
    names = [ev.name for ev in evs]
    start = np.array([ev.start_ns for ev in evs], np.float64).astype(np.int64)
    end = np.array([ev.end_ns for ev in evs], np.float64).astype(np.int64)
    if keep_leaves:
        keep = leaves(start, end)
        names = [n for n, k in zip(names, keep.tolist()) if k]
        start, end = start[keep], end[keep]
    return Events.of(names, start, end)


@dataclasses.dataclass
class Trace:
    """The events of one trace that the reductions read, read once: per
    chip its leaf ops (``XLA Ops``), its asynchronous ops (``Async XLA
    Ops``, where the chip has them) and its program spans (``XLA
    Modules``), each named by its instruction text or program name; and
    the spans of all the host plane's lines."""

    ops: dict[int, Events]
    async_ops: dict[int, Events]
    modules: dict[int, Events]
    host: Events

    def used(self, chips: int) -> list[int]:
        return sorted(self.ops)[:chips]

    def window(self, used: list[int]) -> tuple[int, int]:
        """The ``bench.traced`` span, else the span of ``used``'s ops."""
        if TRACED_SPAN in self.host.texts:
            i = np.flatnonzero(
                self.host.ids == self.host.texts.index(TRACED_SPAN))[0]
            return int(self.host.start[i]), int(self.host.end[i])
        return (min(int(self.ops[k].start.min()) for k in used),
                max(int(self.ops[k].end.max()) for k in used))


def read(pd) -> Trace:
    """The :class:`Trace` of a ``jax.profiler.ProfileData``."""
    ops, asyn, modules, host = {}, {}, {}, NO_EVENTS
    for plane in pd.planes:
        if plane.name == HOST_PLANE:
            lines = [_events(line) for line in plane.lines]
            host = Events.of(
                [e.texts[i] for e in lines for i in e.ids.tolist()],
                np.concatenate([e.start for e in lines] or [[]]),
                np.concatenate([e.end for e in lines] or [[]]))
            continue
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        k = int(m.group(1))
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops[k] = _events(line, keep_leaves=True)
            elif line.name == ASYNC_LINE:
                asyn[k] = _events(line)
            elif line.name == MODULES_LINE:
                modules[k] = _events(line)
    if not ops:
        seen = {pl.name: [ln.name for ln in pl.lines] for pl in pd.planes}
        raise ValueError(f"the trace holds no device op line: {seen}")
    return Trace(ops=ops, async_ops=asyn, modules=modules, host=host)



def summarize(trace: Trace, chips: int) -> TraceSummary:
    used = trace.used(chips)
    lo, hi = trace.window(used)
    busy = 0
    leaf_t: dict[str, int] = {}
    async_t: dict[str, int] = {}
    for k in used:
        leaf = trace.ops[k].clip(lo, hi)
        busy += union_length(leaf.start, leaf.end)
        for times, line in ((leaf_t, leaf),
                            (async_t, trace.async_ops.get(k, NO_EVENTS)
                             .clip(lo, hi))):
            for n, t in line.time_by_text().items():
                times[n] = times.get(n, 0) + t
    op_s: dict[str, float] = {}
    for n, t in leaf_t.items():
        name = op_name(n)
        op_s[name] = op_s.get(name, 0.0) + t
    allreduce = sum(t for times in (leaf_t, async_t)
                    for n, t in times.items() if ALLREDUCE.search(n))
    scale = 1e-9 / len(used)
    first = trace.ops[used[0]].clip(lo, hi)
    a, b, _ = gaps(first.start, first.end, lo, hi)
    top = np.argsort(a - b, kind="stable")[:TOP]
    labelled = [(_label(trace.host, int(a[i] + b[i]) // 2),
                 int(b[i] - a[i]) * 1e-9) for i in top]
    return TraceSummary(
        window_s=(hi - lo) * 1e-9, busy_s=busy * scale,
        allreduce_s=allreduce * scale,
        op_s={n: v * scale for n, v in op_s.items()},
        idle_gaps=labelled, chips=len(used))


def _label(host: Events, t: int) -> str:
    """The innermost host span covering time ``t``."""
    at = np.flatnonzero((host.start <= t) & (t < host.end))
    if not len(at):
        return "no host span"
    length = host.end[at] - host.start[at]
    return min(host.texts[i] for i in host.ids[at[length == length.min()]])


def find_xplane(logdir: str) -> str:
    files = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {logdir}, "
                         f"found {files}")
    return files[0]


class Tracer:
    """The profiler around the window, and the reduction of its trace.

    ``seconds`` holds what each step after the window took: stopping the
    profiler (which writes the trace), reading it, and the two
    reductions."""

    def __init__(self, logdir: str):
        self.logdir = logdir
        self.seconds: dict[str, float] = {}

    def start(self) -> None:
        import jax
        jax.profiler.start_trace(self.logdir)

    def stop(self, chips: int, hlo_text: str):
        """(:class:`TraceSummary`, ``phase_reduce.PhaseSummary``) of the
        window, the phases joined to ``hlo_text``, the compiled solver
        program's text."""
        import jax
        from jax.profiler import ProfileData

        import phase_reduce

        t = time.perf_counter()
        jax.profiler.stop_trace()
        self.seconds["stop_s"] = time.perf_counter() - t
        t = time.perf_counter()
        trace = read(ProfileData.from_file(find_xplane(self.logdir)))
        self.seconds["read_s"] = time.perf_counter() - t
        t = time.perf_counter()
        summary = summarize(trace, chips)
        self.seconds["reduce_s"] = time.perf_counter() - t
        t = time.perf_counter()
        phases = phase_reduce.summarize(trace, chips, hlo_text)
        self.seconds["join_s"] = time.perf_counter() - t
        return summary, phases
