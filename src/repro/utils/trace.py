"""Overlap tracer: recover the staggered in-flight reductions from HLO
(DESIGN.md §6 — the measurement behind the paper's Fig. 4 'staggering').

The p(l)-CG claim is *structural*: the fused dot block initiated at
iteration i is first consumed at iteration i+l, so up to l global
reductions are simultaneously in flight.  This module verifies the claim
on the *compiled, scheduled* HLO rather than trusting the Python source:

1.  Every reduction backend tags the issue site (``GLRED_START_TAG``) and
    the solvers tag the consumption site (``GLRED_WAIT_TAG``) with
    ``jax.named_scope``.  The scopes survive XLA optimization as
    instruction ``metadata op_name``.
2.  ``plcg_overlap_report`` stages a *flat window* of ``window`` raw
    p(l)-CG iterations (no ``lax.while_loop``) through a backend, each
    iteration wrapped in a ``plwin{k}`` scope, and compiles it.  This is
    the same code window ``unroll`` exposes to XLA inside the production
    while-loop, laid out where the whole schedule is one entry
    computation.
3.  ``analyze_overlap`` walks the entry computation's instruction
    sequence (the schedule), finds per-window start events (the tagged
    all-reduces / dot blocks) and wait events (the tagged arrival
    scatter), and counts, at every consumption point, how many chains
    are already issued but not yet consumed.

For p(l)-CG with ``window >= l+1`` a healthy pipeline reports
``max_in_flight >= l``; classic CG reports 1 (each reduction is waited
before the next is issued) — the Table 1 contrast, now measured.
"""

from __future__ import annotations

import dataclasses
import re

import jax
import jax.numpy as jnp

from repro.core import pipelined_cg
from repro.core.types import (GLRED_START_TAG, GLRED_WAIT_TAG, HALO_TAG,
                              REDUCE_TAG)
from repro.utils.hlo import count_collectives

# Window scope prefix used by the flat trace harness (and by the unrolled
# while-loop driver, which uses "plu{k}").
WINDOW_SCOPE = "plwin"

# HLO opcodes that implement a started reduction on a distributed
# substrate.  On the local backend the tagged op is the dot itself.
_COLLECTIVE_START_OPS = ("all-reduce", "all-reduce-start")

# HLO opcodes of the point-to-point halo exchange (``lax.ppermute``),
# tagged HALO_TAG by the distributed SPMVs (structured planes in
# ``parallel.distributed``, unstructured send/recv sets in
# ``linalg.partition``).  ``-done`` halves are skipped so async pairs
# count once.
_PERMUTE_OPS = ("collective-permute", "collective-permute-start")

_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(?:\([^=]*?\)|\S+)\s+([\w\-]+)\("
)
_OPNAME_RE = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
# Instructions that compute nothing.  XLA hoists constants to the top of
# the schedule and keeps the op_name of the scope that created them, so a
# constant tagged ``plwin2/glred_wait`` sits before every start — never
# an event.
_INERT_OPS = ("constant", "parameter")
_WINDOW_RE = re.compile(WINDOW_SCOPE + r"(\d+)(?:\D|$)")
# Staged ring-ladder hops (DESIGN.md §14): ``lax.ppermute`` inside a
# ``glred_hop{k}`` scope, k the global hop index 0..P-2.  Hop 0 is the
# first wire movement of a freshly issued handle — counting hop-0
# permutes per window is the staged substitute for the all-reduce-based
# logical-reduction count (exactly one per iteration, whatever the
# ladder's stage grouping or the slab width s).
_HOP_RE = re.compile(REDUCE_TAG + r"(\d+)(?:\D|$)")


@dataclasses.dataclass(frozen=True)
class ChainEvent:
    """One tagged site in the scheduled entry computation."""

    kind: str          # "start" | "wait" | "halo" | "hop"
    window: int        # plwin{k} iteration index
    pos: int           # instruction position in the entry computation
    opcode: str
    name: str          # HLO instruction name
    hop: int | None = None   # ladder hop index (kind == "hop" only)


@dataclasses.dataclass
class OverlapReport:
    """In-flight reduction chains recovered from one HLO schedule."""

    l: int                          # pipeline depth used for chain pairing
    window: int                     # traced iteration-window length
    events: list[ChainEvent]
    chains: list[tuple[int, int, int | None]]  # (window k, start, wait pos)
    max_in_flight: int              # peak #chains issued but not consumed
    n_collectives: int              # all-reduce count in the module
    collective_bytes: float
    # Tagged collective-start instructions per traced window — the
    # "reduction handles issued per iteration" count.  For a healthy
    # (batched or not) p(l)-CG schedule every window shows exactly 1:
    # batching widens the payload, never the handle count (DESIGN.md §11).
    starts_per_window: dict[int, int] = dataclasses.field(
        default_factory=dict)
    # HALO_TAG'd collective-permutes found in the schedule, and how many
    # of them sit strictly INSIDE an open reduction window (after a
    # chain's start, before its wait) — the paper's second staggering
    # claim: neighbour communication overlaps the in-flight Iallreduce
    # (DESIGN.md §12).  Operators without point-to-point halo (diagonal,
    # single shard) report 0/0.
    n_halo_permutes: int = 0
    halos_in_flight: int = 0
    # Staged ring-ladder metrics (DESIGN.md §14).  ``reduce_hops_per_
    # window``: REDUCE_TAG'd ppermutes per traced window — the ladder
    # traffic the solver advances hop-by-hop (a healthy staged p(l)-CG
    # schedule shows >= l hops in every late window).  ``staged_starts_
    # per_window``: hop-0 permutes per window, the staged analogue of
    # ``starts_per_window`` — exactly 1 per iteration means one logical
    # reduction handle enters the wire per iteration however the hops
    # are grouped or the slab widened.  ``hops_in_flight``: ladder hops
    # scheduled strictly inside open reduction windows — together with
    # ``halos_in_flight`` this is the hop/halo staggering invariant (the
    # reduction's own wire traffic interleaves with neighbour exchange
    # inside the in-flight window).  All zero on monolithic schedules.
    reduce_hops_per_window: dict[int, int] = dataclasses.field(
        default_factory=dict)
    staged_starts_per_window: dict[int, int] = dataclasses.field(
        default_factory=dict)
    n_reduce_hops: int = 0
    hops_in_flight: int = 0

    def __str__(self) -> str:
        staged = ""
        if self.n_reduce_hops:
            staged = (f"; staged ladder: {self.n_reduce_hops} hop(s), "
                      f"{self.hops_in_flight} inside reduction windows, "
                      f"min {min(self.reduce_hops_per_window.values())}"
                      f"/window")
        lines = [
            f"overlap trace: window={self.window} depth l={self.l} -> "
            f"max {self.max_in_flight} reduction chain(s) in flight "
            f"({self.n_collectives} all-reduce(s), "
            f"{self.collective_bytes:.3e} B payload; "
            f"{self.halos_in_flight}/{self.n_halo_permutes} halo "
            f"permute(s) inside reduction windows{staged})"
        ]
        for k, s, w in self.chains:
            tail = f"waited @ {w}" if w is not None else "open at window end"
            lines.append(f"  chain {k:>3d}: issued @ instr {s:>5d}, {tail}")
        return "\n".join(lines)


def _entry_instructions(hlo_text: str
                        ) -> list[tuple[str, str, str, list[str]]]:
    """(name, opcode, op_name-metadata, operand names) for the ENTRY
    computation, in schedule (text) order; inert instructions
    (``_INERT_OPS``) are dropped."""
    out = []
    in_entry = False
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY "):
            in_entry = True
            continue
        if in_entry:
            if line.startswith("}"):
                break
            m = _INSTR_RE.match(line)
            if not m or m.group(2) in _INERT_OPS:
                continue
            om = _OPNAME_RE.search(line)
            args = line[m.end():].split(")", 1)[0]
            out.append((m.group(1), m.group(2), om.group(1) if om else "",
                        _OPERAND_RE.findall(args)))
    return out


def _window(op_name: str) -> int | None:
    wm = _WINDOW_RE.search(op_name)
    return None if wm is None else int(wm.group(1))


def _start_windows(instrs) -> list[tuple[int, int, str, str]]:
    """(window, pos, opcode, name) for every tagged reduction start.

    XLA's all-reduce combiner merges independent reductions — the
    pipeline-fill iterations' dot blocks have no data dependence on each
    other — into ONE tuple all-reduce that keeps only the first
    constituent's op_name.  A collective start therefore counts once for
    every window its operands come from (one handle per iteration, as
    many iterations as the combined all-reduce carries)."""
    op_names = {name: op_name for name, _o, op_name, _a in instrs}
    out = []
    for pos, (name, opcode, op_name, args) in enumerate(instrs):
        k = _window(op_name)
        if k is None or GLRED_START_TAG not in op_name:
            continue
        ks = [k]
        if opcode in _COLLECTIVE_START_OPS and len(args) > 1:
            ks = sorted({w for a in args
                         if (w := _window(op_names.get(a, ""))) is not None}
                        ) or ks
        out.extend((w, pos, opcode, name) for w in ks)
    return out


def extract_events(hlo_text: str) -> list[ChainEvent]:
    """Tagged start/wait events from the scheduled entry computation.

    A start event per window = the first instruction carrying both the
    window scope and GLRED_START_TAG, preferring collective opcodes (the
    all-reduce itself) over the local-matmul fallback.  A wait event per
    window = the first instruction carrying the window scope and
    GLRED_WAIT_TAG (the arrival scatter into the G window).
    """
    instrs = _entry_instructions(hlo_text)
    starts: dict[int, ChainEvent] = {}
    waits: dict[int, ChainEvent] = {}
    halos: list[ChainEvent] = []
    hops: list[ChainEvent] = []
    for k, pos, opcode, name in _start_windows(instrs):
        ev = ChainEvent("start", k, pos, opcode, name)
        cur = starts.get(k)
        is_coll = opcode in _COLLECTIVE_START_OPS
        cur_coll = cur is not None and cur.opcode in _COLLECTIVE_START_OPS
        if cur is None or (is_coll and not cur_coll):
            starts[k] = ev
    for pos, (name, opcode, op_name, _args) in enumerate(instrs):
        k = _window(op_name)
        if k is None:
            continue
        # Staged ladder hops are counted on their own axis: a hop that
        # executes inside the wait's scope (the steps the solver had not
        # advanced yet) carries BOTH glred_wait and glred_hop{j} — it is
        # a hop event, never the wait's consumption marker.
        hm = _HOP_RE.search(op_name)
        is_hop = hm is not None and opcode in _PERMUTE_OPS
        if is_hop:
            hops.append(ChainEvent("hop", k, pos, opcode, name,
                                   hop=int(hm.group(1))))
        if GLRED_START_TAG in op_name:
            continue                     # starts: _start_windows above
        if GLRED_WAIT_TAG in op_name and not is_hop and k not in waits:
            waits[k] = ChainEvent("wait", k, pos, opcode, name)
        elif HALO_TAG in op_name and opcode in _PERMUTE_OPS and not is_hop:
            # Every halo permute is an event (a window has one per
            # direction and hop) — the staggering metric counts them all.
            halos.append(ChainEvent("halo", k, pos, opcode, name))
    evs = list(starts.values()) + list(waits.values()) + halos + hops
    evs.sort(key=lambda e: e.pos)
    return evs


def reduction_starts_per_window(hlo_text: str) -> dict[int, int]:
    """Count tagged COLLECTIVE start instructions per ``plwin{k}`` window.

    This is the per-iteration reduction-handle count: each all-reduce (or
    all-reduce-start) carrying both a window scope and GLRED_START_TAG in
    its op_name is one issued handle.  The batched multi-RHS solvers must
    keep this at exactly 1 per iteration whatever the slab width s — the
    amortization claim of DESIGN.md §11, checked against compiled HLO in
    tests/test_distributed.py."""
    counts: dict[int, int] = {}
    for k, _pos, opcode, _name in _start_windows(
            _entry_instructions(hlo_text)):
        if opcode in _COLLECTIVE_START_OPS:
            counts[k] = counts.get(k, 0) + 1
    return counts


def analyze_overlap(hlo_text: str, l: int, window: int | None = None
                    ) -> OverlapReport:
    """Count outstanding chains at every consumption point.

    Chain k is *in flight* from its start event (window k) until its wait
    event (window k+l).  The peak is measured at the wait events ONLY:
    at each consumption point, how many chains are already issued and not
    yet consumed (the chain being waited counts; trailing chains whose
    wait lies beyond the traced window count when issued, but never form
    a peak on their own).  A fully serialized schedule
    (start, wait, start, wait, ...) therefore reports 1 — the metric is
    falsifiable, not guaranteed by construction — while the paper's
    staggering reports l: the D-ring dataflow forces starts k..k+l-1
    before the consumption of chain k.
    """
    events = extract_events(hlo_text)
    starts = {e.window: e for e in events if e.kind == "start"}
    waits = {e.window: e for e in events if e.kind == "wait"}
    halos = [e for e in events if e.kind == "halo"]
    hops = [e for e in events if e.kind == "hop"]
    if window is None:
        window = max(starts, default=-1) + 1

    chains: list[tuple[int, int, int | None]] = []
    for k, s in sorted(starts.items()):
        w = waits.get(k + l)
        chains.append((k, s.pos, w.pos if w else None))

    peak = 0
    for we in sorted(waits.values(), key=lambda e: e.pos):
        n = sum(
            1 for _k, spos, wpos in chains
            if spos <= we.pos and (wpos is None or wpos >= we.pos)
        )
        peak = max(peak, n)

    # Halo staggering: a permute "rides inside" a reduction window when
    # the schedule places it strictly after a chain's issue and before
    # that chain's consumption — the Iallreduce / neighbour-exchange
    # overlap of the paper, now a measured property of the compiled
    # schedule rather than an assumption.
    halos_in_flight = sum(
        1 for e in halos
        if any(spos < e.pos and (wpos is None or e.pos < wpos)
               for _k, spos, wpos in chains)
    )
    # Hop staggering (DESIGN.md §14): a ladder hop inside an open chain
    # window is reduction wire traffic riding the in-flight window —
    # exactly where the hop-per-iteration advance schedule puts it.
    hops_in_flight = sum(
        1 for e in hops
        if any(spos < e.pos and (wpos is None or e.pos < wpos)
               for _k, spos, wpos in chains)
    )
    hops_per_window: dict[int, int] = {}
    staged_starts: dict[int, int] = {}
    for e in hops:
        hops_per_window[e.window] = hops_per_window.get(e.window, 0) + 1
        if e.hop == 0:
            staged_starts[e.window] = staged_starts.get(e.window, 0) + 1

    colls = count_collectives(hlo_text)
    n_coll = int(sum(v["count"] for kind, v in colls.items()
                     if kind.startswith("all-reduce")))
    cbytes = float(sum(v["bytes"] for kind, v in colls.items()
                       if kind.startswith("all-reduce")))
    return OverlapReport(l=l, window=window, events=events, chains=chains,
                         max_in_flight=peak, n_collectives=n_coll,
                         collective_bytes=cbytes,
                         starts_per_window=reduction_starts_per_window(
                             hlo_text),
                         n_halo_permutes=len(halos),
                         halos_in_flight=halos_in_flight,
                         reduce_hops_per_window=hops_per_window,
                         staged_starts_per_window=staged_starts,
                         n_reduce_hops=len(hops),
                         hops_in_flight=hops_in_flight)


def plcg_overlap_report(
    backend,
    op,
    b,
    l: int,
    window: int | None = None,
    sigmas=None,
    prec=None,
    fused_iteration: bool = False,
    telemetry_cap: int = 0,
    recurrence: str = "ghysels",
    governor=None,
) -> OverlapReport:
    """Trace a flat ``window``-iteration p(l)-CG schedule through
    ``backend`` and report the in-flight reduction chains.

    ``window`` defaults to l+2 — the smallest window exposing the full
    staggering (the paper recommends ``unroll >= l+1`` in production; see
    DESIGN.md §2/§6).  ``b`` may be a ``jax.ShapeDtypeStruct``.

    ``fused_iteration=True`` traces the superkernel path (DESIGN.md §13):
    the vector phase collapses into one Pallas call per window, but the
    reduction structure must be UNCHANGED — still one tagged start per
    iteration (``ops.start_partials``) consumed l windows later, still
    ``max_in_flight >= l`` (asserted in tests/test_fused_iter.py).

    ``telemetry_cap > 0`` traces the INSTRUMENTED solve (DESIGN.md §16):
    the telemetry-ring writes ride the schedule, and the report must show
    the identical reduction structure — the zero-extra-collectives
    invariant, asserted in tests/test_telemetry.py.

    ``recurrence``/``governor`` trace the stable-recurrence and governed
    solves (DESIGN.md §18): the governor is replicated-scalar work in
    the scalar phase, so a governed schedule must STILL show exactly one
    reduction start per window — asserted in tests/test_stability.py.
    """
    window = l + 2 if window is None else window
    if window < 1:
        raise ValueError("window must be >= 1")

    def harness(ops, b_local):
        prog = pipelined_cg.build(ops, b_local, l, tol=0.0,
                                  maxit=window + l + 2, sigmas=sigmas,
                                  fused_iteration=fused_iteration,
                                  telemetry_cap=telemetry_cap,
                                  recurrence=recurrence, governor=governor)
        st = prog.init(jnp.zeros_like(b_local))
        for k in range(window):
            with jax.named_scope(f"{WINDOW_SCOPE}{k}"):
                st = prog.iteration(
                    st, static_phase="late" if k >= l else "early")
        # The history hangs off every arrival — returning it keeps all
        # traced chains (except the trailing un-consumed ones) live.
        # The telemetry ring is returned too so its writes stay live in
        # the instrumented trace (an unused ring would be DCE'd and the
        # zero-overhead assertion would be vacuous); same for the
        # governor vector on governed traces.
        return st.hist, st.cyc.D, st.tel, st.gov

    hlo = backend.lower_hlo(harness, op, b, prec=prec)
    return analyze_overlap(hlo, l=l, window=window)


def batched_plcg_overlap_report(
    backend,
    op,
    B,
    l: int,
    window: int | None = None,
    sigmas=None,
    prec=None,
    fused_iteration: bool = False,
    telemetry_cap: int = 0,
    recurrence: str = "ghysels",
    governor=None,
) -> OverlapReport:
    """Overlap report for the BATCHED multi-RHS p(l)-CG slab
    (DESIGN.md §11): a flat ``window``-iteration schedule of the vmapped
    per-column iteration, staged through ``backend`` with the slab
    B (n, s) domain-decomposed on n.

    The claims this measures: (a) the staggering survives batching —
    ``max_in_flight >= l`` exactly as in the single-RHS trace; (b)
    amortization — ``starts_per_window[k] == 1`` for every window: one
    reduction handle per iteration carrying the whole (2l+1, s) payload,
    not s handles.  ``B`` may be a ``jax.ShapeDtypeStruct``.
    ``telemetry_cap > 0`` traces the instrumented slab (DESIGN.md §16) —
    same invariants, asserted in tests/test_telemetry.py.
    """
    window = l + 2 if window is None else window
    if window < 1:
        raise ValueError("window must be >= 1")

    def harness(ops, B_local):
        def col(bcol):
            prog = pipelined_cg.build(ops, bcol, l, tol=0.0,
                                      maxit=window + l + 2, sigmas=sigmas,
                                      fused_iteration=fused_iteration,
                                      telemetry_cap=telemetry_cap,
                                      recurrence=recurrence,
                                      governor=governor)
            st = prog.init(jnp.zeros_like(bcol))
            for k in range(window):
                with jax.named_scope(f"{WINDOW_SCOPE}{k}"):
                    st = prog.iteration(
                        st, static_phase="late" if k >= l else "early")
            return st.hist, st.cyc.D, st.tel, st.gov

        return jax.vmap(col, in_axes=1)(B_local)

    from jax.sharding import PartitionSpec as P
    b_spec = P(backend.axis, None) if hasattr(backend, "axis") else None
    hlo = backend.lower_hlo(harness, op, B, prec=prec, b_spec=b_spec)
    return analyze_overlap(hlo, l=l, window=window)
