"""Phase scopes of the p(l)-CG solve, read back from the compiled program
(DESIGN.md §16).

The default p(2)-CG solver with the laplace2d configuration's solver
settings (float32, residual replacement every 1000 updates, governor
``verify_only``) is compiled for a small 2D Laplacian, once on the local
backend and once under ``shard_map`` on 4 virtual devices.  In both:

* every instruction the loop's code emits (the ``while`` body and
  condition, and the branches they call) carries a phase scope, and its
  innermost phase scope is its phase: the loop body's interrupt test is
  ``plcg_control``, and the branch it takes nests its own phase inside;
* all five phase scopes appear in the module;
* on 4 shards, every collective-permute is a tagged halo exchange;
* the scopes are metadata only: with ``jax.named_scope`` made a no-op
  the program compiles to the same instructions.

Instructions XLA makes itself (copies, bitcasts, bounds checks of its
scatter expansion) carry no op_name of the loop's code; the profiler
charges them to ``unscoped``, and they are left out here.
"""

import os
import re
import subprocess
import sys

import pytest

from repro.core.types import CONTROL_TAG, HALO_TAG, PHASE_TAGS

ENV = dict(os.environ, PYTHONPATH="src")
ENV.pop("XLA_FLAGS", None)

PROGRAM = """
import contextlib, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
import jax.numpy as jnp
from repro.core.chebyshev import shifts_for_operator
from repro.linalg import Stencil2D5
from repro.parallel import get_backend
from repro.stability import GovernorConfig

name = sys.argv[1]
op = Stencil2D5(32, 24)
be = get_backend(name, **({"n_shards": 4} if name == "shard_map" else {}))


def text():
    solver = be.make_solver(
        op, "plcg", l=2, tol=2e-4, maxit=8000, replace_every=1000,
        max_restarts=18, governor=GovernorConfig(verify_only=True),
        sigmas=shifts_for_operator(op, 2, dtype=jnp.float32))
    b = jax.ShapeDtypeStruct((op.n,), jnp.float32)
    return jax.jit(solver).lower(b).compile().as_text()


print(text())
print("=== UNSCOPED ===")


@contextlib.contextmanager
def no_scope(name):
    yield


jax.named_scope = no_scope
print(text())
"""

INERT = ("parameter", "constant", "tuple", "get-tuple-element")
_HEAD = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*")
_OPNAME = re.compile(r'op_name="([^"]*)"')


@pytest.fixture(scope="module", params=["local", "shard_map"])
def compiled(request):
    out = subprocess.run([sys.executable, "-c", PROGRAM, request.param],
                         capture_output=True, text=True, env=ENV,
                         cwd=os.getcwd(), timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    scoped, unscoped = out.stdout.split("=== UNSCOPED ===\n")
    return request.param, scoped, unscoped


def _opcode(line: str) -> str | None:
    """The opcode of an HLO instruction line (its type may be a tuple
    holding ``=`` in ``/*index=5*/`` comments)."""
    m = _HEAD.match(line)
    if not m:
        return None
    rest = line[m.end():]
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest = rest[i + 1:]
    else:
        rest = rest.split(" ", 1)[1]
    m = re.match(r"\s*([\w\-]+)\(", rest)
    return m.group(1) if m else None


def _computations(hlo: str) -> dict[str, list[str]]:
    comps, cur = {}, None
    for line in hlo.splitlines():
        if line[:1] not in ("", " ") and line.rstrip().endswith("{"):
            cur = re.match(r"^(?:ENTRY )?%?([\w.\-]+)", line).group(1)
            comps[cur] = []
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            comps[cur].append(line)
    return comps


def loop_instructions(hlo: str) -> list[tuple[str, str | None]]:
    """(opcode, op_name) of the instructions of every ``while`` body and
    condition and of the branches and loops they call.  Fused
    computations are not walked: a fusion is one instruction."""
    comps = _computations(hlo)
    calls = re.compile(r"(?:condition|body|true_computation|"
                       r"false_computation)=%([\w.\-]+)")
    todo, seen, out = [], set(), []
    for lines in comps.values():
        for line in lines:
            if _opcode(line) == "while":
                todo += calls.findall(line)
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in comps[name]:
            opcode = _opcode(line)
            if opcode is None:
                continue
            m = _OPNAME.search(line)
            out.append((opcode, m.group(1) if m else None))
            todo += calls.findall(line)
            branches = re.search(r"branch_computations=\{([^}]*)\}", line)
            if branches:
                todo += re.findall(r"%([\w.\-]+)", branches.group(1))
    return out


def phase_scopes(op_name: str) -> list[str]:
    """The phase scopes on an op_name's path, outermost first (XLA joins
    the op_names of merged instructions with ';': the first one counts)."""
    return [c for c in op_name.split(";")[0].split("/") if c in PHASE_TAGS]


def test_loop_instructions_carry_one_innermost_phase(compiled):
    _, hlo, _ = compiled
    code = [(opc, name) for opc, name in loop_instructions(hlo)
            if opc not in INERT and name and "while/" in name]
    assert len(code) > 50
    for opcode, name in code:
        scopes = phase_scopes(name)
        assert scopes, (opcode, name)
        # Only the loop body's interrupt test encloses another phase.
        assert all(s == CONTROL_TAG for s in scopes[:-1]), (opcode, name)
        assert len(scopes) <= 2, (opcode, name)
    phases = {phase_scopes(name)[-1] for _, name in code}
    assert phases == set(PHASE_TAGS) - {"plcg_init"}


def test_all_five_phases_appear(compiled):
    _, hlo, _ = compiled
    found = {s for name in _OPNAME.findall(hlo) for s in phase_scopes(name)}
    assert found == set(PHASE_TAGS)


def test_halo_exchanges_are_tagged(compiled):
    backend, hlo, _ = compiled
    permutes = []
    for line in hlo.splitlines():
        if _opcode(line) in ("collective-permute", "collective-permute-start"):
            m = _OPNAME.search(line)
            permutes.append(m.group(1) if m else "")
    if backend == "local":
        assert permutes == []
        return
    assert permutes and all(HALO_TAG in name for name in permutes), permutes
    in_loop = [name for name in permutes if "while/" in name]
    assert in_loop, permutes
    assert {phase_scopes(name)[-1] for name in in_loop} \
        == {"plcg_vector", "plcg_restart"}, in_loop


def test_phase_scopes_are_metadata_only(compiled):
    _, scoped, unscoped = compiled

    def strip(hlo: str) -> str:
        # Drop the metadata and the source-location tables it points at.
        body = hlo[hlo.index("\n%") if "\n%" in hlo else 0:]
        return re.sub(r", metadata=\{[^}]*\}", "", body)

    assert "plcg_vector" in scoped and "plcg_vector" not in unscoped
    assert strip(scoped) == strip(unscoped)
