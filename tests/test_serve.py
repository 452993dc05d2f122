"""Serving-layer tests (DESIGN.md §11): batched-vs-sequential parity over
ALL THREE reduction backends, masked-retirement freezing, slot recycling
without recompilation, the setup cache, and the end-to-end service loop.

Everything here runs in-process on one device: ``shard_map`` uses a
1-device mesh and ``multiprocess`` its single-process degradation (no
coordinator), both of which exercise the full psum/spec staging paths.
The 8-device slab paths live in tests/test_distributed.py (subprocess).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import METHODS
from repro.core.chebyshev import shifts_for_operator
from repro.core.types import SolverOps
from repro.linalg import operators as ops_mod
from repro.parallel import get_backend
from repro.serve import (BadRequestError, ConfigError, ServeError,
                         SetupCache, SolverService, UnknownOperatorError,
                         VirtualClock, operator_fingerprint)

RNG = np.random.default_rng(7)

# All three reduction backends, in-process (DESIGN.md §3).
ALL_BACKENDS = ["local", "shard_map", "multiprocess"]


def _backend(name):
    if name == "local":
        return get_backend(name)
    if name == "shard_map":
        return get_backend(name, n_shards=1)
    return get_backend(name)        # multiprocess, single-process mode


@pytest.fixture(scope="module")
def lap2d():
    op = ops_mod.Stencil2D5(16, 16)
    B = jnp.asarray(RNG.standard_normal((op.n, 4)))
    B = B.at[:, 2].set(0.0)         # a padding column: must retire at 0
    return op, B


@pytest.mark.parametrize("backend", ALL_BACKENDS)
@pytest.mark.parametrize("method", ["cg", "pcg", "plcg"])
def test_batched_sequential_history_parity(lap2d, backend, method):
    """Each column of the batched solve reproduces the sequential
    single-RHS residual history and iteration count on every backend —
    batching amortizes the reduction, it never changes the arithmetic."""
    op, B = lap2d
    kw = dict(tol=1e-9, maxit=800)
    if method == "plcg":
        kw.update(l=2, sigmas=shifts_for_operator(op, 2))
    res_b = _backend(backend).solve_batched(op, B, method=method, **kw)
    sops = SolverOps.local(op)
    for j in range(B.shape[1]):
        res_j = METHODS[method](sops, B[:, j], kw)
        assert int(res_b.iters[j]) == int(res_j.iters)
        np.testing.assert_allclose(
            np.asarray(res_b.res_history[j]), np.asarray(res_j.res_history),
            rtol=1e-8, atol=1e-11)
    # the zero column retired instantly (exact padding semantics)
    assert int(res_b.iters[2]) == 0 and bool(res_b.converged[2])


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_retired_column_bitwise_frozen(lap2d, backend):
    """Masked retirement: once a column's loop stops, further chunks must
    not perturb its iterate by a single bit while slab-mates keep
    iterating."""
    op, B = lap2d
    # Column 0 = an exact eigenmode of the Laplacian: its Krylov space is
    # one-dimensional, so it converges within a couple of iterations and
    # sits retired for the many chunks its random slab-mates still need.
    ii, jj = np.meshgrid(np.arange(1, op.nx + 1), np.arange(1, op.ny + 1),
                         indexing="ij")
    mode = np.sin(np.pi * ii / (op.nx + 1)) * np.sin(np.pi * jj / (op.ny + 1))
    B = B.at[:, 0].set(jnp.asarray(mode.reshape(-1)))
    be = _backend(backend)
    prog = be.make_slab_program(op, s=4, method="plcg", chunk_iters=10,
                                l=2, sigmas=shifts_for_operator(op, 2),
                                tol=1e-9, maxit=800)
    st = prog.init(B)
    seen_frozen = False
    snapshot = {}
    for _ in range(40):
        st = prog.chunk(B, st)
        stat = prog.status(B, st)
        running = np.asarray(stat.running)
        x = np.asarray(prog.extract(B, st).x)
        for j in range(4):
            if not running[j]:
                if j in snapshot:
                    assert x[j].tobytes() == snapshot[j], \
                        f"column {j} mutated after retirement"
                    seen_frozen = True
                else:
                    snapshot[j] = x[j].tobytes()
        if not running.any():
            break
    assert seen_frozen          # at least one frozen column was re-checked
    assert not np.asarray(prog.status(B, st).running).any()


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_slot_recycling_no_recompile(lap2d, backend):
    """Retire a column, inject a fresh RHS into its slot, keep solving:
    the recycled solve must match a direct solve, other columns stay
    bitwise frozen, and no slab kernel retraces."""
    op, B = lap2d
    be = _backend(backend)
    sig = shifts_for_operator(op, 2)
    prog = be.make_slab_program(op, s=4, method="plcg", chunk_iters=50,
                                l=2, sigmas=sig, tol=1e-9, maxit=800)
    st = prog.init(B)
    for _ in range(6):
        st = prog.chunk(B, st)
    assert not np.asarray(prog.status(B, st).running).any()
    res0 = prog.extract(B, st)

    b_new = jnp.asarray(RNG.standard_normal(op.n))
    B2 = B.at[:, 1].set(b_new)
    st = prog.inject(B2, st, jnp.asarray([False, True, False, False]))
    stat = np.asarray(prog.status(B2, st).iters)
    assert stat[1] == 0                       # slot 1 re-initialized
    for _ in range(6):
        st = prog.chunk(B2, st)
    res1 = prog.extract(B2, st)
    x_direct = np.linalg.solve(op.to_dense(), np.asarray(b_new))
    np.testing.assert_allclose(np.asarray(res1.x[1]), x_direct, atol=1e-6)
    for j in (0, 2, 3):                       # untouched slots frozen
        assert np.asarray(res1.x[j]).tobytes() == \
            np.asarray(res0.x[j]).tobytes()

    # Fixed shapes end-to-end: each kernel compiled exactly once (the jit
    # cache is visible on the local backend, where the program pieces ARE
    # the jit wrappers; distributed backends wrap them in closures).
    for fn in (prog.chunk, prog.inject, prog.status, prog.extract):
        cache_size = getattr(fn, "_cache_size", None)
        if cache_size is not None:
            assert cache_size() <= 1


def test_operator_fingerprint_and_setup_cache():
    op_a = ops_mod.Stencil2D5(16, 16)
    op_b = ops_mod.Stencil2D5(16, 16)     # distinct object, same content
    op_c = ops_mod.Stencil2D5(16, 8)
    assert operator_fingerprint(op_a) == operator_fingerprint(op_b)
    assert operator_fingerprint(op_a) != operator_fingerprint(op_c)
    d = jnp.asarray(RNG.standard_normal(8) ** 2 + 1.0)
    assert operator_fingerprint(ops_mod.DiagonalOp(d)) == \
        operator_fingerprint(ops_mod.DiagonalOp(d.copy()))

    cache = SetupCache()
    p1 = cache.block_jacobi(op_a, 16)
    p2 = cache.block_jacobi(op_b, 16)     # hit: same fingerprint
    assert p1 is p2
    cache.block_jacobi(op_c, 8)           # miss: different operator
    assert cache.stats() == {"hits": 1, "misses": 2, "entries": 2}
    s1 = cache.sigmas(op_a, 2)
    assert cache.sigmas(op_b, 2) is s1


@pytest.mark.parametrize("method", ["cg", "plcg"])
def test_service_end_to_end(method):
    """More requests than slots, two operators: the scheduler packs,
    retires, recycles, and every retired solution solves its system."""
    ops = {"lap": ops_mod.Stencil2D5(16, 16),
           "toy": ops_mod.DiagonalOp(
               ops_mod.laplacian_2d_spectrum(12, 12))}
    svc = SolverService(get_backend("local"), s=3, method=method, l=2,
                        chunk_iters=25, maxit=800)
    for key, op in ops.items():
        svc.register_operator(key, op)
    rng = np.random.default_rng(3)
    sent = {}
    for i in range(8):
        key = "lap" if i % 2 == 0 else "toy"
        b = rng.standard_normal(ops[key].n)
        sent[svc.submit(key, b, tol=1e-8)] = (key, b)
    results = svc.drain()
    assert set(results) == set(sent)
    for rid, (key, b) in sent.items():
        r = results[rid]
        assert r.converged, (rid, key)
        rel = np.linalg.norm(
            b - np.asarray(ops[key].apply(jnp.asarray(r.x)))
        ) / np.linalg.norm(b)
        assert rel < 1e-6, (rid, key, rel)
        assert r.latency_s > 0 and r.res_history[0] > 0
    st = svc.stats()
    assert st["retired"] == 8 and st["pending"] == 0
    assert st["slabs"] == 2
    assert st["latency_p99_s"] >= st["latency_p50_s"] > 0


def test_service_governor_certifies_true_residual():
    """With a governor every retired column converged on a replacement
    whose fresh true residual met its tol — never on the recursion."""
    from repro.stability import GovernorConfig

    op = ops_mod.Stencil2D5(16, 16)
    svc = SolverService(get_backend("local"), s=2, method="plcg", l=2,
                        chunk_iters=25, maxit=800,
                        governor=GovernorConfig(verify_only=True))
    svc.register_operator("lap", op)
    rng = np.random.default_rng(4)
    sent = {svc.submit("lap", b, tol=1e-6): b
            for b in rng.standard_normal((3, op.n))}
    results = svc.drain()
    for rid, b in sent.items():
        r = results[rid]
        assert r.converged
        rel = np.linalg.norm(b - np.asarray(op.apply(jnp.asarray(r.x)))
                             ) / np.linalg.norm(b)
        assert rel < 1e-6, (rid, rel)
    with pytest.raises(ConfigError, match="governor"):
        SolverService(get_backend("local"), method="cg",
                      governor=GovernorConfig())


def test_typed_serve_errors():
    """Malformed traffic raises the typed ServeError hierarchy — one
    distinct exception per failure mode, all catchable as ServeError and
    still catchable under the stdlib ancestor they shadow."""
    op = ops_mod.Stencil2D5(8, 8)
    svc = SolverService(get_backend("local"), s=2)
    svc.register_operator("lap", op)

    with pytest.raises(UnknownOperatorError):
        svc.submit("nope", np.ones(op.n))
    with pytest.raises(BadRequestError):
        svc.submit("lap", np.ones(op.n - 1))          # wrong shape
    with pytest.raises(BadRequestError):
        svc.submit("lap", np.ones(op.n, dtype=np.int64))
    bad = np.ones(op.n)
    bad[3] = np.nan
    with pytest.raises(BadRequestError):
        svc.submit("lap", bad)                        # non-finite RHS
    with pytest.raises(BadRequestError):
        svc.submit("lap", np.ones(op.n), tol=-1.0)
    with pytest.raises(BadRequestError):
        svc.submit("lap", np.ones(op.n), tol=float("nan"))
    with pytest.raises(BadRequestError):
        svc.submit("lap", np.ones(op.n), deadline_s=float("inf"))
    assert svc.pending == 0                           # nothing leaked in

    with pytest.raises(ConfigError):
        svc.register_operator("bad", object())        # no .n / .apply
    with pytest.raises(ConfigError):
        SolverService(get_backend("local"),
                      prec="block_jacobi").register_operator("lap", op)
    with pytest.raises(ConfigError):
        SolverService(get_backend("local"),
                      prec="weird").register_operator("lap", op)

    # hierarchy: every serve failure is a ServeError, and each subclass
    # keeps the stdlib lineage callers may already catch
    assert issubclass(UnknownOperatorError, ServeError)
    assert issubclass(UnknownOperatorError, KeyError)
    assert issubclass(BadRequestError, ServeError)
    assert issubclass(BadRequestError, ValueError)
    assert issubclass(ConfigError, ServeError)


def test_column_granular_uploads():
    """Host->device transfer regression (DESIGN.md §15): the full (n, s)
    slab uploads exactly once; afterwards only the columns an inject
    changed cross the host boundary, and idle ticks transfer nothing."""
    op = ops_mod.Stencil2D5(8, 8)
    svc = SolverService(get_backend("local"), s=4, method="plcg", l=2,
                        chunk_iters=60, maxit=400, clock=VirtualClock())
    svc.register_operator("lap", op)
    rng = np.random.default_rng(0)
    for _ in range(4):
        svc.submit("lap", rng.standard_normal(op.n))
    svc.drain()
    st = svc.stats()
    assert st["full_uploads"] == 1
    assert st["uploaded_cols"] == 4                   # the one full upload

    svc.step()                                        # idle ticks: no work,
    svc.step()                                        # no transfer
    st = svc.stats()
    assert (st["full_uploads"], st["uploaded_cols"]) == (1, 4)

    for _ in range(2):                                # refill 2 of 4 slots
        svc.submit("lap", rng.standard_normal(op.n))
    svc.drain()
    st = svc.stats()
    assert st["full_uploads"] == 1, "re-upload of the whole slab"
    assert st["uploaded_cols"] == 6, "only changed columns may transfer"
