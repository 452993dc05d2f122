"""Compile the chip path for a described TPU v5e, with no chip attached.

Every Pallas kernel on the solver path compiles here with
``interpret=False`` at the widths of the repo's configs, under
``jax_enable_x64`` on and off (Mosaic refuses 64-bit block indices, so
the index maps must return int32 either way).  The fused superkernel
compiles where its single slab tile fits VMEM (256^2 for the 2D
stencil); at ``laplace2d`` size it must refuse with its typed error
before compiling.  The one-chip p(l)-CG program at 2048^2
float32 compiles, and so does the 4-device ``shard_map`` program on the
described 2x2 mesh, with the monolithic psum and with the staged ring
ladder — the staged one carrying no dot-block all-reduce.  The chip
benchmark's own programs (``benchmarks/chip/configs``, 2000^2, one chip
and four) keep the p(l)-CG slab in whole (8, 128) tiles: no vector
lives in a one-sublane tiling, and every row store updates the slab in
place.

The topology is described inside a fixture: only one process may load
the TPU library at a time, so nothing here touches it at import.
"""

import importlib.util
import json
import math
import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs import get_config
from repro.configs.problems import build_operator
from repro.core.types import GLRED_START_TAG
from repro.kernels import fused_iter as fi
from repro.kernels import ops
from repro.linalg import Stencil2D5
from repro.parallel import get_backend, make_solver_mesh
from repro.parallel.distributed import distributed_solve

N_LAPLACE = 2048 * 2048            # laplace2d unknowns
ICESHEET_ELL = (500_000, 11)       # icesheet3d: rows, max nnz per row


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_lowering(name, sh):
    f32, i32 = jnp.float32, jnp.int32
    n = N_LAPLACE
    if name == "stencil2d5":
        return jax.jit(lambda g: ops.stencil2d5_apply(g, interpret=False)
                       ).lower(_spec((2048, 2048), sh))
    if name == "stencil3d7":
        cfg = get_config("icesheet3d-stencil")
        return jax.jit(lambda g: ops.stencil3d7_apply(g, cfg.eps_z,
                                                      interpret=False)
                       ).lower(_spec((cfg.nx, cfg.ny, cfg.nz), sh))
    if name == "ell_spmv":
        r, w = ICESHEET_ELL
        return jax.jit(lambda x, c, v: ops.ell_spmv_apply(x, c, v,
                                                          interpret=False)
                       ).lower(_spec((r,), sh), _spec((r, w), sh, i32),
                               _spec((r, w), sh))
    if name == "fused_dots":
        return jax.jit(lambda m, v: ops.fused_dots(m, v, interpret=False)
                       ).lower(_spec((5, n), sh), _spec((n,), sh))
    if name == "fused_dots_mrhs":
        return jax.jit(lambda m, v: ops.fused_dots_mrhs(m, v,
                                                        interpret=False)
                       ).lower(_spec((5, n), sh), _spec((n, 4), sh))
    if name == "fused_axpy3":
        return jax.jit(lambda a, b, c, c1, c2, s: ops.fused_axpy3(
            a, b, c, c1, c2, s, interpret=False)
        ).lower(*[_spec((n,), sh)] * 3, *[_spec((), sh, f32)] * 3)
    raise KeyError(name)


@pytest.mark.parametrize("x64", [False, True], ids=["x64off", "x64on"])
@pytest.mark.parametrize("name", ["stencil2d5", "stencil3d7", "ell_spmv",
                                  "fused_dots", "fused_dots_mrhs",
                                  "fused_axpy3"])
def test_kernel_compiles_for_v5e(one_chip, name, x64):
    with jax.enable_x64(x64):
        compiled = _kernel_lowering(name, one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


def _superkernel_lowering(op, sh, prec=None, recurrence="ghysels", l=2):
    layout = fi.SlabLayout(l=l, RB=max(l + 1, 3), recurrence=recurrence)
    fiter = ops.fused_iteration_factory(op, prec)(layout, interpret=False)
    return jax.jit(fiter).lower(
        _spec((layout.nv, op.n), sh),
        _spec((fi.idx_layout(l)["size"],), sh, jnp.int32),
        _spec((fi.scal_layout(l)["size"],), sh))


@pytest.mark.parametrize("case", ["stencil2d", "stencil2d-jacobi-stable",
                                  "diagonal", "stencil2d-l3"])
def test_superkernel_compiles_when_slab_fits(one_chip, case):
    from repro.linalg.operators import DiagonalOp
    from repro.linalg.preconditioners import JacobiPrec

    op = Stencil2D5(256, 256)           # the largest 2D tile within VMEM
    kw = {}
    if case == "diagonal":
        op = DiagonalOp(jnp.linspace(1.0, 2.0, op.n, dtype=jnp.float32))
    elif case == "stencil2d-jacobi-stable":
        kw = dict(prec=JacobiPrec.from_operator(op), recurrence="stable")
    elif case == "stencil2d-l3":
        op, kw = Stencil2D5(128, 128), dict(l=3)
    with jax.enable_x64(False):
        compiled = _superkernel_lowering(op, one_chip, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_superkernel_refuses_slab_beyond_vmem(one_chip):
    with jax.enable_x64(False), pytest.raises(fi.FusedSlabTooLarge,
                                              match="VMEM"):
        _superkernel_lowering(Stencil2D5(2048, 2048), one_chip)


def _solver_kwargs(op):
    """The solver settings ``chip_smoke.py`` runs on the chip."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", Path(__file__).resolve().parents[1] / "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    return smoke.solver_kwargs(get_config("laplace2d"), op)


def test_one_chip_plcg_program_compiles(one_chip):
    op = build_operator(get_config("laplace2d"))
    with jax.enable_x64(False):
        solver = get_backend("local").make_solver(op, "plcg",
                                                  **_solver_kwargs(op))
        compiled = solver.lower(_spec((op.n,), one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2**30     # one v5e's HBM


def _dot_block_allreduces(hlo: str) -> int:
    return sum(1 for line in hlo.splitlines()
               if (" all-reduce(" in line or " all-reduce-start(" in line)
               and GLRED_START_TAG in line)


@pytest.mark.parametrize("reduction", ["monolithic", "staged"])
def test_four_chip_plcg_program_compiles(topo, reduction):
    op = build_operator(get_config("laplace2d"))
    mesh = make_solver_mesh(4, devices=topo.devices)
    be = get_backend("shard_map", mesh=mesh, reduction=reduction)
    shard = NamedSharding(mesh, P(be.axis))
    with jax.enable_x64(False):
        fn, arrays = distributed_solve(
            mesh, op, jax.ShapeDtypeStruct((op.n,), jnp.float32),
            method="plcg", jit=False, reduction=be.reduction_cfg,
            **_solver_kwargs(op))
        arr_specs = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=shard),
            arrays)
        compiled = jax.jit(fn).lower(_spec((op.n,), shard),
                                     arr_specs).compile()
    hlo = compiled.as_text()
    n_ar = _dot_block_allreduces(hlo)
    if reduction == "staged":
        assert n_ar == 0, n_ar
        assert "collective-permute" in hlo
    else:
        assert n_ar >= 1, "psum program lost its dot-block all-reduce"
    assert np.prod(mesh.devices.shape) == 4


# --------------------------------------------- the slab's tile layout --

ROOT = Path(__file__).resolve().parents[1]


def _bench_program(name):
    """(op, solver kwargs) of a chip benchmark configuration, built by the
    benchmark's own harness (its backend is built by the test)."""
    spec = importlib.util.spec_from_file_location(
        "chip_harness", ROOT / "benchmarks" / "chip" / "harness.py")
    harness = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = harness        # its dataclasses look it up
    spec.loader.exec_module(harness)
    config = json.loads((ROOT / "benchmarks" / "chip" / "configs"
                         / f"{name}.json").read_text())
    config["backend"] = {"name": "local"}
    op, _backend, kw = harness.build_program(config, ROOT)
    return op, kw


def _one_sublane_vectors(hlo: str, n: int) -> int:
    """Values of at least a vector's length in a one-sublane T(1,128)
    tiling: every vreg that holds one uses 1 of its 8 sublanes."""
    count = 0
    for dims in re.findall(r"= f32\[([0-9,]+)\]\{[^}]*T\(1,128\)", hlo):
        if math.prod(int(d) for d in dims.split(",")) >= n:
            count += 1
    return count


def _slab_ops(hlo: str, slab: str) -> list[str]:
    """Opcodes of the instructions whose value is the whole slab."""
    return re.findall(re.escape(slab) + r"\{[^}]*\} ([a-z-]+)\(", hlo)


@pytest.mark.parametrize("chips", [1, 4])
def test_benchmark_slab_is_tile_aligned(topo, chips):
    """The 2000^2 p(2)-CG programs of ``laplace2d`` (one chip) and
    ``laplace2d-4chip`` (the psum program, four): the slab is
    (14, R, 128) with R = 8 * ceil(n / 1024) for the n one chip holds;
    no vector-length value is tiled T(1,128) (a flat (14, N) slab gives
    106 on one chip and 102 on four); and the slab is only ever updated
    in place — dynamic-update-slices, never a copy of it or a scatter
    into it."""
    op, kw = _bench_program("laplace2d" if chips == 1 else "laplace2d-4chip")
    spec = jax.ShapeDtypeStruct((op.n,), jnp.float32)
    with jax.enable_x64(False):
        if chips == 1:
            sh = SingleDeviceSharding(topo.devices[0])
            solver = get_backend("local").make_solver(op, "plcg", **kw)
            compiled = solver.lower(_spec((op.n,), sh)).compile()
        else:
            mesh = make_solver_mesh(4, devices=topo.devices)
            shard = NamedSharding(mesh, P(mesh.axis_names[0]))
            fn, arrays = distributed_solve(mesh, op, spec, method="plcg",
                                           jit=False, **kw)
            compiled = jax.jit(fn).lower(_spec((op.n,), shard),
                                         arrays).compile()
    hlo = compiled.as_text()
    n = op.n // chips
    slab = f"f32[14,{8 * -(-n // 1024)},128]"
    ops = _slab_ops(hlo, slab)
    assert ops, f"no {slab} slab in the program"
    assert _one_sublane_vectors(hlo, n) == 0
    assert "dynamic-update-slice" in ops
    assert "copy" not in ops and "scatter" not in ops, sorted(set(ops))
