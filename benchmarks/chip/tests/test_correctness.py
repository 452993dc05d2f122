"""``correct`` on the CPU at a 32 x 24 grid: sound runs pass; the control
(the same solves in bfloat16, the precision below the configuration's
float32) and each fault the cells can have, planted under the timed path,
come out not correct."""

import json
import re

import jax
import jax.numpy as jnp
import pytest

import repro.parallel.distributed as distributed
from repro.core import METHODS
from helpers import make_checkout, run, small_config

CELLS = {"laplace2d": "laplace2d.solve",
         "laplace2d-4chip": "laplace2d-4chip.solve"}


@pytest.fixture
def checkout(tmp_path_factory):
    def make(**override):
        return make_checkout(tmp_path_factory.mktemp("checkout"), {
            name: small_config(name, **override) for name in CELLS})
    return make


def _checks(result):
    return {k: v["value"] for k, v in result["checks"].items()}


@pytest.mark.parametrize("config", sorted(CELLS))
def test_sound_run_is_correct(checkout, capsys, config):
    rc, result = run(checkout(), CELLS[config], capsys)
    assert rc == 0 and result["correct"] is True
    assert result["failed"] == 0
    assert _checks(result)["worst_true_rel"] <= 2e-4


def _updates_per_solve(root, workload, capsys):
    """(result, mean updates per solve from the ``[window]`` lines) of one
    CPU run of ``workload``."""
    import run as entry

    rc = entry.main(["--workload", workload, "--seed", str(2**31 + 17),
                     "--seconds", "0.5", "--trace", "0"],
                    root=root, bench_dir=root / "benchmarks" / "chip",
                    require_chip=False)
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    iters = [int(m.group(1)) for ln in lines
             if (m := re.match(r"\[window\] solve \d+: iters=(\d+)", ln))]
    return json.loads(lines[-1]), sum(iters) / len(iters)


@pytest.mark.parametrize("prec", [{"prec": "jacobi"},
                                  {"prec": "blockjacobi", "block_size": 24}],
                         ids=["jacobi", "blockjacobi"])
@pytest.mark.parametrize("config", sorted(CELLS))
def test_preconditioned_run_is_correct(checkout, capsys, config, prec):
    """A configuration that names a preconditioner runs through the
    harness and is correct; block-Jacobi over whole grid lines takes fewer
    updates than no preconditioner."""
    base = small_config(config)
    result, updates = _updates_per_solve(
        checkout(problem=dict(base["problem"], **prec)), CELLS[config],
        capsys)
    assert result["correct"] is True and result["failed"] == 0
    assert _checks(result)["worst_true_rel"] <= 2e-4
    if prec["prec"] == "blockjacobi":
        _, plain = _updates_per_solve(checkout(), CELLS[config], capsys)
        assert updates < plain


@pytest.mark.parametrize("config", sorted(CELLS))
def test_bfloat16_control_is_not_correct(checkout, capsys, config):
    rc, sound = run(checkout(), CELLS[config], capsys)
    rc, control = run(checkout(dtype="bfloat16"), CELLS[config],
                      capsys)
    assert rc == 0 and control["correct"] is False
    assert _checks(control)["worst_true_rel"] >= \
        3 * _checks(sound)["worst_true_rel"]


def _altered_x(alter):
    """The p(l)-CG solver with its answer changed where it is produced."""
    solve = METHODS["plcg"]

    def wrapped(ops, b, kw):
        res = solve(ops, b, kw)
        return res._replace(x=alter(res.x))
    return wrapped


FAULTS = {
    # the solve hands back its starting state unchanged
    "state_unchanged": lambda x: jnp.zeros_like(x),
    # half of the unknowns left out of the answer
    "half_left_out": lambda x: x.at[x.shape[0] // 2:].set(0),
    # one entry of the answer altered
    "answer_altered": lambda x: x.at[x.shape[0] // 3].add(1.0),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("config", sorted(CELLS))
def test_planted_fault_is_not_correct(checkout, capsys, monkeypatch,
                                      config, fault):
    monkeypatch.setitem(METHODS, "plcg", _altered_x(FAULTS[fault]))
    rc, result = run(checkout(), CELLS[config], capsys)
    assert rc == 0 and result["correct"] is False


def test_dot_block_exchange_left_out_is_not_correct(checkout, capsys,
                                                     monkeypatch):
    """The other chips' partial sums dropped from the dot block's
    all-reduce: every shard goes on with chip 0's partials alone.  (Each
    shard keeping its own partials instead would desynchronise the shards'
    loops and hang the halo exchange: an answer that never comes.)"""
    psum = jax.lax.psum

    def first_shard_only(x, axis_name, **kw):
        keep = jax.lax.axis_index(axis_name) == 0
        return psum(jax.tree.map(lambda v: jnp.where(keep, v, 0), x),
                    axis_name, **kw)
    monkeypatch.setattr(jax.lax, "psum", first_shard_only)
    rc, result = run(checkout(), CELLS["laplace2d-4chip"], capsys)
    assert rc == 0 and result["correct"] is False


def test_halo_exchange_left_out_is_not_correct(checkout, capsys,
                                               monkeypatch):
    """The stencil's halo exchange between neighbouring chips dropped."""
    def no_halo(g, axis):
        z = jnp.zeros_like(g[:1])
        return z, z
    monkeypatch.setattr(distributed, "_halo_first_dim", no_halo)
    rc, result = run(checkout(), CELLS["laplace2d-4chip"], capsys)
    assert rc == 0 and result["correct"] is False
