"""Where the persistent compilation cache lives (repro.utils.compile_cache)."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.utils import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_fixed_path_inside_checkout(tmp_path, monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.use_compile_cache(tmp_path)
    assert first == str(tmp_path.resolve() / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    # the same checkout always maps to the same directory
    assert compile_cache.use_compile_cache(tmp_path) == first


_RUN = """
import sys
import jax, jax.numpy as jnp
from jax import monitoring
from repro.utils.compile_cache import use_compile_cache
hits = []
monitoring.register_event_listener(
    lambda name, **kw: hits.append(name) if name.endswith("cache_hits")
    else None)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
use_compile_cache(sys.argv[1])
jax.jit(lambda x: jnp.sin(x) * 2 + 1)(jnp.ones(8)).block_until_ready()
print("HITS", len(hits))
"""


def test_second_process_reads_the_cache(tmp_path):
    env = {k: v for k, v in os.environ.items() if k != compile_cache.ENV_VAR}
    env.update(PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")

    def run():
        proc = subprocess.run([sys.executable, "-c", _RUN, str(tmp_path)],
                              capture_output=True, text=True, env=env,
                              timeout=120)
        assert proc.returncode == 0, proc.stderr[-2000:]
        return int(proc.stdout.split("HITS")[1])

    assert run() == 0
    assert any((tmp_path / ".jax_cache").iterdir())
    assert run() > 0


def test_env_var_is_left_to_jax(tmp_path, monkeypatch, restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "env_cache"))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache(tmp_path) == str(
        tmp_path / "env_cache")
    assert jax.config.jax_compilation_cache_dir == before
