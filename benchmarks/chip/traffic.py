"""The one traffic generator: turns a mix's parameters (``traffic/<mix>.json``)
and a run's seed into right-hand sides on the device.

A mix names how the exact solution u is drawn (``exact_solution``) and
how many right-hand sides the pool holds (``pool``); the generator forms
b = A u with the configuration's plain reference operator, in the
configuration's dtype, in one jitted call.  Each right-hand side is its
own device array, so the window picks one without running a program.
"""

from __future__ import annotations

import numpy as np


def seed_key(seed: int):
    """A JAX PRNG key for any whole ``seed`` (also one over 32 bits)."""
    import jax

    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32),
                                    impl="threefry2x32")


def draw_exact(key, shape, dtype, spec: dict):
    import jax

    kind = spec["distribution"]
    if kind == "uniform":
        return jax.random.uniform(key, shape, dtype, spec["low"], spec["high"])
    raise ValueError(f"unknown exact-solution distribution {kind!r}")


def rhs_pool(mix: dict, seed: int, n: int, dtype, apply_a, sharding=None):
    """``mix["pool"]`` right-hand sides b = A u, u drawn per ``mix`` from
    ``seed``: a list of (n,) device arrays, placed with ``sharding``."""
    import jax

    count = int(mix["pool"])
    spec = mix["exact_solution"]

    def make(key):
        keys = jax.random.split(key, count)
        return tuple(apply_a(draw_exact(k, (n,), dtype, spec)) for k in keys)

    out = None if sharding is None else (sharding,) * count
    return list(jax.block_until_ready(
        jax.jit(make, out_shardings=out)(seed_key(seed))))
