"""Pins the measurement conventions EXPERIMENTS.md §Roofline relies on:
(1) compiled.cost_analysis() reports the PER-DEVICE partitioned module;
(2) collective payloads parsed from the partitioned HLO are shard-sized.
Subprocess with 4 fake host devices (tests must not set XLA_FLAGS
globally)."""

import os
import subprocess
import sys


def test_cost_analysis_is_per_device():
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.utils.hlo import count_collectives
from repro.utils.roofline import cost_analysis_dict

mesh = jax.make_mesh((4,), ("x",))
n = 512
a = jax.ShapeDtypeStruct((n, n), jnp.float32)
bsh = NamedSharding(mesh, P("x", None))
rep = NamedSharding(mesh, P())

# sharded matmul: per-device flops = 2 n^3 / 4
comp = jax.jit(lambda a, b: a @ b,
               in_shardings=(bsh, rep)).lower(a, a).compile()
flops = cost_analysis_dict(comp)["flops"]
assert abs(flops - 2 * n**3 / 4) / (2 * n**3 / 4) < 0.01, flops

# psum of a replicated (n,n): partitioned all-reduce payload = full tensor
comp2 = jax.jit(
    lambda x: jax.shard_map(
        lambda v: jax.lax.psum(v, "x"), mesh=mesh,
        in_specs=P("x", None), out_specs=P(), check_vma=False)(x),
    in_shardings=(bsh,), out_shardings=rep).lower(a).compile()
c = count_collectives(comp2.as_text())
ar = c.get("all-reduce", {"bytes": 0})
# each device contributes its (n/4, n) shard -> payload n/4*n*4 bytes
assert ar["bytes"] == n // 4 * n * 4, c
print("PER-DEVICE-OK")
"""
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=os.getcwd(), timeout=600)
    assert "PER-DEVICE-OK" in out.stdout, (out.stdout[-1000:],
                                           out.stderr[-2000:])
