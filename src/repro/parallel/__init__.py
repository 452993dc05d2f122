"""Parallel execution of the solver family (DESIGN.md §2/§3).

Two layers:

* ``repro.parallel.backends`` — the pluggable reduction-backend registry
  (``get_backend("local" | "shard_map" | "multiprocess")``), the API new
  code should use;
* ``repro.parallel.distributed`` — the shard_map mechanism (halo
  exchange, operator partitioning, the fused-psum dot block) the
  backends are built from;
* ``repro.parallel.reduction`` — the staged ring-reduction ladder the
  dot block runs as when a backend is built with ``reduction="staged"``
  (DESIGN.md §14).
"""

from repro.parallel.backends import (
    ReductionBackend,
    available_backends,
    get_backend,
    register_backend,
)
from repro.parallel.reduction import StagedConfig
from repro.parallel.distributed import (
    distributed_solve,
    distributed_solve_batched,
    make_solver_mesh,
    partitioned_solver_ops,
)

__all__ = [
    "ReductionBackend",
    "StagedConfig",
    "available_backends",
    "get_backend",
    "register_backend",
    "distributed_solve",
    "distributed_solve_batched",
    "make_solver_mesh",
    "partitioned_solver_ops",
]
