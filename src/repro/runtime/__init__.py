"""Runtime portability layer: device/mesh/sharding concerns + batching.

Single entry point for everything that touches JAX's (version-volatile)
device and sharding machinery:

  * ``runtime.mesh``    — one ``make_mesh`` API for every mesh in the
    repo (tests, local solves, 16x16 / 2x16x16 production dry-runs) with
    a CPU multi-device fallback for tests, plus the sharding-annotation
    helpers (``constrain``/``batch_axes``) over the ambient mesh that
    ``jax.set_mesh`` installs.
  * ``runtime.cache``   — the persistent compilation cache every entry
    point turns on first.
  * ``runtime.batch``   — shape-bucketed batch solving of heterogeneous
    LP streams with a compiled-executable cache per bucket.
  * ``runtime.sanitize`` — compile-count guard (warm streams assert
    zero recompiles) + ``jax.transfer_guard`` wrapper for the jitted
    solve paths; the runtime twin of ``tools.jaxlint``.
  * ``runtime.cluster`` — multi-host serving: env-driven
    ``jax.distributed`` bring-up with a single-process fallback,
    deterministic per-pod bucket routing, and the
    ``ClusterBatchSolver`` routed-stream scheduler.
"""
from . import batch, cache, mesh, sanitize
# cluster pulls in repro.distributed (fault-tolerant transport); import
# it last so the partially initialized package already exposes the
# submodules that chain re-enters (mesh via distributed.pdhg_dist)
from . import cluster
from .batch import BatchSolver, solve_stream
from .cache import enable_compile_cache
from .cluster import ClusterBatchSolver, init_cluster
from .sanitize import CompileGuard, RecompileError, no_implicit_transfers
from .mesh import (
    batch_axes,
    constrain,
    make_cluster_mesh,
    make_local_mesh,
    make_mesh,
    make_production_mesh,
)

__all__ = [
    "BatchSolver",
    "ClusterBatchSolver",
    "CompileGuard",
    "RecompileError",
    "batch",
    "batch_axes",
    "cache",
    "cluster",
    "constrain",
    "enable_compile_cache",
    "init_cluster",
    "no_implicit_transfers",
    "sanitize",
    "make_cluster_mesh",
    "make_local_mesh",
    "make_mesh",
    "make_production_mesh",
    "mesh",
    "solve_stream",
]
