"""Parallelism layer: device meshes, logical shardings, collectives.

The reference has **no** parallelism components (SURVEY §2.4 — exhaustively
verified: no DP/TP/PP/SP/EP, no collectives; its only distributed dimension
is task-level fan-out over SSH).  This subpackage is the TPU-native
capability the north star adds: electrons scale *within* a task via
``jax.sharding`` meshes + pjit/shard_map, with XLA emitting the ICI/DCN
collectives — never hand-written NCCL-style calls.
"""

# Lazy (PEP 562) re-exports: mesh/sharding/collectives import jax at module
# level (seconds), which the dispatcher control plane — which imports this
# package only for `coordinator_spec` — must not pay.
import importlib
import sys

_EXPORTS = {
    "psum": ".collectives",
    "all_gather": ".collectives",
    "all_to_all": ".collectives",
    "reduce_scatter": ".collectives",
    "ring_permute": ".collectives",
    "coordinator_spec": ".distributed",
    "process_info": ".distributed",
    "MeshPlan": ".mesh",
    "auto_mesh": ".mesh",
    "make_mesh": ".mesh",
    "make_hybrid_mesh": ".mesh",
    "DEFAULT_RULES": ".sharding",
    "batch_sharding": ".sharding",
    "logical_sharding": ".sharding",
    "param_shardings": ".sharding",
    "replicated": ".sharding",
    "shard_batch": ".sharding",
    "shard_batch_per_process": ".sharding",
    "process_local_slice": ".sharding",
    "pipelined": ".pipeline",
    "pipeline_apply": ".pipeline",
    "pipeline_stages": ".pipeline",
}


def __getattr__(name):
    if name in _EXPORTS:
        module = importlib.import_module(_EXPORTS[name], __name__)
        if "jax" in sys.modules:
            # That module imported jax (``.distributed`` alone does not):
            # from here on the process keeps its own account of its
            # compiles (obs.jitstats).
            from ..obs.jitstats import watch_jit

            watch_jit()
        value = getattr(module, name)
        globals()[name] = value  # cache: subsequent lookups skip __getattr__
        return value
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))


__all__ = [
    "MeshPlan",
    "auto_mesh",
    "make_mesh",
    "make_hybrid_mesh",
    "DEFAULT_RULES",
    "logical_sharding",
    "param_shardings",
    "batch_sharding",
    "shard_batch",
    "shard_batch_per_process",
    "process_local_slice",
    "pipelined",
    "pipeline_apply",
    "pipeline_stages",
    "replicated",
    "psum",
    "all_gather",
    "all_to_all",
    "reduce_scatter",
    "ring_permute",
    "process_info",
    "coordinator_spec",
]
