"""The program's own account of its jit compiles.

:func:`watch_jit` registers ``jax.monitoring`` listeners, once per process,
that feed ``REGISTRY``: seconds by phase and persistent-cache hits and
misses, under the event names jax 0.9.0 emits.  It is called where the
program imports jax anyway (``models/``, ``parallel/``), never from code a
plain-Python electron runs.  A worker sends :func:`totals` home with its
result (launch mode) or in ``serve.stats`` (serving); the dispatcher adds
them into its own registry with :func:`absorb_worker`.

Phases (``covalent_tpu_jit_seconds_total{phase=}``):

``jaxpr_trace``
    Python tracing of jitted functions; a jit traced inside another's
    trace is counted once, in the outer one.
``jaxpr_to_mlir_module``
    lowering to StableHLO.
``backend_compile``
    ``compile_or_get_cached``: the backend's compile on a cache miss, the
    fetch and deserialisation on a hit.  Holds ``cache_retrieval``.
``cache_retrieval``
    the persistent cache's read alone, on hits.

``covalent_tpu_compile_cache_total{result=hit|miss}`` counts backend
compiles that asked the persistent cache (none is counted while the cache
is off).  A miss is a request that no hit answered by the time its
``backend_compile`` ended: jax's own miss event fires only for entries it
then writes, which leaves out programs under its size and time thresholds.
"""

from __future__ import annotations

import threading

from .metrics import REGISTRY, Registry

__all__ = [
    "watch_jit", "totals", "absorb_worker",
    "JIT_SECONDS", "COMPILE_CACHE", "WORKER_JIT_SECONDS",
    "WORKER_COMPILE_CACHE",
]

JIT_SECONDS = "covalent_tpu_jit_seconds_total"
COMPILE_CACHE = "covalent_tpu_compile_cache_total"
WORKER_JIT_SECONDS = "covalent_tpu_worker_jit_seconds_total"
WORKER_COMPILE_CACHE = "covalent_tpu_worker_compile_cache_total"

_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "jaxpr_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jaxpr_to_mlir_module",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
}
_CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"

_watch_lock = threading.Lock()
_watching = False


class _PerThread(threading.local):
    """Open events of each phase (jax announces a phase's start as a
    scalar, its end as a duration), and a cache request awaiting its hit."""

    def __init__(self) -> None:
        self.depth: dict[str, int] = {}
        self.asked = False


_local = _PerThread()


def _seconds(registry: Registry = REGISTRY):
    return registry.counter(
        JIT_SECONDS, "Seconds this process spent tracing, lowering and "
        "compiling jitted programs", label_names=("phase",))


def _cache(registry: Registry = REGISTRY):
    return registry.counter(
        COMPILE_CACHE, "Backend compiles answered (hit) or not (miss) by "
        "jax's persistent compilation cache", label_names=("result",))


def _on_start(event: str, _value, **_kwargs) -> None:
    if event in _PHASES:
        _local.depth[event] = _local.depth.get(event, 0) + 1


def _on_duration(event: str, duration: float, **_kwargs) -> None:
    phase = _PHASES.get(event)
    if phase is None:
        return
    open_now = _local.depth.get(event, 0)
    if open_now > 1:
        # Nested inside an event of the same phase, whose own duration
        # already holds this one.
        _local.depth[event] = open_now - 1
        return
    _local.depth[event] = 0
    _seconds().labels(phase=phase).inc(max(0.0, float(duration)))
    if phase == "backend_compile" and _local.asked:
        _local.asked = False
        _cache().labels(result="miss").inc()


def _on_event(event: str, **_kwargs) -> None:
    if event == _CACHE_REQUEST:
        _local.asked = True
    elif event == _CACHE_HIT:
        _local.asked = False
        _cache().labels(result="hit").inc()


def watch_jit() -> None:
    """Register the listeners; idempotent, and a no-op ever after."""
    global _watching
    with _watch_lock:
        if _watching:
            return
        _watching = True
    from jax import monitoring

    monitoring.register_scalar_listener(_on_start)
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)


def totals(registry: Registry = REGISTRY) -> dict:
    """``{"seconds": {phase: s}, "cache": {result: n}}`` so far in this
    process; empty dicts where nothing compiled."""
    out = {}
    for key, name in (("seconds", JIT_SECONDS), ("cache", COMPILE_CACHE)):
        metric = registry.get(name)
        out[key] = {} if metric is None else {
            labels[0]: value for labels, value in metric.values().items()
        }
    return out


def absorb_worker(
    worker_totals, seen: dict | None = None, source=None,
    registry: Registry = REGISTRY,
) -> None:
    """Add a worker's :func:`totals` into this process's
    ``covalent_tpu_worker_*`` series.

    A worker that reports more than once (a serving session's stats, a
    resident runtime's invocations) reports running totals: pass the same
    ``seen`` dict every time and only the growth is added.  ``source``
    names the reporting process (its pid): when it changes (a handoff, a
    restarted runtime) the totals start from zero again and ``seen`` is
    forgotten.  Never raises: the record crossed a process boundary.
    """
    if not isinstance(worker_totals, dict):
        return
    if seen is not None and seen.get("source") != source:
        seen.clear()
        seen["source"] = source
    for key, name, label, text in (
        ("seconds", WORKER_JIT_SECONDS, "phase",
         "Seconds workers spent tracing, lowering and compiling jitted "
         "programs, as they reported them"),
        ("cache", WORKER_COMPILE_CACHE, "result",
         "Workers' backend compiles answered (hit) or not (miss) by the "
         "persistent compilation cache, as they reported them"),
    ):
        values = worker_totals.get(key)
        if not isinstance(values, dict):
            continue
        counter = registry.counter(name, text, label_names=(label,))
        for which, value in values.items():
            try:
                value = float(value)
            except (TypeError, ValueError):
                continue
            before = 0.0
            if seen is not None:
                before = seen.get((key, which), 0.0)
                seen[(key, which)] = max(before, value)
            if value > before:
                counter.labels(**{label: str(which)}).inc(value - before)
