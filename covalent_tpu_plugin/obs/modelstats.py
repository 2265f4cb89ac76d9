"""What a model counts about its own steps, where no trace can see it: the
rows a step sent through the routed experts this process holds, how uneven
they fell, the rows that got no place (none, by construction: counted,
not assumed), and the layers whose bounded row buffer the step outgrew, so
that they took the whole one.

The train step returns the counts as device arrays beside its metrics;
:func:`defer` keeps them and reads them when the NEXT step's arrive (by
then the device has long made them), so no step waits for a transfer and
no host callback stands in the compiled program (jax's persistent cache
takes no program that holds one).  A worker sends :func:`totals` home with
its result; the dispatcher adds them into its own registry with
:func:`absorb_worker` (``covalent_tpu_worker_moe_*``).
"""

from __future__ import annotations

import threading

from .metrics import REGISTRY, Registry

__all__ = [
    "defer", "totals", "absorb_worker", "MOE_ROWS", "MOE_STEPS",
    "MOE_LOAD_RATIO", "WORKER_MOE_ROWS", "WORKER_MOE_STEPS",
    "WORKER_MOE_ROWS_PER_STEP", "WORKER_MOE_LOAD_RATIO",
]

MOE_ROWS = "covalent_tpu_moe_rows_total"
MOE_STEPS = "covalent_tpu_moe_steps_total"
MOE_LOAD_RATIO = "covalent_tpu_moe_load_ratio"
WORKER_MOE_ROWS = "covalent_tpu_worker_moe_rows_total"
WORKER_MOE_STEPS = "covalent_tpu_worker_moe_steps_total"
WORKER_MOE_ROWS_PER_STEP = "covalent_tpu_worker_moe_rows_per_step"
WORKER_MOE_LOAD_RATIO = "covalent_tpu_worker_moe_load_ratio"

_lock = threading.Lock()
_pending: list = []


def _rows(registry: Registry):
    return registry.counter(
        MOE_ROWS, "Rows (token, choice pairs) train steps sent through the "
        "routed experts held here (held), held pairs that got no row "
        "(dropped), and layers that took the whole row buffer for the "
        "bounded one (whole_buffer)", label_names=("kind",))


def _steps(registry: Registry):
    return registry.counter(
        MOE_STEPS, "Train steps whose routed layers reported their rows")


def _load(registry: Registry):
    return registry.gauge(
        MOE_LOAD_RATIO, "The most loaded held expert's rows over the mean "
        "held expert's, the worst layer: of the last step (last), of any "
        "step so far (peak)", label_names=("stat",))


def defer(stats) -> None:
    """Keep one step's ``(layers, 4)`` device array (held rows, peak load
    over mean, dropped rows, whole buffer taken) and record every earlier
    step's."""
    with _lock:
        ready, _pending[:] = list(_pending), [stats]
    _record(ready)


def _record(ready: list, registry: Registry = REGISTRY) -> None:
    if not ready:
        return
    import numpy as np

    rows, steps, load = _rows(registry), _steps(registry), _load(registry)
    for stats in ready:
        table = np.asarray(stats, dtype=np.float64)
        table = table.reshape(-1, table.shape[-1])
        rows.labels(kind="held").inc(float(table[:, 0].sum()))
        rows.labels(kind="dropped").inc(max(0.0, float(table[:, 2].sum())))
        # A program from before the bounded buffer counts three numbers.
        rows.labels(kind="whole_buffer").inc(float(table[:, 3:4].sum()))
        steps.inc()
        worst = float(table[:, 1].max())
        load.labels(stat="last").set(worst)
        peak = load.labels(stat="peak")
        peak.set(max(worst, peak.value))


def totals(registry: Registry = REGISTRY) -> dict:
    """``{"rows": {kind: n}, "steps": n, "load_ratio": {stat: x}}`` so far
    in this process (the step still pending read first); ``{}`` where no
    routed layer ever reported."""
    with _lock:
        ready, _pending[:] = list(_pending), []
    _record(ready, registry)
    steps = registry.get(MOE_STEPS)
    if steps is None:
        return {}
    by_label = lambda name: {  # noqa: E731
        labels[0]: value
        for labels, value in registry.get(name).values().items()}
    return {"rows": by_label(MOE_ROWS),
            "steps": sum(steps.values().values()),
            "load_ratio": by_label(MOE_LOAD_RATIO)}


def absorb_worker(worker_totals, registry: Registry = REGISTRY) -> None:
    """Add one worker's final :func:`totals` into this process's
    ``covalent_tpu_worker_moe_*`` series: the counts as counters, the rows
    a step and the load ratios as gauges of the worker that reported last.
    Never raises: the record crossed a process boundary."""
    try:
        steps = float(worker_totals["steps"])
        rows = {str(k): float(v) for k, v in worker_totals["rows"].items()}
        ratios = {str(k): float(v)
                  for k, v in worker_totals["load_ratio"].items()}
    except (TypeError, KeyError, ValueError, AttributeError):
        return
    if steps <= 0:
        return
    registry.counter(
        WORKER_MOE_STEPS, "Train steps whose routed layers reported their "
        "rows, as workers reported them").inc(steps)
    total = registry.counter(
        WORKER_MOE_ROWS, "Rows through held routed experts (held), held "
        "pairs without a row (dropped) and layers that took the whole row "
        "buffer (whole_buffer), as workers reported them",
        label_names=("kind",))
    per_step = registry.gauge(
        WORKER_MOE_ROWS_PER_STEP, "Rows (held, dropped) and layers "
        "(whole_buffer) a train step, of the worker that reported last",
        label_names=("kind",))
    for kind, value in rows.items():
        total.labels(kind=kind).inc(value)
        per_step.labels(kind=kind).set(value / steps)
    load = registry.gauge(
        WORKER_MOE_LOAD_RATIO, "Most loaded held expert's rows over the "
        "mean's, of the worker that reported last", label_names=("stat",))
    for stat, value in ratios.items():
        load.labels(stat=stat).set(value)
