"""SLO engine: declarative objectives, multi-window error-budget burn rates.

The serving tier streams latency histograms and the dispatch plane counts
outcomes — but nothing *judges* them.  This module closes the loop with the
standard SRE shape: each SLO names an SLI (a latency histogram with a
threshold, or a good/bad counter ratio), an objective (the fraction of good
events promised), and evaluation windows.  The **burn rate** over a window
is ``bad_fraction / (1 - objective)`` — 1.0 means the error budget is being
spent exactly at the promised pace, >1 means an incident in progress.  An
SLO *fires* only when every configured window burns above its threshold
(the classic multi-window gate: the short window proves it's happening
now, the long window proves it's not a blip).

Specs come from three layers, merged by name (later wins):

* shipped defaults (:data:`DEFAULT_SLOS`) covering serve p95 latency,
  TTFT, the task error rate, and dispatch ``wall_overhead``;
* the ``observability.slos`` config key (a list of spec tables);
* the ``COVALENT_TPU_SLOS`` environment variable — a JSON list of spec
  objects, or ``off`` to disable the engine entirely.

Spec object::

    {"name": "serve_p95",                     # unique id (gauge label)
     "metric": "covalent_tpu_serve_request_seconds",
     "kind": "latency",                       # or "ratio"
     "threshold_s": 2.5,                      # latency: good iff <= this
     "bad": {"outcome": ["failed"]},          # ratio: bad-series filter
     "objective": 0.95,                       # promised good fraction
     "windows": [60, 300],                    # evaluation windows (s)
     "burn_threshold": 1.0}                   # fire above this burn

Each evaluation moves ``covalent_tpu_slo_burn_rate{slo}`` (the max burn
across windows), emits ``slo.burn`` / ``slo.recovered`` events on state
transitions, and calls every registered alert hook — the pluggable seam a
deployment points at its pager.  The engine evaluates after every history
sample (it subscribes to :data:`.history.HISTORY`) and on demand from the
ops server's ``GET /slo`` route.
"""

from __future__ import annotations

import json
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable

from . import events as _events
from .history import HISTORY, MetricsHistory, ensure_history
from .metrics import REGISTRY

__all__ = [
    "SLOSpec",
    "SLOEngine",
    "DEFAULT_SLOS",
    "load_slo_specs",
    "ensure_slo_engine",
]

_SLOS_ENV = "COVALENT_TPU_SLOS"

SLO_BURN_RATE = REGISTRY.gauge(
    "covalent_tpu_slo_burn_rate",
    "Error-budget burn rate per SLO (max across its windows; >1 = burning)",
    ("slo",),
)

#: Shipped objectives for the serving + dispatch planes.  Deliberately
#: loose (these are guardrails, not latency targets); deployments
#: tighten them via config/env.
DEFAULT_SLOS: tuple[dict[str, Any], ...] = (
    {
        "name": "serve_p95_latency",
        "metric": "covalent_tpu_serve_request_seconds",
        "kind": "latency",
        "threshold_s": 2.5,
        "objective": 0.95,
        "windows": [60, 300],
    },
    {
        "name": "serve_ttft",
        "metric": "covalent_tpu_serve_ttft_seconds",
        "kind": "latency",
        "threshold_s": 1.0,
        "objective": 0.95,
        "windows": [60, 300],
    },
    {
        "name": "task_error_rate",
        "metric": "covalent_tpu_tasks_total",
        "kind": "ratio",
        "bad": {"outcome": ["failed", "fallback_local"]},
        "objective": 0.99,
        "windows": [60, 300],
    },
    {
        "name": "dispatch_overhead",
        "metric": "covalent_tpu_wall_overhead_seconds",
        "kind": "latency",
        "threshold_s": 2.0,
        "objective": 0.95,
        "windows": [60, 300],
    },
)


@dataclass
class SLOSpec:
    """One declarative objective over a history-backed SLI."""

    name: str
    metric: str
    kind: str = "latency"  # "latency" (histogram) or "ratio" (counter)
    threshold_s: float = 0.0
    bad: dict[str, Any] = field(default_factory=dict)
    objective: float = 0.99
    windows: tuple[float, ...] = (60.0, 300.0)
    burn_threshold: float = 1.0
    labels: dict[str, str] = field(default_factory=dict)

    def __post_init__(self) -> None:
        if not self.name or not self.metric:
            raise ValueError("SLO spec needs a name and a metric")
        if self.kind not in ("latency", "ratio"):
            raise ValueError(
                f"SLO {self.name}: kind must be 'latency' or 'ratio', "
                f"got {self.kind!r}"
            )
        if self.kind == "latency" and self.threshold_s <= 0:
            raise ValueError(f"SLO {self.name}: latency needs threshold_s > 0")
        if not 0.0 < self.objective < 1.0:
            raise ValueError(
                f"SLO {self.name}: objective must be in (0, 1), "
                f"got {self.objective}"
            )
        self.windows = tuple(float(w) for w in self.windows) or (60.0,)
        if any(w <= 0 for w in self.windows):
            raise ValueError(f"SLO {self.name}: windows must be > 0 seconds")

    @property
    def budget(self) -> float:
        return 1.0 - self.objective

    @classmethod
    def from_dict(cls, data: dict[str, Any]) -> "SLOSpec":
        known = set(cls.__dataclass_fields__)
        unknown = set(data) - known
        if unknown:
            raise ValueError(
                f"unknown SLO spec field(s) {sorted(unknown)} "
                f"(known: {sorted(known)})"
            )
        return cls(**data)


def load_slo_specs(env: str | None = None) -> list[SLOSpec]:
    """Defaults <- config ``observability.slos`` <- ``COVALENT_TPU_SLOS``.

    Merged by ``name``, field-level: an override listing only the fields
    it changes tunes the same-name default (new names must be complete
    specs); a spec with ``"disabled": true`` drops that name.  Returns [] when the env
    var is ``off`` (the whole engine then idles).  Malformed layers are
    skipped with a warning — observability config must never take down
    the dispatch it observes.
    """
    raw_env = os.environ.get(_SLOS_ENV) if env is None else env
    if raw_env is not None and raw_env.strip().lower() in (
        "off", "0", "false", "none"
    ):
        return []
    merged: dict[str, dict[str, Any]] = {
        spec["name"]: dict(spec) for spec in DEFAULT_SLOS
    }

    def merge_layer(layer: Any, origin: str) -> None:
        if not isinstance(layer, (list, tuple)):
            raise ValueError(f"expected a list of spec objects, got {layer!r}")
        for entry in layer:
            if not isinstance(entry, dict) or not entry.get("name"):
                raise ValueError(f"spec without a name in {origin}: {entry!r}")
            name = str(entry["name"])
            if entry.get("disabled"):
                merged.pop(name, None)
            else:
                # Field-level merge over a same-name base: tuning one
                # field of a shipped default ({"name": "serve_ttft",
                # "threshold_s": 2.0}) adjusts that field — a whole-spec
                # replace would drop the unnamed required fields and
                # silently DELETE the SLO at from_dict time.
                base = dict(merged.get(name, ()))
                base.update(
                    {k: v for k, v in entry.items() if k != "disabled"}
                )
                merged[name] = base

    from ..utils.config import get_config

    try:
        config_layer = get_config("observability.slos", None)
        if config_layer:
            merge_layer(config_layer, "config observability.slos")
    except Exception as err:  # noqa: BLE001 - bad config never fatal
        from ..utils.log import app_log

        app_log.warning("ignoring observability.slos config: %s", err)
    if raw_env and raw_env.strip():
        try:
            merge_layer(json.loads(raw_env), _SLOS_ENV)
        except (ValueError, TypeError) as err:
            from ..utils.log import app_log

            app_log.warning("ignoring malformed %s: %s", _SLOS_ENV, err)
    specs: list[SLOSpec] = []
    for data in merged.values():
        try:
            specs.append(SLOSpec.from_dict(data))
        except (TypeError, ValueError) as err:
            from ..utils.log import app_log

            app_log.warning("ignoring invalid SLO spec %r: %s", data, err)
    return specs


class SLOEngine:
    """Evaluates SLO specs as burn rates over one history ring.

    Thread-safe; ``clock`` rides the history's clock by default so fake
    clocks in tests drive both windows and evaluations coherently.
    """

    def __init__(
        self,
        history: MetricsHistory,
        specs: list[SLOSpec] | None = None,
        alert_hook: Callable[[str, str, dict], None] | None = None,
    ) -> None:
        self.history = history
        self.specs = list(specs if specs is not None else load_slo_specs())
        self._lock = threading.Lock()
        self._states: dict[str, str] = {}
        self._last: dict[str, Any] = {}
        self._alert_hooks: list[Callable[[str, str, dict], None]] = []
        if alert_hook is not None:
            self._alert_hooks.append(alert_hook)

    def add_alert_hook(
        self, hook: Callable[[str, str, dict], None]
    ) -> None:
        """``hook(slo_name, state, info)`` on every burning/ok transition."""
        if hook not in self._alert_hooks:
            self._alert_hooks.append(hook)

    def remove_alert_hook(
        self, hook: Callable[[str, str, dict], None]
    ) -> None:
        """Detach a hook (no-op when absent) — a closing subscriber
        (e.g. an autoscale controller) must not be kept alive, or kept
        firing, by a process-wide engine."""
        try:
            self._alert_hooks.remove(hook)
        except ValueError:
            pass

    # -- evaluation --------------------------------------------------------

    def _window_burn(
        self, spec: SLOSpec, window_s: float
    ) -> dict[str, Any]:
        """Burn rate + SLI for one spec over one window."""
        if spec.kind == "latency":
            count, good = self.history.good_fraction(
                spec.metric, spec.threshold_s, window_s,
                labels=spec.labels or None,
            )
            if good is None:
                return {"window_s": window_s, "burn": 0.0, "data": False}
            bad_fraction = 1.0 - good
            return {
                "window_s": window_s,
                "burn": bad_fraction / spec.budget,
                "sli": good,
                "count": count,
                "data": True,
            }
        total, bad_fraction = self.history.bad_ratio(
            spec.metric, spec.bad or None, window_s
        )
        if bad_fraction is None:
            return {"window_s": window_s, "burn": 0.0, "data": False}
        return {
            "window_s": window_s,
            "burn": bad_fraction / spec.budget,
            "sli": 1.0 - bad_fraction,
            "count": total,
            "data": True,
        }

    def evaluate(self) -> dict[str, Any]:
        """Evaluate every spec; move gauges, fire transitions, return the
        full view (also served verbatim at ``GET /slo``)."""
        slos: dict[str, Any] = {}
        transitions: list[tuple[str, str, dict]] = []
        with self._lock:
            for spec in self.specs:
                windows = [
                    self._window_burn(spec, w) for w in spec.windows
                ]
                with_data = [w for w in windows if w["data"]]
                max_burn = max((w["burn"] for w in with_data), default=0.0)
                if not with_data:
                    state = "no_data"
                elif all(
                    w["burn"] > spec.burn_threshold for w in with_data
                ):
                    state = "burning"
                else:
                    state = "ok"
                SLO_BURN_RATE.labels(slo=spec.name).set(max_burn)
                info = {
                    "state": state,
                    "burn_rate": round(max_burn, 4),
                    "burn_threshold": spec.burn_threshold,
                    "objective": spec.objective,
                    "kind": spec.kind,
                    "metric": spec.metric,
                    **(
                        {"threshold_s": spec.threshold_s}
                        if spec.kind == "latency"
                        else {"bad": spec.bad}
                    ),
                    "windows": [
                        {
                            k: (round(v, 4) if isinstance(v, float) else v)
                            for k, v in w.items()
                        }
                        for w in windows
                    ],
                }
                slos[spec.name] = info
                previous = self._states.get(spec.name, "ok")
                # no_data is not a recovery — a quiet window after an
                # incident must not clear the alert until good traffic does.
                if state == "burning" and previous != "burning":
                    transitions.append((spec.name, "burning", info))
                    self._states[spec.name] = "burning"
                elif state == "ok" and previous == "burning":
                    transitions.append((spec.name, "ok", info))
                    self._states[spec.name] = "ok"
                elif spec.name not in self._states:
                    self._states[spec.name] = state
            self._last = {
                "evaluated_at": round(time.time(), 3),
                "slos": slos,
            }
        for name, state, info in transitions:
            _events.emit(
                "slo.burn" if state == "burning" else "slo.recovered",
                slo=name,
                **{
                    k: v for k, v in info.items()
                    if k in ("burn_rate", "burn_threshold", "objective",
                             "windows", "state", "metric")
                },
            )
            for hook in list(self._alert_hooks):
                try:
                    hook(name, state, info)
                except Exception:  # noqa: BLE001 - alerting must not break
                    pass
        return dict(self._last)

    def status(self) -> dict[str, Any]:
        """Most recent evaluation (evaluating first if none happened)."""
        with self._lock:
            last = dict(self._last)
        if last:
            return last
        return self.evaluate()


_engine_lock = threading.Lock()
_engine: SLOEngine | None = None


def ensure_slo_engine() -> SLOEngine | None:
    """Start the process-wide engine over :data:`HISTORY` once.

    Subscribes an evaluation to every history sample so burn events fire
    without any scrape; returns None when ``COVALENT_TPU_SLOS=off`` or
    history sampling is disabled.  Idempotent.
    """
    global _engine
    with _engine_lock:
        if _engine is not None:
            return _engine
        specs = load_slo_specs()
        if not specs or ensure_history() is None:
            return None
        engine = SLOEngine(HISTORY, specs=specs)
        HISTORY.add_listener(lambda _ts: engine.evaluate())
        _engine = engine
    return _engine


def get_engine() -> SLOEngine | None:
    """The process-wide engine if one is running (ops ``/slo`` route)."""
    return _engine
