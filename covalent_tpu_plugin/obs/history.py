"""Metrics history: a bounded downsampling ring over registry snapshots.

The registry (:mod:`.metrics`) answers "what is the value *now*"; every
question the fleet plane actually asks during an incident is about *change*
— requests per second over the last minute, p95 latency over the last five,
whether the queue depth is growing.  This module records fixed-interval
samples of the process registry into one bounded ring and answers windowed
rate/percentile queries over it, with zero third-party dependencies and an
injectable clock for deterministic tests.

**Downsampling.**  The ring holds at most ``capacity`` samples.  When it
fills, every other sample is dropped and the recording stride doubles: the
ring then covers twice the wall-clock span at half the resolution.  Memory
stays bounded forever while the observable window keeps growing — recent
data is fine-grained, old data coarse, which is exactly the shape
dashboards and burn-rate queries want.

**Queries.**  :meth:`MetricsHistory.query` is kind-aware:

* *gauge* — the raw timeline plus last/min/max/avg per labelled series;
* *counter* — the increase and per-second rate over the window (counters
  only go up, so ``last - first`` is the windowed delta);
* *histogram* — the windowed distribution (latest cumulative bucket counts
  minus the earliest in-window sample's), yielding p50/p90/p95/p99, count,
  rate and mean *for the window* rather than for process lifetime.

The process-wide :data:`HISTORY` ring is fed by a daemon sampler thread
(:func:`ensure_history`), started automatically with the ops server and
configurable via ``COVALENT_TPU_HISTORY_S`` (sample interval, default 1.0;
``0``/``off`` disables) and ``COVALENT_TPU_HISTORY_SAMPLES`` (ring
capacity, default 512).  The SLO engine (:mod:`.slo`) subscribes to each
recorded sample via :meth:`MetricsHistory.add_listener`.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Any, Callable

from .metrics import REGISTRY, Registry

__all__ = ["MetricsHistory", "HISTORY", "ensure_history"]

_INTERVAL_ENV = "COVALENT_TPU_HISTORY_S"
_CAPACITY_ENV = "COVALENT_TPU_HISTORY_SAMPLES"
_DEFAULT_INTERVAL_S = 1.0
_DEFAULT_CAPACITY = 512


def _series_key(labels: dict[str, str]) -> str:
    """Stable JSON key for one labelled series ("" for the unlabelled)."""
    if not labels:
        return ""
    return json.dumps(labels, sort_keys=True)


class MetricsHistory:
    """Fixed-interval bounded ring of compact registry samples.

    Thread-safe: the sampler thread records while ops-server request
    threads query.  ``clock`` is injectable so downsampling and windowed
    queries are testable without real sleeps.
    """

    def __init__(
        self,
        registry: Registry = REGISTRY,
        interval_s: float = _DEFAULT_INTERVAL_S,
        capacity: int = _DEFAULT_CAPACITY,
        clock: Callable[[], float] = time.time,
    ) -> None:
        self.registry = registry
        self.interval_s = max(0.0, float(interval_s))
        self.capacity = max(8, int(capacity))
        self._clock = clock
        self._lock = threading.Lock()
        #: (ts, {metric: {"kind", "series": {key: payload}}}) samples,
        #: oldest first.  Counter/gauge payloads are floats; histogram
        #: payloads are (count, sum, cumulative-counts tuple).
        self._samples: collections.deque = collections.deque()
        #: effective recording stride multiplier; doubles on each compaction.
        self._stride = 1
        self._ticks_until_record = 0
        self._listeners: list[Callable[[float], None]] = []

    # -- recording ---------------------------------------------------------

    def _capture(self) -> dict[str, Any]:
        """One compact sample of every registered metric's series."""
        out: dict[str, Any] = {}
        with self.registry._lock:
            metrics = list(self.registry._metrics.values())
        for metric in metrics:
            series: dict[str, Any] = {}
            for labels, child in metric._series():
                key = _series_key(labels)
                if metric.kind == "histogram":
                    series[key] = (
                        child.count,
                        child.sum,
                        tuple(child.cumulative()),
                    )
                else:
                    series[key] = float(child.value)
            out[metric.name] = {"kind": metric.kind, "series": series}
        return out

    def sample(self, force: bool = False) -> bool:
        """Record one sample now; returns whether one was recorded.

        The sampler thread calls this once per ``interval_s`` tick; the
        stride counter makes post-compaction ticks record every Nth call
        so the ring's spacing stays uniform.  ``force`` (tests) bypasses the
        stride.
        """
        now = self._clock()
        with self._lock:
            if not force:
                if self._ticks_until_record > 0:
                    self._ticks_until_record -= 1
                    return False
                self._ticks_until_record = self._stride - 1
            self._samples.append((now, self._capture()))
            if len(self._samples) >= self.capacity:
                # Downsample: drop every other sample (keeping the newest)
                # and double the stride — bounded memory, growing span.
                kept = list(self._samples)[::-2][::-1]
                self._samples = collections.deque(kept)
                self._stride *= 2
        for listener in list(self._listeners):
            try:
                listener(now)
            except Exception:  # noqa: BLE001 - observers must not break flow
                pass
        return True

    def add_listener(self, listener: Callable[[float], None]) -> None:
        """Call ``listener(ts)`` after every recorded sample (SLO engine)."""
        if listener not in self._listeners:
            self._listeners.append(listener)

    def remove_listener(self, listener: Callable[[float], None]) -> None:
        try:
            self._listeners.remove(listener)
        except ValueError:
            pass

    def clear(self) -> None:
        with self._lock:
            self._samples.clear()
            self._stride = 1
            self._ticks_until_record = 0

    # -- views -------------------------------------------------------------

    def __len__(self) -> int:
        with self._lock:
            return len(self._samples)

    @property
    def stride(self) -> int:
        return self._stride

    def span_s(self) -> float:
        """Wall-clock seconds between the oldest and newest sample."""
        with self._lock:
            if len(self._samples) < 2:
                return 0.0
            return self._samples[-1][0] - self._samples[0][0]

    def metric_names(self) -> list[str]:
        with self._lock:
            if not self._samples:
                return []
            return sorted(self._samples[-1][1])

    def describe(self) -> dict[str, Any]:
        """The ``/history`` index payload (no ``metric`` param)."""
        return {
            "samples": len(self),
            "capacity": self.capacity,
            "interval_s": self.interval_s,
            "stride": self._stride,
            "span_s": round(self.span_s(), 3),
            "metrics": self.metric_names(),
        }

    def _window(self, window_s: float) -> list[tuple[float, dict]]:
        """Samples whose ts falls inside the trailing window, oldest first."""
        cutoff = self._clock() - max(0.0, float(window_s))
        with self._lock:
            return [s for s in self._samples if s[0] >= cutoff]

    # -- queries -----------------------------------------------------------

    def query(
        self,
        metric: str,
        window_s: float = 60.0,
        labels: dict[str, str] | None = None,
        agg: str = "",
    ) -> dict[str, Any]:
        """Windowed, kind-aware view of one metric.

        Returns ``{"metric", "kind", "window_s", "samples", "series"}``
        where ``series`` maps the JSON label key to that series' windowed
        stats + timeline.  ``labels`` (exact match) restricts to one
        series.  An unknown metric or empty window answers with zero
        samples rather than raising — dashboards poll speculatively.

        ``agg="trend"`` swaps the per-series stats for a least-squares
        **slope** over the window — the predictive-autoscaling primitive
        ("is the queue depth growing, and how fast"): gauges report
        ``slope_per_s`` of the raw value, counters and histograms report
        the slope of their per-second *rate* (a positive value means
        traffic is accelerating, not merely flowing).
        """
        window = self._window(window_s)
        out: dict[str, Any] = {
            "metric": metric,
            "window_s": float(window_s),
            "samples": len(window),
            "kind": None,
            "series": {},
        }
        if agg:
            out["agg"] = agg
        if not window:
            return out
        wanted = _series_key(labels) if labels else None
        kind = None
        #: key -> [(ts, payload)] across the window
        timelines: dict[str, list[tuple[float, Any]]] = {}
        for ts, snap in window:
            entry = snap.get(metric)
            if entry is None:
                continue
            kind = entry["kind"]
            for key, payload in entry["series"].items():
                if wanted is not None and key != wanted:
                    continue
                timelines.setdefault(key, []).append((ts, payload))
        out["kind"] = kind
        # Cumulative series (counters, histograms) absent from the
        # window's FIRST sample were born mid-window; registry children
        # start at zero, so their true baseline is a zero at the window
        # edge — using their first captured value instead would swallow
        # every observation that landed between two sampler ticks.
        first_entry = window[0][1].get(metric) or {}
        first_series = first_entry.get("series", {})
        window_start = window[0][0]
        for key, points in timelines.items():
            if key not in first_series and kind == "histogram":
                zeros = (0, 0.0, (0,) * len(points[-1][1][2]))
                points = [(window_start, zeros)] + points
            elif key not in first_series and kind == "counter":
                points = [(window_start, 0.0)] + points
            if agg == "trend":
                out["series"][key] = self._trend_stats(kind, points)
            elif kind == "histogram":
                out["series"][key] = self._histogram_stats(metric, points)
            elif kind == "counter":
                out["series"][key] = self._counter_stats(points)
            else:
                out["series"][key] = self._gauge_stats(points)
        return out

    # -- trend (agg="trend") -------------------------------------------------

    @staticmethod
    def _slope_of(points: list[tuple[float, float]]) -> float:
        """Least-squares slope (units per second) over ``(ts, value)``.

        Fewer than two points — or a degenerate time axis — has no
        trend; the answer is 0.0, never an exception (the controller
        polls this every tick, including on freshly started rings).
        """
        n = len(points)
        if n < 2:
            return 0.0
        t0 = points[0][0]
        ts = [t - t0 for t, _ in points]
        vs = [float(v) for _, v in points]
        mean_t = sum(ts) / n
        mean_v = sum(vs) / n
        var_t = sum((t - mean_t) ** 2 for t in ts)
        if var_t <= 0:
            return 0.0
        cov = sum(
            (t - mean_t) * (v - mean_v) for t, v in zip(ts, vs)
        )
        return cov / var_t

    @classmethod
    def _trend_stats(
        cls, kind: str | None, points: list[tuple[float, Any]]
    ) -> dict[str, Any]:
        """Per-series trend: value slope for gauges, rate slope for
        cumulative kinds (counters by value delta, histograms by
        observation-count delta).  Counter resets (value decreasing)
        drop the torn interval instead of reporting a negative burst."""
        if kind == "gauge" or kind is None:
            values = [(ts, float(v)) for ts, v in points]
            return {
                "last": values[-1][1] if values else 0.0,
                "slope_per_s": cls._slope_of(values),
            }
        # Cumulative kinds: build the per-interval rate series at
        # interval midpoints, then fit the slope of THAT — "is the rate
        # itself rising" is the question predictive scaling asks.
        rates: list[tuple[float, float]] = []
        increase = 0.0
        for (t_a, p_a), (t_b, p_b) in zip(points, points[1:]):
            dt = t_b - t_a
            if dt <= 0:
                continue
            v_a = p_a[0] if kind == "histogram" else float(p_a)
            v_b = p_b[0] if kind == "histogram" else float(p_b)
            if v_b < v_a:  # reset between samples: skip the torn interval
                continue
            increase += v_b - v_a
            rates.append(((t_a + t_b) / 2.0, (v_b - v_a) / dt))
        span = max(points[-1][0] - points[0][0], 1e-9)
        return {
            "increase": increase,
            "rate_per_s": (
                increase / span if len(points) > 1 else 0.0
            ),
            "slope_per_s": cls._slope_of(rates),
        }

    @staticmethod
    def _gauge_stats(points: list[tuple[float, float]]) -> dict[str, Any]:
        values = [v for _, v in points]
        return {
            "points": [[round(ts, 3), v] for ts, v in points],
            "last": values[-1],
            "min": min(values),
            "max": max(values),
            "avg": sum(values) / len(values),
        }

    @staticmethod
    def _counter_stats(points: list[tuple[float, float]]) -> dict[str, Any]:
        t0, first = points[0]
        t1, last = points[-1]
        increase = max(0.0, last - first)
        dt = max(t1 - t0, 1e-9)
        return {
            "points": [[round(ts, 3), v] for ts, v in points],
            "last": last,
            "increase": increase,
            # A single in-window sample has no baseline: rate is 0, not a
            # division of the full lifetime count by epsilon.
            "rate_per_s": increase / dt if len(points) > 1 else 0.0,
        }

    def _histogram_stats(
        self, metric: str, points: list[tuple[float, Any]]
    ) -> dict[str, Any]:
        t0, (count0, sum0, cum0) = points[0]
        t1, (count1, sum1, cum1) = points[-1]
        count = max(0, count1 - count0)
        total = max(0.0, sum1 - sum0)
        # Bucket-shape changes across a registry reset make the delta
        # meaningless; fall back to the latest cumulative state.
        if len(cum0) != len(cum1) or count1 < count0:
            count, total, delta = count1, sum1, list(cum1)
        else:
            delta = [max(0, b - a) for a, b in zip(cum0, cum1)]
        hist = self.registry.get(metric)
        bounds = list(getattr(hist, "buckets", ())) + [float("inf")]
        dt = max(t1 - t0, 1e-9)
        stats: dict[str, Any] = {
            "count": count,
            "sum": round(total, 9),
            "rate_per_s": count / dt if len(points) > 1 else 0.0,
            "mean": (total / count) if count else None,
        }
        for q in (0.5, 0.9, 0.95, 0.99):
            stats[f"p{int(q * 100)}"] = self._quantile_from(
                delta, bounds, count, q
            )
        return stats

    @staticmethod
    def _quantile_from(
        cumulative: list[int], bounds: list[float], total: int, q: float
    ) -> float | None:
        """Upper-bound quantile estimate from windowed cumulative counts
        (same semantics as ``metrics._HistogramChild.quantile``)."""
        if total <= 0 or len(cumulative) != len(bounds):
            return None
        target = q * total
        for cum, bound in zip(cumulative, bounds):
            if cum >= target:
                return bound if bound != float("inf") else (
                    bounds[-2] if len(bounds) > 1 else None
                )
        return bounds[-2] if len(bounds) > 1 else None

    def good_fraction(
        self,
        metric: str,
        threshold: float,
        window_s: float,
        labels: dict[str, str] | None = None,
    ) -> tuple[int, float | None]:
        """``(windowed count, fraction of observations <= threshold)``.

        The latency-SLI primitive: how many of the window's observations
        landed at or under the threshold bucket.  ``threshold`` snaps to
        the smallest bucket bound >= itself (Prometheus ``le``
        semantics); fraction is None when the window holds no data.
        """
        window = self._window(window_s)
        wanted = _series_key(labels) if labels else None
        firsts: dict[str, Any] = {}
        lasts: dict[str, Any] = {}
        first_series = (
            (window[0][1].get(metric) or {}).get("series", {})
            if window
            else {}
        )
        for _, snap in window:
            entry = snap.get(metric)
            if entry is None or entry["kind"] != "histogram":
                continue
            for key, payload in entry["series"].items():
                if wanted is not None and key != wanted:
                    continue
                firsts.setdefault(key, payload)
                lasts[key] = payload
        hist = self.registry.get(metric)
        bounds = list(getattr(hist, "buckets", ()))
        if not bounds or not lasts:
            return 0, None
        for key, (_c1, _s1, cum1) in lasts.items():
            if key not in first_series:
                # Born mid-window: zero baseline (see query()).
                firsts[key] = (0, 0.0, (0,) * len(cum1))
        # Index of the threshold bucket (first bound >= threshold).  A
        # threshold above every finite bound snaps to +Inf — the bucket
        # resolution cannot observe a violation there, so everything
        # counts good rather than everything bad (a false "all bad"
        # would page on a service meeting its objective).
        le_index = next(
            (i for i, b in enumerate(bounds) if b >= threshold),
            len(bounds),  # cumulative() carries a trailing +Inf entry
        )
        count = good = 0
        for key, (count1, _sum1, cum1) in lasts.items():
            count0, _sum0, cum0 = firsts[key]
            if len(cum0) != len(cum1) or count1 < count0:
                count0, cum0 = 0, (0,) * len(cum1)
            count += max(0, count1 - count0)
            if le_index >= len(cum1):
                # Defensive: a snapshot without the +Inf entry.
                good += max(0, count1 - count0)
            else:
                good += max(0, cum1[le_index] - cum0[le_index])
        if count <= 0:
            return 0, None
        return count, min(1.0, good / count)

    def bad_ratio(
        self,
        metric: str,
        bad: dict[str, Any] | None,
        window_s: float,
    ) -> tuple[float, float | None]:
        """``(windowed total, bad fraction)`` across a counter family.

        ``bad`` filters series by label values (each value may be a
        scalar or a list of acceptable values); None/empty marks EVERY
        series bad — useful for "this counter should not move at all"
        specs (retries, faults).  For those, the denominator is the
        window's elapsed time in ticks — the fraction is then a rate
        normalized into [0, 1] by min().
        """
        window = self._window(window_s)
        firsts: dict[str, float] = {}
        lasts: dict[str, float] = {}
        first_series = (
            (window[0][1].get(metric) or {}).get("series", {})
            if window
            else {}
        )
        for _, snap in window:
            entry = snap.get(metric)
            if entry is None or entry["kind"] != "counter":
                continue
            for key, payload in entry["series"].items():
                firsts.setdefault(key, payload)
                lasts[key] = payload
        if not lasts:
            return 0.0, None
        for key in lasts:
            if key not in first_series:
                # Born mid-window: zero baseline (see query()).
                firsts[key] = 0.0

        def matches(key: str) -> bool:
            if not bad:
                return True
            labels = json.loads(key) if key else {}
            for name, accept in bad.items():
                values = accept if isinstance(accept, (list, tuple)) else [accept]
                if str(labels.get(name)) not in [str(v) for v in values]:
                    return False
            return True

        total = bad_count = 0.0
        for key, last in lasts.items():
            delta = max(0.0, last - firsts[key])
            total += delta
            if matches(key):
                bad_count += delta
        if bad is None or not bad:
            # Denominatorless spec ("this counter should not move"): the
            # denominator is the window's elapsed sample ticks, so one
            # lone increment in a wide window reads as a small rate —
            # not an instantly-saturated burn.
            if not window:
                return bad_count, None
            ticks = max(1.0, float(len(window) - 1))
            return bad_count, min(1.0, bad_count / ticks)
        if total <= 0:
            return 0.0, None
        return total, bad_count / total


#: Process-wide history ring (fed by :func:`ensure_history`'s sampler).
HISTORY = MetricsHistory()

_thread_lock = threading.Lock()
_thread: threading.Thread | None = None


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name, "").strip().lower()
    if not raw:
        return default
    if raw in ("0", "off", "false", "no", "none"):
        return 0.0
    try:
        return float(raw)
    except ValueError:
        return default


def ensure_history(interval_s: float | None = None) -> MetricsHistory | None:
    """Start the process-wide sampler thread once; returns the ring.

    ``interval_s`` overrides ``COVALENT_TPU_HISTORY_S`` (default 1.0
    second); 0 disables sampling and returns None.  Idempotent — the ops
    server and executors all call this freely.
    """
    global _thread
    interval = (
        _env_float(_INTERVAL_ENV, _DEFAULT_INTERVAL_S)
        if interval_s is None
        else float(interval_s)
    )
    if interval <= 0:
        return None
    with _thread_lock:
        if _thread is not None and _thread.is_alive():
            if interval_s is not None and interval < HISTORY.interval_s:
                # An explicit finer interval wins even after the sampler
                # started (the loop re-reads interval_s every tick).
                # Tighten only — coarsening would silently degrade a
                # timeline some other caller is already relying on.
                HISTORY.interval_s = interval
            return HISTORY
        HISTORY.interval_s = interval
        try:
            HISTORY.capacity = max(
                8, int(os.environ.get(_CAPACITY_ENV, "") or _DEFAULT_CAPACITY)
            )
        except ValueError:
            pass

        def loop() -> None:
            while True:
                time.sleep(HISTORY.interval_s)
                try:
                    HISTORY.sample()
                except Exception:  # noqa: BLE001 - sampler must never die
                    pass

        _thread = threading.Thread(
            target=loop, name="covalent-tpu-history", daemon=True
        )
        _thread.start()
    return HISTORY
