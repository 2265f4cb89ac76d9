"""Bounded in-memory trace store with tail-based sampling.

Spans already fan out as ``span`` events on the structured event stream
(``obs.events``) — dispatcher-side from :class:`~.trace.Span`, worker- and
agent-side re-emitted off the telemetry backhaul with their original ids.
This module is the queryable half: one process-wide listener groups those
events by ``trace_id`` into bounded per-trace buffers, and when a trace's
root span closes it makes the *tail-based* keep/drop decision — by then
the whole trace is known, so the decision can look at what head-based
sampling cannot:

* **errors** — any span with ``status != OK`` keeps the trace;
* **SLO burn** — traces that overlapped a burning SLO window (the store
  listens for ``slo.burn`` / ``slo.recovered``) are always kept;
* **p99 outliers** — a root whose duration lands at or above the p99 of
  recent same-named roots is kept (that is exactly the trace an operator
  wants when a histogram exemplar points here);
* everything else survives with probability ``COVALENT_TPU_TRACE_SAMPLE``
  (default 0.1).

The ops server serves ``GET /traces`` (index) and ``GET /traces/<id>``
(waterfall JSON: spans with offsets/depths plus per-segment aggregation
and end-to-end coverage).  Bounds: ``COVALENT_TPU_TRACE_STORE_TRACES``
kept traces (LRU, default 256), ``COVALENT_TPU_TRACE_SPANS`` spans per
trace (default 512), ``COVALENT_TPU_TRACE_PENDING`` open traces
(default 512).  Everything degrades by dropping records, never by
raising into the instrumented path.
"""

from __future__ import annotations

import collections
import os
import random
import threading
import time
from typing import Any

from . import events as _events

__all__ = ["TraceStore", "TRACE_STORE", "ensure_trace_store", "get_store"]

_SAMPLE_ENV = "COVALENT_TPU_TRACE_SAMPLE"
_TRACES_ENV = "COVALENT_TPU_TRACE_STORE_TRACES"
_SPANS_ENV = "COVALENT_TPU_TRACE_SPANS"
_PENDING_ENV = "COVALENT_TPU_TRACE_PENDING"
_DEFAULT_SAMPLE = 0.1
_DEFAULT_TRACES = 256
_DEFAULT_SPANS = 512
_DEFAULT_PENDING = 512
#: Minimum same-named root durations seen before the p99-outlier rule
#: activates (a fresh process would otherwise keep its first N traces as
#: trivial "outliers" of a one-element distribution).
_OUTLIER_MIN_HISTORY = 20
#: Recently dropped trace ids remembered so a straggler span (a worker
#: record that crossed the wire after the root closed) cannot resurrect a
#: sampled-out trace as a new pending entry.
_DROPPED_MEMORY = 1024

_SPAN_FIELDS = (
    "name", "span_id", "parent_id", "start_ts", "duration_s", "status",
    "attributes",
)


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _env_int(name: str, default: int) -> int:
    try:
        return max(1, int(os.environ.get(name, "") or default))
    except ValueError:
        return default


class TraceStore:
    """Groups ``span`` events into traces; keeps the interesting tails."""

    def __init__(
        self,
        max_traces: int | None = None,
        max_spans: int | None = None,
        max_pending: int | None = None,
        sample: float | None = None,
    ) -> None:
        self.max_traces = (
            _env_int(_TRACES_ENV, _DEFAULT_TRACES)
            if max_traces is None
            else max(1, int(max_traces))
        )
        self.max_spans = (
            _env_int(_SPANS_ENV, _DEFAULT_SPANS)
            if max_spans is None
            else max(1, int(max_spans))
        )
        self.max_pending = (
            _env_int(_PENDING_ENV, _DEFAULT_PENDING)
            if max_pending is None
            else max(1, int(max_pending))
        )
        self._sample_override = None if sample is None else float(sample)
        self._lock = threading.Lock()
        #: trace_id -> open trace being assembled (root not yet seen).
        self._pending: "collections.OrderedDict[str, dict]" = (
            collections.OrderedDict()
        )
        #: trace_id -> finalized kept trace, LRU-evicted.
        self._kept: "collections.OrderedDict[str, dict]" = (
            collections.OrderedDict()
        )
        #: root span name -> recent durations (the p99-outlier baseline).
        self._durations: dict[str, collections.deque] = {}
        self._dropped: "collections.OrderedDict[str, None]" = (
            collections.OrderedDict()
        )
        self._slo_burning: set[str] = set()
        self.finalized = 0
        self.kept_total = 0

    @property
    def sample(self) -> float:
        """Keep probability for unremarkable traces.

        Reads ``COVALENT_TPU_TRACE_SAMPLE`` live (unless constructed with
        an explicit rate) so tests can retune the process-wide store
        after import.
        """
        if self._sample_override is not None:
            return min(1.0, max(0.0, self._sample_override))
        return min(1.0, max(0.0, _env_float(_SAMPLE_ENV, _DEFAULT_SAMPLE)))

    @sample.setter
    def sample(self, value: float) -> None:
        self._sample_override = float(value)

    # -- feeding -----------------------------------------------------------

    def record_event(self, event: dict[str, Any]) -> None:
        """Events-stream listener; never raises (observer contract)."""
        try:
            etype = event.get("type")
            if etype == "span":
                self._record_span(event)
            elif etype == "slo.burn":
                with self._lock:
                    self._slo_burning.add(str(event.get("slo")))
            elif etype == "slo.recovered":
                with self._lock:
                    self._slo_burning.discard(str(event.get("slo")))
        except Exception:  # noqa: BLE001 - observers must not break flow
            pass

    def _record_span(self, event: dict[str, Any]) -> None:
        trace_id = event.get("trace_id")
        if not trace_id:
            return
        trace_id = str(trace_id)
        span = {k: event[k] for k in _SPAN_FIELDS if k in event}
        with self._lock:
            kept = self._kept.get(trace_id)
            if kept is not None:
                # Straggler from a remote worker: the root already closed
                # and the trace was kept — splice the span in so the
                # waterfall stays complete.
                if len(kept["spans"]) < self.max_spans:
                    kept["spans"].append(span)
                    kept["span_count"] = len(kept["spans"])
                else:
                    kept["dropped_spans"] = kept.get("dropped_spans", 0) + 1
                return
            if trace_id in self._dropped:
                return
            trace = self._pending.get(trace_id)
            if trace is None:
                trace = {
                    "trace_id": trace_id,
                    "first_ts": event.get("ts") or time.time(),
                    "spans": [],
                    "dropped_spans": 0,
                    "slo_burn": False,
                }
                self._pending[trace_id] = trace
                while len(self._pending) > self.max_pending:
                    stale_id, stale = self._pending.popitem(last=False)
                    self._finalize_locked(stale_id, stale, root=None)
            else:
                self._pending.move_to_end(trace_id)
            if self._slo_burning:
                trace["slo_burn"] = True
            if len(trace["spans"]) >= self.max_spans:
                trace["dropped_spans"] += 1
                return
            trace["spans"].append(span)
            if span.get("parent_id") is None:
                # Root closed: the whole trace is now known — decide.
                del self._pending[trace_id]
                self._finalize_locked(trace_id, trace, root=span)

    # -- tail-based decision ----------------------------------------------

    def _outlier_threshold(self, name: str) -> float | None:
        history = self._durations.get(name)
        if history is None or len(history) < _OUTLIER_MIN_HISTORY:
            return None
        ordered = sorted(history)
        return ordered[min(len(ordered) - 1, int(0.99 * len(ordered)))]

    def _finalize_locked(
        self, trace_id: str, trace: dict, root: dict | None
    ) -> None:
        self.finalized += 1
        reason = None
        duration = float((root or {}).get("duration_s") or 0.0)
        if root is not None:
            name = str(root.get("name") or "")
            threshold = self._outlier_threshold(name)
            history = self._durations.get(name)
            if history is None:
                history = collections.deque(maxlen=512)
                self._durations[name] = history
            history.append(duration)
            if threshold is not None and duration >= threshold:
                reason = "p99_outlier"
        if any(s.get("status") not in (None, "OK") for s in trace["spans"]):
            reason = "error"
        elif trace["slo_burn"]:
            reason = "slo_burn"
        if reason is None:
            if root is None:
                reason = "evicted"  # pending overflow: sample like the rest
            if random.random() >= self.sample:
                self._dropped[trace_id] = None
                while len(self._dropped) > _DROPPED_MEMORY:
                    self._dropped.popitem(last=False)
                return
            reason = reason or "sampled"
        self.kept_total += 1
        trace["keep_reason"] = reason
        trace["root"] = (root or {}).get("name")
        trace["duration_s"] = duration if root is not None else None
        trace["span_count"] = len(trace["spans"])
        self._kept[trace_id] = trace
        while len(self._kept) > self.max_traces:
            self._kept.popitem(last=False)

    # -- views -------------------------------------------------------------

    def index(self) -> dict[str, Any]:
        """The ``GET /traces`` payload: newest-first trace summaries."""
        with self._lock:
            kept = [
                {
                    "trace_id": t["trace_id"],
                    "root": t.get("root"),
                    "duration_s": t.get("duration_s"),
                    "start_ts": t.get("first_ts"),
                    "span_count": t.get("span_count", len(t["spans"])),
                    "keep_reason": t.get("keep_reason"),
                }
                for t in reversed(self._kept.values())
            ]
            pending = len(self._pending)
            finalized = self.finalized
            kept_total = self.kept_total
        return {
            "traces": kept,
            "count": len(kept),
            "pending": pending,
            "finalized": finalized,
            "kept_total": kept_total,
            "sample": self.sample,
        }

    def waterfall(self, trace_id: str) -> dict[str, Any] | None:
        """The ``GET /traces/<id>`` payload: one trace as a waterfall.

        Spans come back start-ordered with ``offset_s`` (from the earliest
        span start), ``depth`` (parent chain length), and ``orphan``
        (parent id set but absent from the trace).  ``segments``
        aggregates the spans that carry a ``segment`` attribute — the
        waterfall tiling the serving path records — and ``coverage`` is
        their summed share of the root duration, which is how tests
        assert the segments account for the measured end-to-end latency.
        """
        with self._lock:
            trace = self._kept.get(trace_id) or self._pending.get(trace_id)
            if trace is None:
                return None
            spans = [dict(s) for s in trace["spans"]]
            out = {
                "trace_id": trace_id,
                "root": trace.get("root"),
                "duration_s": trace.get("duration_s"),
                "keep_reason": trace.get("keep_reason", "open"),
                "dropped_spans": trace.get("dropped_spans", 0),
            }
        by_id = {s.get("span_id"): s for s in spans if s.get("span_id")}
        starts = [
            s["start_ts"] for s in spans if s.get("start_ts") is not None
        ]
        t0 = min(starts) if starts else 0.0
        root_duration = out.get("duration_s")
        segments: dict[str, dict[str, Any]] = {}
        for span in spans:
            parent = span.get("parent_id")
            depth, seen, node = 0, set(), span
            while node is not None and node.get("parent_id") in by_id:
                pid = node["parent_id"]
                if pid in seen:
                    break  # defensive: a cycle off the wire must not hang
                seen.add(pid)
                node = by_id[pid]
                depth += 1
            span["depth"] = depth
            span["orphan"] = bool(parent) and parent not in by_id
            if span.get("start_ts") is not None:
                span["offset_s"] = round(span["start_ts"] - t0, 6)
            segment = (span.get("attributes") or {}).get("segment")
            if segment:
                agg = segments.setdefault(
                    str(segment), {"duration_s": 0.0, "count": 0}
                )
                agg["duration_s"] = round(
                    agg["duration_s"] + float(span.get("duration_s") or 0.0),
                    6,
                )
                agg["count"] += 1
        spans.sort(key=lambda s: (s.get("start_ts") or 0.0, s["depth"]))
        out["spans"] = spans
        out["span_count"] = len(spans)
        out["start_ts"] = t0 or None
        out["segments"] = segments
        if segments and root_duration:
            out["coverage"] = round(
                sum(s["duration_s"] for s in segments.values())
                / root_duration,
                4,
            )
        return out

    def dump(self) -> dict[str, Any]:
        """Everything, for the CI trace-store artifact."""
        with self._lock:
            kept_ids = list(self._kept)
        waterfalls = []
        for trace_id in kept_ids:
            wf = self.waterfall(trace_id)
            if wf is not None:
                waterfalls.append(wf)
        return {
            "ts": round(time.time(), 6),
            "index": self.index(),
            "traces": waterfalls,
        }

    def clear(self) -> None:
        with self._lock:
            self._pending.clear()
            self._kept.clear()
            self._durations.clear()
            self._dropped.clear()
            self._slo_burning.clear()
            self.finalized = 0
            self.kept_total = 0


#: Process-wide store (fed once :func:`ensure_trace_store` ran).
TRACE_STORE = TraceStore()

_wired_lock = threading.Lock()
_wired = False


def ensure_trace_store() -> TraceStore:
    """Register the store on the event stream once; returns it."""
    global _wired
    with _wired_lock:
        if not _wired:
            _events.add_listener(TRACE_STORE.record_event)
            _wired = True
    return TRACE_STORE


def get_store() -> TraceStore | None:
    """The live store, or None when never wired (no listener overhead)."""
    return TRACE_STORE if _wired else None
