"""Span-based tracing for the dispatch control plane and its workers.

A :class:`Span` carries trace/span/parent ids, status, and attributes,
propagates through ``contextvars`` (so asyncio tasks nest correctly without
threading a handle through every call), and on close fans out to both
sinks:

* the structured event stream (``obs.events``) as a ``span`` event — the
  JSONL file doubles as a flat trace export with consistent ids;
* the metrics registry, as one observation in the
  ``covalent_tpu_span_duration_seconds{span="<name>"}`` histogram — which
  is exactly the per-stage dispatch-overhead distribution the
  Prometheus exposition surfaces.

Usage::

    with span("executor.run", operation_id=op) as root:
        with span("executor.connect"):
            ...
    root.stage_durations   # {"connect": 0.012}

Parent spans accumulate each direct child's duration under the child's
*leaf* name (the part after the last dot): ``stage_durations`` and
``summary()`` are the executor's ``last_timings``.

Workers time their own segments on their own monotonic clock (the
harness is stdlib-only and has no sink of ours) and ship the records
home; :func:`record_remote_span` re-emits them here with the worker's
ids kept, so worker time lands inside the dispatch's own waterfall.

Where ``jax`` is already imported in the process, an open span also
opens a ``jax.profiler.TraceAnnotation`` of the same name, so a profiler
capture's host plane shows the program's spans on the device's clock.
jax is never imported for it: the dispatcher stays off JAX.
"""

from __future__ import annotations

import contextvars
import os
import sys
import time
from typing import Any

from . import events as _events
from .metrics import REGISTRY

__all__ = [
    "Span", "span", "current_span", "SPAN_HISTOGRAM",
    "context_of", "extract_context", "record_span", "record_remote_span",
]

#: Name of the histogram every finished span observes into.
SPAN_HISTOGRAM = "covalent_tpu_span_duration_seconds"

_current: contextvars.ContextVar["Span | None"] = contextvars.ContextVar(
    "covalent_tpu_current_span", default=None
)


def _new_id(nbytes: int) -> str:
    return os.urandom(nbytes).hex()


def _annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` named ``name``, or ``None`` in a
    process that has not imported jax (it is never imported for this).
    With no profiler session open, entering one costs a flag test."""
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    return None if profiler is None else profiler.TraceAnnotation(name)


def current_span() -> "Span | None":
    """The innermost open span in this task/thread context, if any."""
    return _current.get()


def context_of(span: "Span", **extra: Any) -> dict[str, Any]:
    """Wire-format trace context for propagation across a process boundary.

    The dispatcher stamps this dict into the harness task spec and agent
    RPCs so worker-side events join the dispatch trace: ``trace_id`` is
    the trace to join, ``span_id`` the parent for whatever the remote side
    records.  ``extra`` rides along verbatim (e.g. ``attempt=N``, which
    the retry driver preserves so one trace follows an electron across
    gang re-submissions).
    """
    return {"trace_id": span.trace_id, "span_id": span.span_id, **extra}


def extract_context(carrier: Any) -> tuple[str, str] | None:
    """``(trace_id, parent_span_id)`` from a :func:`context_of` dict.

    Never raises: the carrier crossed a process boundary, so anything —
    a non-dict, ids of the wrong type, a partial dict — may arrive.  Any
    malformed carrier yields ``None`` and the local span falls back to a
    fresh root rather than poisoning the dispatch it instruments.
    """
    if not carrier or not isinstance(carrier, dict):
        return None
    try:
        trace_id = carrier.get("trace_id")
        span_id = carrier.get("span_id")
        if (
            not trace_id
            or not span_id
            or not isinstance(trace_id, (str, int))
            or not isinstance(span_id, (str, int))
        ):
            return None
        return str(trace_id), str(span_id)
    except Exception:  # noqa: BLE001 - carriers come off the wire
        return None


class Span:
    """One timed operation with ids, status, and attributes.

    Use as a context manager (sync ``with`` works inside async code — no
    await happens at enter/exit).  Exceptions mark the span ``ERROR`` with
    the exception repr attached, then propagate.
    """

    __slots__ = (
        "name", "trace_id", "span_id", "parent_id", "attributes",
        "status", "start_ts", "duration_s", "stage_durations",
        "_t0", "_token", "_parent", "_emit", "_activate", "_context",
        "_annotation",
    )

    def __init__(
        self,
        name: str,
        attributes: dict[str, Any] | None = None,
        emit: bool = True,
        parent: "Span | None" = None,
        activate: bool = True,
        context: tuple[str, str] | None = None,
    ) -> None:
        """``parent`` overrides contextvar lookup; ``activate=False`` keeps
        the span out of the ambient context (for a long-lived root that is
        never exited, which must not capture it).
        ``context`` — a ``(trace_id, parent_span_id)`` pair from
        :func:`extract_context` — adopts a *remote* parent when no local
        one applies, joining a trace that started in another process."""
        self.name = name
        self.attributes: dict[str, Any] = dict(attributes or {})
        self.status = "OK"
        self.parent_id: str | None = None
        self.trace_id: str | None = None
        self.span_id = _new_id(8)
        self.start_ts: float | None = None
        self.duration_s: float | None = None
        #: leaf-name -> accumulated seconds of *direct* child spans.
        self.stage_durations: dict[str, float] = {}
        self._t0: float | None = None
        self._token = None
        self._parent: Span | None = parent
        self._emit = emit
        self._activate = activate
        self._context = context
        self._annotation = None

    # -- lifecycle ---------------------------------------------------------

    def __enter__(self) -> "Span":
        if self._parent is None:
            self._parent = _current.get()
        parent = self._parent
        if parent is not None:
            self.trace_id = parent.trace_id
            self.parent_id = parent.span_id
        elif self._context is not None:
            self.trace_id, self.parent_id = self._context
        else:
            self.trace_id = _new_id(16)
        self.start_ts = time.time()
        self._t0 = time.perf_counter()
        if self._activate:
            self._token = _current.set(self)
        self._annotation = _annotation(self.name)
        if self._annotation is not None:
            self._annotation.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc is not None:
            self.record_error(exc)
        self.end()
        return False

    def set_attribute(self, key: str, value: Any) -> None:
        self.attributes[key] = value

    def record_error(self, error: BaseException | str) -> None:
        self.status = "ERROR"
        self.attributes["error"] = (
            error if isinstance(error, str) else repr(error)
        )

    @property
    def leaf_name(self) -> str:
        return self.name.rsplit(".", 1)[-1]

    def end(self) -> None:
        if self._t0 is None or self.duration_s is not None:
            return  # never entered, or already ended
        self.duration_s = time.perf_counter() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None
        if self._token is not None:
            try:
                _current.reset(self._token)
            except ValueError:
                # Ended from a different context than it was entered in
                # (e.g. a callback); the var will fall out of scope anyway.
                pass
            self._token = None
        parent = self._parent
        if parent is not None:
            parent.stage_durations[self.leaf_name] = (
                parent.stage_durations.get(self.leaf_name, 0.0)
                + self.duration_s
            )
        REGISTRY.histogram(
            SPAN_HISTOGRAM,
            "Duration of instrumented control-plane spans",
            label_names=("span",),
        ).labels(span=self.name).observe(
            self.duration_s, trace_id=self.trace_id
        )
        if self._emit:
            _events.emit(
                "span",
                name=self.name,
                trace_id=self.trace_id,
                span_id=self.span_id,
                parent_id=self.parent_id,
                start_ts=round(self.start_ts, 6),
                duration_s=round(self.duration_s, 6),
                status=self.status,
                **({"attributes": self.attributes} if self.attributes else {}),
            )

    # -- stage accounting (``TPUExecutor.last_timings``) -------------------

    def total(self) -> float:
        if self.duration_s is not None:
            return self.duration_s
        if self._t0 is None:
            return 0.0
        return time.perf_counter() - self._t0

    def overhead(self, exclude: tuple[str, ...] = ("execute",)) -> float:
        """Dispatch overhead = child stages minus the task's own runtime."""
        return sum(
            v for k, v in self.stage_durations.items() if k not in exclude
        )

    def summary(self) -> dict[str, float]:
        out = dict(self.stage_durations)
        out["total"] = self.total()
        out["overhead"] = self.overhead()
        return out


def record_span(
    name: str,
    *,
    trace_id: str | None = None,
    parent_id: str | None = None,
    span_id: str | None = None,
    start_ts: float | None = None,
    duration_s: float,
    status: str = "OK",
    attributes: dict[str, Any] | None = None,
) -> str:
    """Emit one span retrospectively from explicit timings.

    The waterfall instrumentation measures segments with plain monotonic
    stamps on the request object (a :class:`Span` context manager cannot
    wrap code that spans callbacks and reconnects), and remote spans come
    back off the wire already timed; both land here.  Fans out exactly
    like :meth:`Span.end` — one histogram observation (exemplar-linked to
    the trace) plus one ``span`` event — and returns the span id so
    callers can parent further segments under it.
    """
    if span_id is None:
        span_id = _new_id(8)
    if trace_id is None:
        trace_id = _new_id(16)
    duration_s = max(0.0, float(duration_s))
    REGISTRY.histogram(
        SPAN_HISTOGRAM,
        "Duration of instrumented control-plane spans",
        label_names=("span",),
    ).labels(span=name).observe(duration_s, trace_id=trace_id)
    _events.emit(
        "span",
        name=name,
        trace_id=trace_id,
        span_id=span_id,
        parent_id=parent_id,
        start_ts=round(start_ts if start_ts is not None else time.time(), 6),
        duration_s=round(duration_s, 6),
        status=status,
        **({"attributes": dict(attributes)} if attributes else {}),
    )
    return span_id


def record_remote_span(data: Any) -> None:
    """Re-emit one span a worker recorded, with the worker's ids kept.

    ``data`` is the record the harness's recorder builds (``name``,
    ``trace_id``, ``parent_id``, ``span_id``, ``start_ts``, ``duration_s``,
    ``status``, ``attributes``), off a serving session's side-band, an RPC
    invocation's, or a launch-mode result's trailer.  Keeping the ids is
    what puts worker time inside the request's or electron's own waterfall
    rather than in a disconnected worker-local trace.  Never raises: the
    record crossed a process boundary, and observability is never fatal.
    """
    try:
        attributes = data.get("attributes")
        record_span(
            str(data.get("name") or "worker"),
            trace_id=data.get("trace_id") or None,
            parent_id=data.get("parent_id") or None,
            span_id=data.get("span_id") or None,
            start_ts=data.get("start_ts"),
            duration_s=float(data.get("duration_s") or 0.0),
            status=str(data.get("status") or "OK"),
            attributes=attributes if isinstance(attributes, dict) else None,
        )
    except Exception:  # noqa: BLE001 - records come off the wire
        pass


def span(name: str, **attributes: Any) -> Span:
    """Open a new span as a context manager: ``with span("x", k=v): ...``."""
    return Span(name, attributes)
