"""One supervised serving session: open, stream, reconnect, replay.

The serving tier splits into two concerns that PR 9 originally fused
inside ``ServeHandle``:

* **Supervision** (this module) — owning ONE remote session generation:
  lease a gang, ship the factory by CAS digest, watch the side-band,
  reconnect on channel death with jittered bounded retries, and replay
  in-flight requests with the exactly-once ``idx`` splice.
* **Routing / multiplexing** (``handle.py``, ``replicas.py``) — deciding
  WHICH supervised session a caller's request lands on.  A
  :class:`~.handle.ServeHandle` fronts one supervisor; a
  :class:`~.replicas.ReplicaSet` fronts N of them behind a
  session-aware router — neither re-implements any replay machinery.

A :class:`SessionSupervisor` registers itself in the executor's
``_serve_handles`` book (so ``/status``, ``pool.status()`` and the
profile-target pinning see every live session, replica or not), pins one
fleet capacity slot when opened through a pool, and reaps its gauge
series through ``_drop_live`` on every terminal path.

Because a replayed (or re-routed) stream restarts from token 0 and is
spliced on the request's token high-water mark, any supervisor can pick
up any :class:`ServeRequest` mid-stream: the request object carries the
splice state, not the session.  That is what lets a replica set drain a
dying session's callers onto survivors without duplicating a token.
"""

from __future__ import annotations

import asyncio
import os
import time
import uuid
from typing import Any, AsyncIterator, Callable

from ..agent import HARNESS_BASENAME, AgentClient, AgentError
from ..cache import bytes_digest, cas_path
from ..fleet import journal as journal_mod
from ..fleet.health import HEALTH
from ..obs import events as obs_events
from ..obs import jitstats
from ..obs.trace import Span, context_of, record_remote_span, record_span
from ..resilience import FaultClass, RetryPolicy, classify_error
from ..transport.base import TransportError
from ..utils.log import app_log
from .metrics import (
    SERVE_ADAPTER_ATTACH_SECONDS,
    SERVE_ADAPTER_ATTACHES_TOTAL,
    SERVE_ADAPTER_REQUESTS_TOTAL,
    SERVE_ADAPTER_TOKENS,
    SERVE_ADAPTERS,
    SERVE_HANDOFFS_TOTAL,
    SERVE_MODE_TOKENS,
    SERVE_PREFILL_POSITIONS,
    SERVE_PREFIX_HITS,
    SERVE_PREFIX_MISSES,
    SERVE_QUEUE_DEPTH,
    SERVE_RECONNECTS_TOTAL,
    SERVE_REPLICA_IN_FLIGHT,
    SERVE_REPLICA_REQUESTS_TOTAL,
    SERVE_REQUEST_SECONDS,
    SERVE_REQUESTS_TOTAL,
    SERVE_SESSIONS,
    SERVE_SPEC_ACCEPT_RATE,
    SERVE_TOKENS_PER_S,
    SERVE_TOKENS_TOTAL,
    SERVE_TTFT_SECONDS,
    SERVE_WORKER_SLOTS,
)

#: Mirror of ``models.quant.SERVING_MODES``: the closed decode-mode set
#: the per-mode token gauge is labelled with.  Mirrored rather than
#: imported — the dispatcher-side serving tier deliberately never
#: imports the models package (it would drag jax into processes that
#: only route) — and the reap in :meth:`SessionSupervisor._drop_live`
#: enumerates it, which is only sound because the set is closed.
_SERVING_MODES = ("fp", "int8", "kv_quant", "full_quant")

__all__ = [
    "ServeError",
    "ServeRequest",
    "ServeRequestRejected",
    "SessionSupervisor",
]


def _env_number(name: str, default: float, cast=float):
    value = os.environ.get(name)
    if value is None:
        return default
    try:
        return cast(value)
    except (TypeError, ValueError):
        app_log.warning("ignoring non-numeric %s=%r", name, value)
        return default


class ServeError(RuntimeError):
    """Session-level failure (open refused, stream torn, handle closed)."""


class ServeRequestRejected(ServeError):
    """One request refused by the worker (shed, unknown session, engine).

    Duck-tagged for :func:`~..resilience.classify_error`: an admission
    shed is PERMANENT under the ``serve_admission_shed`` label — the
    bounded queue refused the work *because* the session is overloaded,
    and a gang retry would amplify exactly that.  A lost session
    (``unknown_session`` racing a worker restart) stays transient: the
    handle's reconnect re-opens it.
    """

    def __init__(self, rid: str, code: str, message: str) -> None:
        super().__init__(f"request {rid} rejected ({code}): {message}")
        self.rid = rid
        self.code = code
        if code == "serve_admission_shed":
            self.fault_label = "serve_admission_shed"
            self.fault_transient = False
        elif code == "unknown_session":
            self.fault_label = "serve_session_lost"
            self.fault_transient = True
        else:
            self.fault_label = f"serve_{code or 'rejected'}"
            self.fault_transient = False


class ServeRequest:
    """One in-flight request's stream state (created by the front-end).

    ``stream()`` yields token chunks as they arrive; ``result()`` awaits
    the final token list.  A request that hit its deadline completes
    normally with the partial stream and ``error == "deadline_exceeded"``
    (the tokens generated before the reclaim are real); a *rejected*
    request raises :class:`ServeRequestRejected` from both surfaces.

    The request carries its own splice state (the ``tokens`` high-water
    mark), so a replayed — or re-routed — stream can be picked up by a
    different supervisor with exactly-once delivery intact.
    """

    def __init__(
        self,
        rid: str,
        prompt: list[int],
        params: dict | None,
        deadline_s: float,
        tenant: str,
    ) -> None:
        self.rid = rid
        self.prompt = prompt
        self.params = dict(params or {})
        self.deadline_s = float(deadline_s)
        self.tenant = tenant
        #: the caller's multi-turn session key (set by a replica set);
        #: rides the request so a drain-on-death re-route keeps the pin.
        self.sticky = ""
        #: (bundle bytes, sha256) attached by a disaggregated front: the
        #: decode replica admits from this KV instead of prefilling.  It
        #: rides the request so a replay — or a re-route onto another
        #: replica — keeps the prefill-tier work.
        self.kv: tuple[bytes, str] | None = None
        #: prefix-affinity routing key (digest of the prompt's reusable
        #: prefix): the router steers requests sharing it to the replica
        #: whose engine-side prefix tree is already warm for it.
        self.prefix_key = ""
        self.tokens: list[int] = []
        #: absolute stream offset this request resumed from (crash
        #: recovery): the prefix ``[0, resumed_from)`` was delivered by a
        #: PRIOR dispatcher incarnation and is not re-collected here, so
        #: every splice compares worker ``idx`` against
        #: ``resumed_from + len(tokens)``, not ``len(tokens)`` alone.
        self.resumed_from = 0
        self.error: str = ""
        #: sid of the supervisor whose stream fed this request's FIRST
        #: fresh tokens.  With a hedge in flight two supervisors hold the
        #: same request object; whichever feeds first is the winner and
        #: the other arm is cancelled.  Duplicate chunks from the loser
        #: splice to nothing, so the stream stays byte-equal regardless.
        self.served_by = ""
        #: True once a hedge copy of this request was issued (budget
        #: accounting + at-most-one-hedge-per-request).
        self.hedged = False
        #: sid -> monotonic submit time for every supervisor currently
        #: holding this request (a hedge puts TWO arms in flight).  A
        #: terminal (reject, error, done) on one arm consults this to
        #: decide whether another arm still owns the stream — and a
        #: hedge winner's health feed reads its OWN dispatch time here,
        #: not the original submit, so the winner is not charged the
        #: primary's stall.
        self.arms: dict[str, float] = {}
        self.t_submit = time.monotonic()
        self.t_first: float | None = None
        self.t_done: float | None = None
        #: lifecycle checkpoints (monotonic) between submit and first
        #: token: each adjacent pair becomes one tiling waterfall segment
        #: under :attr:`span` at finalize, so the trace store can show
        #: where a request's TTFT went.  Stamped once — a replay or a
        #: re-route re-sends the SAME request object, and re-stamping
        #: would erase the latency the retry actually cost.
        self.t_prefill_done: float | None = None
        self.t_dispatched: float | None = None
        self.t_sent: float | None = None
        #: wall time the engine spent in fused speculative verify steps
        #: on this request's behalf (harness-attributed share, rides the
        #: final token chunk).  Not a checkpoint stamp: it becomes a
        #: ``spec_verify`` waterfall tile carved out of the decode-stream
        #: window at finalize.
        self.spec_verify_s: float | None = None
        #: root span of this request's trace.  Entered at construction
        #: (``activate=False``: feeding happens in callbacks, the ambient
        #: context must not capture it) and closed LAST by
        #: :meth:`_finalize_trace` — the root arriving is what tells the
        #: tail-sampling store the trace is complete.  Because the span
        #: lives on the request, not the session, one trace follows the
        #: stream across reconnect replays, re-routes, and warm handoffs.
        self.span = Span(
            "serve.request",
            {"rid": rid, "tenant": tenant} if tenant else {"rid": rid},
            activate=False,
        ).__enter__()
        self._trace_done = False
        #: set the moment the first fresh tokens (or any terminal) land —
        #: the hedge watcher's TTFT deadline races this event.
        self.first_token = asyncio.Event()
        self._chunks: asyncio.Queue = asyncio.Queue()
        self._done: asyncio.Future = asyncio.get_event_loop().create_future()
        # Unawaited failures must not warn at GC: a caller may only ever
        # consume stream(), or fire-and-forget a best-effort request.
        self._done.add_done_callback(
            lambda f: None if f.cancelled() else f.exception()
        )

    @property
    def done(self) -> bool:
        return self._done.done()

    @property
    def ttft_s(self) -> float | None:
        """Submit -> first streamed token (None until one arrived)."""
        if self.t_first is None:
            return None
        return self.t_first - self.t_submit

    @property
    def latency_s(self) -> float | None:
        if self.t_done is None:
            return None
        return self.t_done - self.t_submit

    async def result(self, timeout: float | None = None) -> list[int]:
        """The full token stream (prompt excluded); raises on rejection."""
        return await asyncio.wait_for(asyncio.shield(self._done), timeout)

    async def stream(self) -> AsyncIterator[list[int]]:
        """Yield token chunks in arrival order until the stream closes."""
        while True:
            item = await self._chunks.get()
            if item is None:
                return
            if isinstance(item, BaseException):
                raise item
            yield item

    # -- supervisor-side feeding (event-loop context only) -----------------

    def _feed(self, tokens: list[int], done: bool, error: str = "") -> None:
        if self._done.done():
            return
        if tokens:
            if self.t_first is None:
                self.t_first = time.monotonic()
            self.tokens.extend(tokens)
            self._chunks.put_nowait(list(tokens))
            self.first_token.set()
        if done:
            self.t_done = time.monotonic()
            self.error = error
            self._chunks.put_nowait(None)
            self._done.set_result(list(self.tokens))
            self.first_token.set()
            self._finalize_trace()

    def _fail(self, err: BaseException) -> None:
        if self._done.done():
            return
        self.t_done = time.monotonic()
        self._chunks.put_nowait(err)
        self._done.set_exception(err)
        self.first_token.set()
        self.span.record_error(err)
        self._finalize_trace()

    def _finalize_trace(self) -> None:
        """Close this request's trace: turn the monotonic checkpoints
        into tiling segment spans, then end the root.

        Each adjacent checkpoint pair becomes one child span tagged with
        a ``segment`` attribute — the store's waterfall view sums those
        into the per-request latency attribution, and because they tile
        (every segment starts where the previous ended) the sum matches
        the request's end-to-end latency.  Checkpoints a given request
        never hit (no prefill tier, rejected before dispatch) simply
        drop out; the next segment absorbs the span of wall time.  The
        root closes LAST so a trace never finalizes in the store with
        its segments still in flight.
        """
        if self._trace_done:
            return
        self._trace_done = True
        span = self.span
        cursor = self.t_submit
        # The spec_verify tile is synthesized, not stamped: the engine's
        # fused verify passes interleave with streaming, so the harness
        # ships an attributed duration and the tile carves that much out
        # of the FRONT of the decode window.  Clamped to t_done so the
        # tiling sum still equals end-to-end latency exactly.
        t_spec: float | None = None
        if (
            self.spec_verify_s is not None
            and self.t_first is not None
            and self.t_done is not None
        ):
            t_spec = min(self.t_first + self.spec_verify_s, self.t_done)
        tiles: list[tuple[str, float, float]] = []
        for name, stamp in (
            ("prefill", self.t_prefill_done),
            ("route", self.t_dispatched),
            ("dispatch", self.t_sent),
            ("ttft_wait", self.t_first),
            ("spec_verify", t_spec),
            ("decode_stream", self.t_done),
            ("stream_flush", time.monotonic()),
        ):
            if stamp is None:
                continue
            tiles.append((name, cursor, stamp))
            cursor = stamp
        for name, t0, t1 in tiles:
            if t1 <= t0:
                continue
            record_span(
                f"serve.{name}",
                trace_id=span.trace_id,
                parent_id=span.span_id,
                start_ts=span.start_ts + (t0 - self.t_submit),
                duration_s=t1 - t0,
                attributes={"segment": name, "rid": self.rid},
            )
        span.set_attribute("tokens", len(self.tokens))
        if self.ttft_s is not None:
            span.set_attribute("ttft_s", round(self.ttft_s, 6))
        if self.error:
            span.record_error(self.error)
        span.end()


class SessionSupervisor:
    """One resident serving session, supervised for its whole life.

    Owns the session's remote generations (lease, open, watch, reconnect,
    replay, drain-close), the in-flight requests ASSIGNED to it, and the
    per-session accounting (metrics series, fleet capacity pin, the
    executor ``_serve_handles`` registration).  It does NOT decide which
    requests it gets — that is the front-end's job (a handle's trivial
    routing, or a replica set's router).

    ``on_change(supervisor)`` fires on every state transition and request
    completion (a router's pump signal); ``on_failed(supervisor, error)``
    fires when the session dies past its retry budget — a front-end that
    returns ``True`` from it has taken ownership of the in-flight
    requests (via :meth:`detach_requests`) and re-routes them itself;
    otherwise the supervisor fails them with the cause.

    All methods must run on the executor's event loop.
    """

    def __init__(
        self,
        executor: Any,
        *,
        sid: str = "",
        queue_max: int | None = None,
        default_deadline_s: float | None = None,
        stats_interval_s: float | None = None,
        open_timeout_s: float | None = None,
        retries: int | None = None,
        pool: Any = None,
        replica_of: tuple[str, str] | None = None,
        on_change: Callable[["SessionSupervisor"], None] | None = None,
        on_failed: Callable[
            ["SessionSupervisor", BaseException], bool
        ] | None = None,
    ) -> None:
        self.executor = executor
        self.sid = sid or f"serve-{uuid.uuid4().hex[:10]}"
        self.queue_max = int(
            queue_max
            if queue_max is not None
            else _env_number("COVALENT_TPU_SERVE_QUEUE_MAX", 64, int)
        )
        self.default_deadline_s = float(
            default_deadline_s
            if default_deadline_s is not None
            else _env_number("COVALENT_TPU_SERVE_DEADLINE_S", 0.0)
        )
        self.stats_interval_s = float(
            stats_interval_s
            if stats_interval_s is not None
            else _env_number("COVALENT_TPU_SERVE_STATS_INTERVAL_S", 1.0)
        )
        self.open_timeout_s = float(
            open_timeout_s
            if open_timeout_s is not None
            else _env_number("COVALENT_TPU_SERVE_OPEN_TIMEOUT_S", 120.0)
        )
        self.retries = int(
            retries
            if retries is not None
            else _env_number("COVALENT_TPU_SERVE_RETRIES", 2, int)
        )
        self._pool = pool
        #: (set name, replica id) when owned by a ReplicaSet — keys the
        #: per-replica metric series; None for a standalone handle.
        self.replica_of = replica_of
        self._on_change = on_change
        self._on_failed = on_failed
        self.slots = 0
        self.generation = 0
        self.served = 0
        self.reconnects = 0
        #: warm handoffs completed (drain-and-reopen before gang death).
        self.handoffs = 0
        self._gen_counter = 0
        self._in_handoff = False
        self._handoff_task: asyncio.Task | None = None
        #: a worker preemption notice (serve.preempt on the side-band)
        #: auto-triggers a warm handoff; COVALENT_TPU_SERVE_HANDOFF=0
        #: disables and leaves recovery to the reconnect path.
        self._auto_handoff = str(
            os.environ.get("COVALENT_TPU_SERVE_HANDOFF", "1")
        ).strip().lower() not in ("0", "off", "false", "no")
        self.opened_at = 0.0
        self.stats: dict[str, Any] = {}
        #: compile totals already added (``serve.stats`` carries the
        #: runtime's running totals).
        self._jit_seen: dict = {}
        self.address = ""
        self._payload: bytes | None = None
        self._digest = ""
        self._local_payload = ""
        self._client: AgentClient | None = None
        self._conns: list = []
        self._sid_g = ""
        self._requests: dict[str, ServeRequest] = {}
        #: name -> adapter record ({digest, content, path, ...}) for every
        #: adapter attached to THIS session, in attach order — the replay
        #: set a reconnect/handoff re-splices into the fresh generation.
        self._adapters: dict[str, dict] = {}
        #: (session, adapter) metric series this supervisor created; the
        #: adapter label set is OPEN (operators name adapters), so the
        #: stale-series reap in :meth:`_drop_live` replays exactly this
        #: set instead of enumerating.
        self._adapter_series: set[str] = set()
        self._closed = False
        self._failed: BaseException | None = None
        self._ready = asyncio.Event()
        self._supervisor: asyncio.Task | None = None
        self._counted_live = False
        #: fire-and-forget wire tasks (hedge loser cancels) held here so
        #: they are not collected mid-await.
        self._bg_tasks: set = set()

    # -- identity / views ---------------------------------------------------

    @property
    def _health_group(self) -> str:
        """Peer group for differential health scoring: the replica set
        name when owned by one (peers = sibling replicas), else ''."""
        return self.replica_of[0] if self.replica_of is not None else ""

    @property
    def state(self) -> str:
        if self._failed is not None:
            return "failed"
        if self._closed:
            return "closed"
        if not self._ready.is_set():
            return "reconnecting"
        return "open"

    @property
    def in_flight(self) -> int:
        return len(self._requests)

    @property
    def routable(self) -> bool:
        """Whether a router may assign NEW requests here right now."""
        return self.state == "open"

    @property
    def alive(self) -> bool:
        """Open or recovering — a sticky pin to this session still holds."""
        return self.state in ("open", "reconnecting")

    def status(self) -> dict[str, Any]:
        """This session's contribution to ``/status`` / ``pool.status()``."""
        view: dict[str, Any] = {
            "state": self.state,
            "address": self.address,
            "slots": self.slots,
            "generation": self.generation,
            "served": self.served,
            "in_flight": self.in_flight,
            "reconnects": self.reconnects,
            "handoffs": self.handoffs,
            "age_s": (
                round(time.time() - self.opened_at, 3) if self.opened_at else 0
            ),
        }
        if self.replica_of is not None:
            view["replica_set"] = self.replica_of[0]
            view["replica"] = self.replica_of[1]
        if self._adapters:
            view["adapters"] = self.adapters
        view["health_score"] = HEALTH.score(self.sid)
        view["health_state"] = HEALTH.state(self.sid)
        for field in ("busy", "queued", "tokens_per_s", "tokens_total"):
            if field in self.stats:
                view[field] = self.stats[field]
        return view

    def _changed(self) -> None:
        if self._on_change is not None:
            try:
                self._on_change(self)
            except Exception:  # noqa: BLE001 - router hooks never fatal
                app_log.exception("serve on_change hook failed")

    # -- open ---------------------------------------------------------------

    async def open(
        self, payload: bytes, digest: str = ""
    ) -> "SessionSupervisor":
        """First open: stage the factory payload, lease a gang, supervise.

        ``payload`` is the cloudpickled factory; ``digest`` (its sha256)
        may be precomputed by a replica set staging the same bytes N
        times.
        """
        self._payload = payload
        self._digest = digest or bytes_digest(payload)
        self._local_payload = os.path.join(
            self.executor.cache_dir, f"serve_{self._digest}.pkl"
        )
        await asyncio.to_thread(
            self._write_payload, self._local_payload, self._payload
        )
        await self._open_generation()
        self.opened_at = time.time()
        handles = getattr(self.executor, "_serve_handles", None)
        if handles is not None:
            handles[self.sid] = self
        if self._pool is not None:
            # A session IS long-lived load: pin one capacity slot so the
            # fleet scheduler bin-packs electrons around it, not into it.
            self._pool.place()
        SERVE_SESSIONS.inc()
        self._counted_live = True
        if self.replica_of is not None:
            SERVE_REPLICA_IN_FLIGHT.labels(
                set=self.replica_of[0], replica=self.replica_of[1]
            ).set(0)
        self._supervisor = asyncio.ensure_future(self._supervise())
        self._ready.set()
        obs_events.emit(
            "serve.session_opened",
            sid=self.sid,
            address=self.address,
            slots=self.slots,
        )
        return self

    async def adopt(
        self,
        *,
        client: AgentClient,
        conns: list,
        address: str,
        sid_g: str,
        slots: int = 1,
        digest: str = "",
        payload_path: str = "",
    ) -> "SessionSupervisor":
        """Bind to a SURVIVING remote session instead of opening one.

        The crash-recovery path: the worker held this session through
        the dispatcher's death (orphan mode) and a successor dispatcher
        re-attached its channel; the supervisor adopts the existing
        ``sid_g`` — no lease, no staging, no ``serve_open`` — and the
        usual supervision (reconnect, replay, stats, close) takes over
        from there.  Journaled in-flight streams are re-attached one by
        one via :meth:`resume_stream`.
        """
        self._digest = digest
        self._local_payload = payload_path
        self._client = client
        self._conns = list(conns)
        self._sid_g = sid_g
        self.address = address
        self.slots = int(slots or 1)
        self.generation = 1
        # Future reconnects mint fresh generation sids AFTER the adopted
        # one: "serve-x.g2" resumes counting at 3, not at a collision.
        tail = sid_g.rsplit(".g", 1)
        try:
            self._gen_counter = int(tail[1]) + 1 if len(tail) == 2 else 1
        except ValueError:
            self._gen_counter = 1
        client.watch_serve(sid_g, self._sink)
        self.opened_at = time.time()
        handles = getattr(self.executor, "_serve_handles", None)
        if handles is not None:
            handles[self.sid] = self
        if self._pool is not None:
            self._pool.place()
        SERVE_SESSIONS.inc()
        self._counted_live = True
        if self.replica_of is not None:
            SERVE_REPLICA_IN_FLIGHT.labels(
                set=self.replica_of[0], replica=self.replica_of[1]
            ).set(0)
        self._journal_binding()
        # A re-adopted session starts at a NEUTRAL health score: the
        # journal deliberately does not persist pre-crash scores, and a
        # recovered fleet must never inherit a stale quarantine from its
        # predecessor's (possibly fault-storm-polluted) view.
        HEALTH.neutral(self.sid, group=self._health_group)
        self._supervisor = asyncio.ensure_future(self._supervise())
        self._ready.set()
        obs_events.emit(
            "serve.session_adopted",
            sid=self.sid,
            address=self.address,
            sid_g=sid_g,
            slots=self.slots,
        )
        return self

    async def resume_stream(self, request: ServeRequest) -> str:
        """Re-attach one journaled in-flight stream to this session.

        ``request.resumed_from`` holds the journal's token high-water
        mark; the worker re-emits its history from that offset (the
        splice in :meth:`_on_token` guards the overlap) and live chunks
        follow.  Returns the worker's resume state — a stream the worker
        never saw (``unknown``: it died in the dead pipe between journal
        and wire) is re-sent in full from the journaled prompt.
        """
        if self._client is None:
            raise ServeError(f"session {self.sid} has no live runtime")
        request.span.set_attribute("sid", self.sid)
        # Register BEFORE the wire write: re-emitted history races the
        # resume ack on the side-band.
        self._requests[request.rid] = request
        self._publish_in_flight()
        try:
            ack = await self._client.serve_resume(
                self._sid_g, request.rid, request.resumed_from
            )
        except BaseException:
            self._requests.pop(request.rid, None)
            self._publish_in_flight()
            raise
        state = str(ack.get("state") or "")
        if state == "refused":
            self._finish(request.rid, "error")
            request._fail(ServeError(
                f"resume of {request.rid} refused: worker fenced this "
                "dispatcher as stale"
            ))
        elif state == "unknown":
            # The prior dispatcher journaled the intent but died before
            # (or during) the wire write: send it as a fresh stream.
            request.resumed_from = 0
            await self._send_request(request)
        return state

    @staticmethod
    def _write_payload(path: str, payload: bytes) -> None:
        if os.path.exists(path):
            return
        tmp = f"{path}.tmp.{os.getpid()}.{os.urandom(4).hex()}"
        with open(tmp, "wb") as f:
            f.write(payload)
        os.replace(tmp, path)

    async def _open_generation(self) -> None:
        """Open one remote session generation on a freshly leased gang.

        Failures discard whatever channels the attempt dialed (the
        ``lease_gang(dialed=)`` contract): a pre-flight or ``serve_open``
        refusal would otherwise leave the just-proved-broken transports
        pooled, and every reconnect retry would silently reuse them.
        """
        self._adopt(await self._dial_generation())

    async def _dial_generation(self) -> dict:
        """Dial + open one fresh session generation WITHOUT touching the
        current binding; returns it for :meth:`_adopt`.

        The split is what makes the warm handoff possible: the old
        generation keeps streaming while the replacement leases, stages,
        and opens — the swap at adopt time is a few field writes.
        """
        dialed: list = []
        try:
            return await self._dial_generation_on(dialed)
        except BaseException:
            if dialed:
                try:
                    await self.executor._discard_workers(dialed)
                except Exception:  # noqa: BLE001 - teardown is best-effort
                    pass
            raise

    def _adopt(self, binding: dict) -> None:
        self._client = binding["client"]
        self._conns = binding["conns"]
        self._sid_g = binding["sid_g"]
        self.address = binding["address"]
        self.slots = binding["slots"]
        self.generation += 1
        self._journal_binding()

    def _journal_binding(self) -> None:
        """Journal this session's current remote binding — everything a
        successor dispatcher needs to find (or re-open) the session."""
        journal_mod.record(
            "session", sid=self.sid, sid_g=self._sid_g,
            address=self.address, digest=self._digest,
            payload=self._local_payload, slots=self.slots,
            queue_max=self.queue_max,
            default_deadline_s=self.default_deadline_s,
            stats_interval_s=self.stats_interval_s,
            replica_of=list(self.replica_of) if self.replica_of else None,
            sync=True,
        )

    async def _dial_generation_on(self, dialed: list) -> dict:
        executor = self.executor
        lease = await executor.lease_gang(dialed=dialed)
        conns, addresses = lease.conns, lease.addresses
        if len(conns) != 1:
            raise ServeError(
                f"serving sessions target single-worker gangs, got "
                f"{len(conns)} workers (shard inside the engine instead)"
            )
        conn, address = conns[0], addresses[0]
        client = executor._agents.get(conn.address)
        if client is None or not client.alive:
            raise AgentError(
                f"no resident agent runtime on {address} "
                "(serving needs use_agent enabled)"
            )
        key = executor._pool_key(address)
        remote = cas_path(executor.remote_cache, self._digest, ".pkl")
        codec = executor._codec_for(key, conn)
        await executor._cas.ensure_probed(
            key, conn, [(self._digest, remote)]
        )
        await executor._cas.ensure(
            key, conn, self._digest, self._local_payload, remote,
            codec=codec, python_path=executor.python_path,
        )
        runner = None
        if client.mode != "pool":
            # The native C++ agent only switches lines: it forks this
            # runner argv to host the session (stdin pipe held open).
            from .. import harness as harness_module

            remote_harness = f"{executor.remote_cache}/{HARNESS_BASENAME}"
            await conn.put(harness_module.__file__, remote_harness)
            runner = [
                executor.python_path, remote_harness, "--serve-child",
            ]
        sid_g = f"{self.sid}.g{self._gen_counter}"
        self._gen_counter += 1
        spec: dict[str, Any] = {"operation_id": sid_g}
        if executor.task_env:
            spec["env"] = dict(executor.task_env)
        client.watch_serve(sid_g, self._sink)
        try:
            opened = await client.serve_open(
                sid_g,
                self._digest,
                remote,
                options={
                    "queue_max": self.queue_max,
                    "default_deadline_s": self.default_deadline_s,
                    "stats_interval_s": self.stats_interval_s,
                },
                spec=spec,
                runner=runner,
                timeout=self.open_timeout_s,
            )
        except BaseException:
            client.unwatch_serve(sid_g)
            raise
        return {
            "client": client,
            "conns": list(conns),
            "sid_g": sid_g,
            "address": address,
            "slots": int(opened.get("slots") or 1),
        }

    # -- requests -----------------------------------------------------------

    async def submit(
        self,
        request: ServeRequest,
        *,
        fail_on_error: bool = True,
        wait_ready: bool = True,
    ) -> ServeRequest:
        """Assign one request to this session and write its wire line.

        Fire-and-stream: tokens arrive on the side-band.  Raises when
        the write cannot be made (waiting out an in-progress reconnect
        first by default); ``wait_ready=False`` refuses a non-routable
        session IMMEDIATELY instead — a router must not head-of-line
        block a whole assignment batch behind one replica's reconnect
        when survivors are idle.  ``fail_on_error=False`` leaves the
        request itself unfailed so that router can re-route it instead
        of surfacing the error to the caller.
        """
        try:
            if wait_ready:
                await self._await_ready()
            elif not self.routable:
                raise ServeError(
                    f"session {self.sid} is not routable ({self.state})"
                )
            if request.t_dispatched is None:
                request.t_dispatched = time.monotonic()
            request.span.set_attribute("sid", self.sid)
            self._requests[request.rid] = request
            request.arms[self.sid] = time.monotonic()
            self._publish_in_flight()
            # Write-ahead: the intent is durable BEFORE the wire write,
            # so a dispatcher crash between the two replays the request
            # rather than losing it.
            journal_mod.record(
                "stream", sid=self.sid, rid=request.rid,
                prompt=list(request.prompt), params=request.params,
                deadline_s=request.deadline_s, tenant=request.tenant,
                resumed_from=request.resumed_from,
            )
            try:
                await self._send_request(request)
            except BaseException:
                self._requests.pop(request.rid, None)
                request.arms.pop(self.sid, None)
                self._publish_in_flight()
                raise
        except BaseException as err:
            if fail_on_error:
                SERVE_REQUESTS_TOTAL.labels(outcome="error").inc()
                request._fail(
                    err
                    if isinstance(err, ServeError)
                    else ServeError(f"request submit failed: {err!r}")
                )
            raise
        if self.replica_of is not None:
            SERVE_REPLICA_REQUESTS_TOTAL.labels(
                set=self.replica_of[0], replica=self.replica_of[1]
            ).inc()
        return request

    def detach_requests(self) -> list[ServeRequest]:
        """Hand every in-flight request back WITHOUT failing or counting
        it — the drain-on-death path: a replica set re-routes these onto
        surviving sessions, and the requests' own token high-water marks
        keep the splice exactly-once across the move."""
        detached = list(self._requests.values())
        self._requests.clear()
        for request in detached:
            request.arms.pop(self.sid, None)
        self._publish_in_flight()
        return detached

    async def _send_request(self, request: ServeRequest) -> None:
        assert self._client is not None
        t_send = time.monotonic()
        kv_bytes: bytes | None = None
        kv_digest = ""
        kv_path = ""
        if request.kv is not None:
            kv_bytes, kv_digest = request.kv
            if not self._client.frames_active:
                # Cross-pool road: a JSONL channel would pay ~33% base64
                # inflation per send (and per replay), so the bundle
                # ships ONCE into the worker's remote CAS — digest-named,
                # single-flighted, deduped across identical prompts —
                # and the request references it by path.  Any staging
                # failure just drops the KV: the worker's full-prefill
                # fallback owns correctness.
                try:
                    kv_path = await self._stage_kv(kv_bytes, kv_digest)
                    kv_bytes = None
                except Exception as err:  # noqa: BLE001 - degrade
                    app_log.debug(
                        "KV staging for %s failed (%s); degrading to "
                        "full prefill", request.rid, err,
                    )
                    kv_bytes, kv_digest = None, ""
        await self._client.serve_request(
            self._sid_g,
            request.rid,
            request.prompt,
            params=request.params,
            deadline_s=request.deadline_s,
            tenant=request.tenant,
            kv_bytes=kv_bytes,
            kv_digest=kv_digest,
            kv_path=kv_path,
            trace=context_of(request.span, rid=request.rid),
        )
        now = time.monotonic()
        if request.kv is not None:
            # The KV data plane is its own waterfall row: shipping a
            # multi-megabyte bundle (CAS stage or inline frame body) is
            # exactly the cost disaggregation trades for prefill reuse,
            # and it must be attributable per request.
            record_span(
                "serve.kv_ship",
                trace_id=request.span.trace_id,
                parent_id=request.span.span_id,
                start_ts=request.span.start_ts + (t_send - request.t_submit),
                duration_s=now - t_send,
                attributes={
                    "rid": request.rid,
                    "kv_bytes": len(request.kv[0]),
                    "staged": bool(kv_path),
                },
            )
        if request.t_sent is None:
            request.t_sent = now

    async def _stage_kv(self, data: bytes, digest: str) -> str:
        """Ship one KV bundle into this session's worker CAS; returns the
        remote path.  Content-addressed: a repeated prompt's identical
        bundle is a present-set hit, zero wire bytes."""
        executor = self.executor
        local = os.path.join(
            executor.cache_dir, "cas", f"{digest}.kv"
        )
        if not os.path.exists(local):
            os.makedirs(os.path.dirname(local), exist_ok=True)
            await asyncio.to_thread(self._write_payload, local, data)
        conn = self._conns[0]
        key = executor._pool_key(self.address)
        remote = cas_path(executor.remote_cache, digest, ".kv")
        await executor._cas.ensure(
            key, conn, digest, local, remote,
            codec=executor._codec_for(key, conn),
            python_path=executor.python_path,
        )
        return remote

    async def prefill_kv(
        self,
        prompt,
        params: dict | None = None,
        rid: str = "",
        timeout_s: float = 60.0,
        trace: dict | None = None,
    ) -> dict:
        """Run a prefill-only pass on this session's resident engine and
        return the ``serve_kv`` event (bundle under ``data_bytes``,
        worker-announced sha256 under ``digest``).

        The disaggregated front calls this on a prefill-tier replica;
        the caller owns digest verification of the received bytes and
        the degrade-to-full-prefill decision on any failure.
        """
        await self._await_ready()
        client = self._client
        if client is None:
            raise ServeError(f"session {self.sid} has no live runtime")
        rid = rid or f"kv-{uuid.uuid4().hex[:8]}"
        return await client.serve_prefill(
            self._sid_g, rid, [int(t) for t in prompt],
            params=params, timeout=timeout_s, trace=trace,
        )

    # -- multi-adapter registry (live attach / detach / replay) --------------

    def _adapter_registry(self):
        """The executor-scoped adapter book (built through the
        executor's accessor when it has one, so every session on one
        executor shares one registry; stub executors in tests get a
        lazily attached instance)."""
        accessor = getattr(self.executor, "adapter_registry", None)
        if callable(accessor):
            return accessor()
        registry = getattr(self.executor, "_adapter_registry", None)
        if registry is None:
            from .registry import AdapterRegistry

            registry = AdapterRegistry(self.executor.cache_dir)
            self.executor._adapter_registry = registry
        return registry

    @property
    def adapters(self) -> dict[str, str]:
        """name -> content digest of every adapter attached here."""
        return {
            name: str(record.get("content") or "")
            for name, record in self._adapters.items()
        }

    async def attach_adapter(
        self,
        name: str,
        payload: Any = None,
        *,
        path: str = "",
        digest: str = "",
        rank: int | None = None,
        alpha: float = 16.0,
        timeout_s: float | None = None,
    ) -> dict:
        """Splice a named LoRA adapter into this RUNNING session.

        Three sources, first match wins: ``payload`` (bundle bytes, a
        bundle dict, or an ordered leaf list — packed and registered
        here), ``path`` (a packed bundle file, e.g. a journaled CAS
        path; ``digest`` cross-checks it when given), or the executor's
        adapter registry by ``name``.  The bundle ships into the
        worker's CAS sha256-verified, the engine splices it in between
        decode waves (a re-attach of an existing name is a hot swap:
        in-flight requests finish on the old generation), and the
        attachment is journaled sync so a successor dispatcher
        re-attaches it after a crash.  Returns the worker's ack
        (content ``digest``, ``attach_s``).
        """
        await self._await_ready()
        client = self._client
        if client is None:
            raise ServeError(f"session {self.sid} has no live runtime")
        t0 = time.monotonic()
        timeout = float(
            timeout_s
            if timeout_s is not None
            else _env_number("COVALENT_TPU_SERVE_ATTACH_TIMEOUT_S", 60.0)
        )
        registry = self._adapter_registry()
        if payload is not None:
            record = await asyncio.to_thread(
                registry.put, name, payload, rank, alpha
            )
        elif path:
            data = await asyncio.to_thread(self._read_payload, path)
            record = await asyncio.to_thread(registry.put, name, data)
            if digest and record["digest"] != digest:
                SERVE_ADAPTER_ATTACHES_TOTAL.labels(
                    op="attach", outcome="digest_mismatch"
                ).inc()
                raise ServeError(
                    f"adapter {name!r} bundle at {path} hashes to "
                    f"{record['digest'][:12]}, journal says {digest[:12]} "
                    "(torn or tampered artifact)"
                )
        else:
            record = registry.get(name)
            if record is None:
                raise ServeError(
                    f"no adapter {name!r} in the registry (register it, "
                    "or pass payload=/path=)"
                )
        try:
            remote = await self._stage_adapter(record)
            ack = await client.serve_attach(
                self._sid_g, name, record["digest"], remote,
                timeout=timeout,
            )
        except BaseException as err:
            SERVE_ADAPTER_ATTACHES_TOTAL.labels(
                op="attach", outcome="error"
            ).inc()
            obs_events.emit(
                "serve.adapter_attach_failed",
                sid=self.sid, adapter=str(name), error=repr(err),
            )
            raise self._adapter_refusal(err, "attach", str(name))
        elapsed = time.monotonic() - t0
        record = dict(record)
        record["content"] = str(
            ack.get("digest") or record.get("content") or ""
        )
        self._adapters[str(name)] = record
        SERVE_ADAPTER_ATTACHES_TOTAL.labels(op="attach", outcome="ok").inc()
        SERVE_ADAPTER_ATTACH_SECONDS.observe(elapsed)
        SERVE_ADAPTERS.labels(session=self.sid).set(
            float(len(self._adapters))
        )
        journal_mod.record(
            "session_adapter", sid=self.sid, adapter=str(name),
            digest=record["digest"], path=record["path"],
            content=record["content"], sync=True,
        )
        obs_events.emit(
            "serve.adapter_attached",
            sid=self.sid, adapter=str(name),
            digest=record["content"], attach_s=round(elapsed, 4),
        )
        self._changed()
        return ack

    async def detach_adapter(
        self, name: str, timeout_s: float = 30.0
    ) -> dict:
        """Remove a named adapter from the running session; its decode
        slot frees once requests pinned to it drain.  Journaled sync so
        recovery does not resurrect the detached name."""
        await self._await_ready()
        client = self._client
        if client is None:
            raise ServeError(f"session {self.sid} has no live runtime")
        try:
            ack = await client.serve_detach(
                self._sid_g, name, timeout=timeout_s
            )
        except BaseException as err:
            SERVE_ADAPTER_ATTACHES_TOTAL.labels(
                op="detach", outcome="error"
            ).inc()
            raise self._adapter_refusal(err, "detach", str(name))
        self._adapters.pop(str(name), None)
        SERVE_ADAPTER_ATTACHES_TOTAL.labels(op="detach", outcome="ok").inc()
        SERVE_ADAPTERS.labels(session=self.sid).set(
            float(len(self._adapters))
        )
        journal_mod.record(
            "session_adapter", sid=self.sid, adapter=str(name),
            detached=True, sync=True,
        )
        obs_events.emit(
            "serve.adapter_detached", sid=self.sid, adapter=str(name),
        )
        self._changed()
        return ack

    def _adapter_refusal(
        self, err: BaseException, op: str, name: str
    ) -> BaseException:
        """A classified worker refusal (it carries a ``fault_label``)
        becomes a :class:`ServeError` with the SAME duck tags, so
        callers catch the serving tier's exception while
        ``classify_error`` still sees the worker's permanence verdict.
        Channel faults and cancellations pass through untouched — the
        reconnect machinery owns those."""
        label = str(getattr(err, "fault_label", "") or "")
        if not label:
            return err
        wrapped = ServeError(
            f"{op} of adapter {name!r} on {self.sid} refused: {err}"
        )
        wrapped.fault_label = label
        wrapped.fault_transient = bool(
            getattr(err, "fault_transient", True)
        )
        wrapped.__cause__ = err
        return wrapped

    def note_adapter(
        self, name: str, *, digest: str, path: str, content: str = ""
    ) -> None:
        """Record an adapter that is ALREADY resident in the remote
        engine (crash recovery: the worker held it through the
        dispatcher's death) without re-shipping anything."""
        self._adapters[str(name)] = {
            "name": str(name), "digest": str(digest),
            "path": str(path), "content": str(content),
        }
        SERVE_ADAPTERS.labels(session=self.sid).set(
            float(len(self._adapters))
        )
        journal_mod.record(
            "session_adapter", sid=self.sid, adapter=str(name),
            digest=str(digest), path=str(path), content=str(content),
            sync=True,
        )

    async def _stage_adapter(self, record: dict) -> str:
        """Ship one packed bundle into this generation's worker CAS;
        returns the remote path (digest-named, single-flighted — a
        replay after reconnect onto the same worker is a present-set
        hit, zero wire bytes)."""
        executor = self.executor
        conn = self._conns[0]
        key = executor._pool_key(self.address)
        digest = str(record["digest"])
        remote = cas_path(executor.remote_cache, digest, ".lora")
        await executor._cas.ensure(
            key, conn, digest, str(record["path"]), remote,
            codec=executor._codec_for(key, conn),
            python_path=executor.python_path,
        )
        return remote

    async def _replay_adapters(self) -> None:
        """Re-splice every attached adapter into a FRESH generation
        (reconnect / warm handoff): the new engine starts with an empty
        bank, and a request naming an un-replayed adapter would refuse.
        Per-adapter degrade: one failed replay logs and keeps going —
        the other adapters (and the base lane) must not die with it.
        """
        client = self._client
        if client is None or not self._adapters:
            return
        for name, record in list(self._adapters.items()):
            try:
                remote = await self._stage_adapter(record)
                await client.serve_attach(
                    self._sid_g, name, str(record["digest"]), remote,
                    timeout=_env_number(
                        "COVALENT_TPU_SERVE_ATTACH_TIMEOUT_S", 60.0
                    ),
                )
            except asyncio.CancelledError:
                raise
            except BaseException as err:  # noqa: BLE001 - degrade per name
                app_log.warning(
                    "adapter %r replay onto %s generation %d failed: %r",
                    name, self.sid, self.generation, err,
                )
                obs_events.emit(
                    "serve.adapter_replay_failed",
                    sid=self.sid, adapter=str(name), error=repr(err),
                )

    @staticmethod
    def _read_payload(path: str) -> bytes:
        with open(path, "rb") as f:
            return f.read()

    async def _await_ready(self) -> None:
        if self._closed:
            raise ServeError(f"session {self.sid} is closed")
        while not self._ready.is_set():
            await self._ready.wait()
        if self._failed is not None:
            raise ServeError(
                f"session {self.sid} failed: {self._failed}"
            ) from self._failed
        if self._closed:
            raise ServeError(f"session {self.sid} is closed")

    def _publish_in_flight(self) -> None:
        if self.replica_of is not None:
            SERVE_REPLICA_IN_FLIGHT.labels(
                set=self.replica_of[0], replica=self.replica_of[1]
            ).set(float(len(self._requests)))

    # -- side-band routing --------------------------------------------------

    def _sink(self, _sid: str, data: dict) -> None:
        """One telemetry record for this session (event-loop context)."""
        kind = data.get("type")
        if kind == "serve.token":
            self._on_token(data)
        elif kind == "serve.reject":
            self._on_reject(data)
        elif kind == "serve.stats":
            self._on_stats(data)
        elif kind == "serve.preempt":
            self._on_preempt(data)
        elif kind == "span":
            # The worker has no event sink of ours: it times its segments
            # (queue wait, admission, decode, prefill) itself and ships them.
            record_remote_span(data)

    def _on_preempt(self, data: dict) -> None:
        """The worker hosting this session announced a preemption notice
        (SIGTERM): start the warm handoff NOW, while the old runtime is
        still serving inside its grace window."""
        obs_events.emit(
            "serve.preempt_notice",
            sid=self.sid,
            address=self.address,
            reason=str(data.get("reason") or ""),
        )
        if not self._auto_handoff or self._closed or self._in_handoff:
            return

        async def _run() -> None:
            try:
                await self.handoff(reason="preempt_notice")
            except Exception:  # noqa: BLE001 - reconnect path still guards
                app_log.exception(
                    "preemption-notice handoff for %s failed", self.sid
                )

        # Hold the reference: an unreferenced task can be collected
        # mid-await, silently dropping the handoff.
        self._handoff_task = asyncio.ensure_future(_run())
        self._handoff_task.add_done_callback(
            lambda _t: setattr(self, "_handoff_task", None)
        )

    def _on_token(self, data: dict) -> None:
        rid = str(data.get("rid") or "")
        request = self._requests.get(rid)
        if request is None:
            return
        idx = int(data.get("idx") or 0)
        tokens = list(data.get("tokens") or ())
        have = request.resumed_from + len(request.tokens)
        if idx > have:
            # A chunk went missing (idx jumped past our high-water mark):
            # the exactly-once contract is broken for this stream, fail
            # it loudly rather than splice around a hole.
            self._finish(rid, "error")
            request._fail(ServeError(
                f"token stream gap for {rid}: chunk starts at {idx}, "
                f"have {have}"
            ))
            return
        # Replay splice: after a reconnect (or a re-route onto another
        # replica) the fresh session re-streams from idx 0; everything
        # at-or-below our high-water mark is a duplicate and drops here,
        # so callers see each token exactly once.
        fresh = tokens[have - idx:] if idx < have else tokens
        first = request.t_first is None and bool(fresh)
        if first and not request.served_by:
            # Hedge arbitration: the FIRST arm to feed fresh tokens wins
            # the request; the replica set cancels the other arm.
            request.served_by = self.sid
        done = bool(data.get("done"))
        error = str(data.get("error") or "")
        hedge_loser = bool(
            request.hedged
            and request.served_by
            and request.served_by != self.sid
        )
        if hedge_loser and error:
            # A terminal error on the hedge-losing arm — the cancel ack,
            # or the loser dying mid-drain — must never fail (or even
            # reach) the SHARED request: the winning stream owns the
            # request's terminal record; this arm only releases its claim.
            self.abandon(rid)
            return
        spec_s = data.get("spec_verify_s")
        if spec_s is not None:
            # Rides the final chunk from a speculative engine's harness;
            # captured BEFORE _feed so _finalize_trace (which _feed calls
            # on done) sees it and tiles the spec_verify segment.
            request.spec_verify_s = float(spec_s)
        request._feed(fresh, done, error=error)
        if fresh:
            SERVE_TOKENS_TOTAL.inc(len(fresh))
            # The stream's durable high-water mark: a successor
            # dispatcher resumes the stream from here exactly-once.
            journal_mod.record(
                "stream_hwm", sid=self.sid, rid=rid,
                hwm=request.resumed_from + len(request.tokens),
            )
        # The trace id rides as the bucket exemplar: a p99 spike on the
        # serving dashboards resolves straight to this request's
        # waterfall at /traces/<id>.
        if first and request.ttft_s is not None:
            SERVE_TTFT_SECONDS.observe(
                request.ttft_s, trace_id=request.span.trace_id
            )
            # Differential health feed: TTFT vs sibling replicas is the
            # straggler signal a binary breaker never sees.  For a hedged
            # request this arm's latency is measured from its OWN
            # dispatch: the caller-visible ttft_s includes the primary's
            # stall plus the hedge threshold wait, and charging that to
            # the healthy winner would pollute the very differential
            # signal that routed around the straggler.
            arm_lat = request.ttft_s
            if request.hedged:
                sent = request.arms.get(self.sid)
                if sent is not None and request.t_first is not None:
                    arm_lat = max(0.0, request.t_first - sent)
            HEALTH.record_latency(
                self.sid, arm_lat, group=self._health_group
            )
        if done:
            if hedge_loser:
                # The losing arm completed normally before its cancel
                # drained: its chunks already spliced as duplicates and
                # request._feed ignored the second done — but the outcome
                # accounting (request counters, latency histogram, health
                # credit) belongs to the winner alone.  Release the claim
                # without counting anything.
                self.abandon(rid)
                return
            outcome = "ok"
            if error == "deadline_exceeded":
                outcome = "deadline"
            elif error:
                outcome = "error"
            if outcome == "ok":
                HEALTH.record_success(self.sid, group=self._health_group)
            elif outcome == "error":
                HEALTH.record_fault(
                    self.sid, label=error[:40], group=self._health_group
                )
            self._finish(rid, outcome)
            if request.latency_s is not None:
                SERVE_REQUEST_SECONDS.observe(
                    request.latency_s, trace_id=request.span.trace_id
                )

    def _on_reject(self, data: dict) -> None:
        rid = str(data.get("rid") or "")
        request = self._requests.get(rid)
        if request is None:
            return
        code = str(data.get("code") or "rejected")
        if code == "unknown_session" and not self._ready.is_set():
            # Raced a dying generation; the reconnect replay will re-send
            # this request on the fresh session.
            return
        HEALTH.record_fault(self.sid, label=code, group=self._health_group)
        if request.hedged and request.served_by != self.sid and (
            request.served_by or request.arms.keys() - {self.sid}
        ):
            # Hedge guard: a wire-level reject of one arm (e.g. the
            # speculative copy shed under the same load that triggered
            # the hedge) must not fail the SHARED request while the other
            # arm still holds it — that arm owns the terminal.  The
            # reject was still a real fault for THIS replica (recorded
            # above); only the request survives it.
            self.abandon(rid)
            return
        self._finish(
            rid, "shed" if code == "serve_admission_shed" else "rejected"
        )
        request._fail(ServeRequestRejected(
            rid, code, str(data.get("message") or "")
        ))

    def _on_stats(self, data: dict) -> None:
        # The runtime's running compile totals: only their growth is added.
        jitstats.absorb_worker(
            data.get("jit"), self._jit_seen, source=data.get("pid")
        )
        self.stats = {
            k: v for k, v in data.items()
            if k in (
                "slots", "busy", "queued", "served",
                "tokens_total", "tokens_per_s",
                "prefix_hits", "prefix_misses", "prefill_positions",
                "prefix_evictions", "kv_admits", "kv_fallbacks",
                "kv_exports", "prefills",
                "spec_rounds", "spec_proposed", "spec_accepted",
                "spec_refusals", "spec_accept_rate", "mode_refusals",
            )
            # Per-lane token counters arrive as one key per configured
            # mode (and one per attached adapter); pass the families
            # through rather than enumerating them.
            or k.startswith("mode_tokens_")
            or k.startswith("adapter_")
        }
        SERVE_QUEUE_DEPTH.labels(session=self.sid).set(
            float(self.stats.get("queued") or 0)
        )
        HEALTH.record_queue_depth(
            self.sid, float(self.stats.get("queued") or 0),
            group=self._health_group,
        )
        SERVE_TOKENS_PER_S.labels(session=self.sid).set(
            float(self.stats.get("tokens_per_s") or 0.0)
        )
        # Engine prefix counters ride the same stats record; only engines
        # that report them (ContinuousEngine) create the series, so stub
        # engines leave no dead zero gauges behind.
        for key, gauge in (
            ("prefix_hits", SERVE_PREFIX_HITS),
            ("prefix_misses", SERVE_PREFIX_MISSES),
            ("prefill_positions", SERVE_PREFILL_POSITIONS),
        ):
            if key in self.stats:
                gauge.labels(session=self.sid).set(
                    float(self.stats[key] or 0)
                )
        # Speculative / lane-mode series: again only engines that report
        # them create the series (stale-series reap in _drop_live must
        # enumerate modes, which is fine — the mode set is closed).
        if "spec_accept_rate" in self.stats:
            SERVE_SPEC_ACCEPT_RATE.labels(session=self.sid).set(
                float(self.stats["spec_accept_rate"] or 0.0)
            )
        for key, value in self.stats.items():
            if key.startswith("mode_tokens_"):
                SERVE_MODE_TOKENS.labels(
                    session=self.sid, mode=key[len("mode_tokens_"):]
                ).set(float(value or 0))
            elif key.startswith("adapter_tokens_"):
                adapter = key[len("adapter_tokens_"):]
                self._adapter_series.add(adapter)
                SERVE_ADAPTER_TOKENS.labels(
                    session=self.sid, adapter=adapter
                ).set(float(value or 0))
            elif key.startswith("adapter_requests_"):
                adapter = key[len("adapter_requests_"):]
                self._adapter_series.add(adapter)
                SERVE_ADAPTER_REQUESTS_TOTAL.labels(
                    session=self.sid, adapter=adapter
                ).set(float(value or 0))

    def _finish(self, rid: str, outcome: str) -> None:
        request = self._requests.pop(rid, None)
        if request is not None:
            request.arms.pop(self.sid, None)
            self.served += 1
            SERVE_REQUESTS_TOTAL.labels(outcome=outcome).inc()
            journal_mod.record(
                "stream_done", sid=self.sid, rid=rid, outcome=outcome,
                sync=True,
            )
            self._publish_in_flight()
            self._changed()

    def abandon(self, rid: str) -> None:
        """Drop one request ASSIGNMENT without failing the request object
        or counting an outcome — the hedge-loser path: the same request
        lives on (and completes) under the winning supervisor, so this
        arm only releases its claim and frees the worker lane with a
        fire-and-forget ``serve_cancel``.  Journaled as a ``stream_done``
        so a successor dispatcher does not resume the dead arm."""
        request = self._requests.pop(rid, None)
        if request is None:
            return
        request.arms.pop(self.sid, None)
        journal_mod.record(
            "stream_done", sid=self.sid, rid=rid, outcome="hedge_abandoned",
        )
        self._publish_in_flight()
        client, sid_g = self._client, self._sid_g
        if client is not None and client.alive and not self._closed:
            task = asyncio.ensure_future(client.serve_cancel(sid_g, rid))
            self._bg_tasks.add(task)
            task.add_done_callback(
                lambda t: (
                    self._bg_tasks.discard(t),
                    None if t.cancelled() else t.exception(),
                )
            )
        self._changed()

    async def canary(self, timeout: float = 10.0) -> bool:
        """Cheap readmission probe for a quarantined replica: one agent
        ping round trip (no model work, no lane taken).  True means the
        channel answers promptly — enough to readmit to PROBATION, where
        real traffic re-earns (or re-loses) the health score."""
        client = self._client
        if client is None or not client.alive or self.state != "open":
            return False
        try:
            await client.ping(timeout=timeout)
            return True
        except (AgentError, TransportError, asyncio.TimeoutError, OSError):
            return False

    # -- warm handoff ---------------------------------------------------------

    async def handoff(self, reason: str = "planned") -> bool:
        """Drain-and-reopen: move this session to a FRESH gang with zero
        dropped tokens.

        The replacement generation is leased, staged, and opened while the
        old one is still serving (planned churn — a preemption notice, a
        rebalance — gives us that window); the swap then re-sends every
        in-flight request on the new session, whose restart-from-0 streams
        are spliced on each request's token high-water mark, so callers
        observe exactly-once delivery across the move.  The old session is
        closed best-effort afterwards — it is about to die anyway.

        Returns True when the session now runs on the new generation;
        False when no handoff was possible (closed/failed/already moving,
        or the replacement open failed — the reconnect path still guards
        the latter when the old gang eventually dies).
        """
        if (
            self._closed
            or self._failed is not None
            or self._in_handoff
            or not self._ready.is_set()
        ):
            return False
        self._in_handoff = True
        try:
            old_client, old_sid = self._client, self._sid_g
            old_conns, old_address = list(self._conns), self.address
            obs_events.emit(
                "serve.handoff_started",
                sid=self.sid,
                address=old_address,
                reason=reason,
                in_flight=self.in_flight,
            )
            try:
                binding = await self._dial_generation()
            except asyncio.CancelledError:
                raise
            except BaseException as err:  # noqa: BLE001 - degrade, not fail
                SERVE_HANDOFFS_TOTAL.labels(outcome="failed").inc()
                obs_events.emit(
                    "serve.handoff_failed",
                    sid=self.sid,
                    address=old_address,
                    reason=reason,
                    error=repr(err),
                )
                app_log.warning(
                    "warm handoff of %s failed (%s); the reconnect path "
                    "recovers when the old gang dies", self.sid, err,
                )
                return False
            # Swap: stop the old generation's feed BEFORE replaying so the
            # splice sees one stream at a time, then re-send everything
            # in flight on the fresh session.
            self._adopt(binding)
            if old_client is not None:
                old_client.unwatch_serve(old_sid)
            await self._replay_adapters()
            await self._replay_in_flight()
            self.handoffs += 1
            SERVE_HANDOFFS_TOTAL.labels(outcome="ok").inc()
            obs_events.emit(
                "serve.handoff_complete",
                sid=self.sid,
                from_address=old_address,
                to_address=self.address,
                generation=self.generation,
                replayed=len(self._requests),
                reason=reason,
            )
            # Retire the old generation: a short drain-free close (its
            # requests were replayed; duplicates are spliced away), and
            # its channels leave the pool unless the replacement landed on
            # the very same gang (single-address executors re-lease the
            # pooled transport).
            if old_client is not None:
                try:
                    await old_client.serve_close(old_sid, timeout=5.0)
                except (
                    AgentError, TransportError, asyncio.TimeoutError,
                ) as err:
                    app_log.debug(
                        "post-handoff close of %s failed: %s", old_sid, err
                    )
            shared = {id(c) for c in self._conns}
            leftovers = [c for c in old_conns if id(c) not in shared]
            if leftovers:
                try:
                    await self.executor._discard_workers(leftovers)
                except Exception:  # noqa: BLE001 - teardown is best-effort
                    pass
            self._changed()
            return True
        finally:
            self._in_handoff = False

    # -- supervision / reconnect --------------------------------------------

    async def _supervise(self) -> None:
        """Re-open the session on a fresh gang when its channel dies."""
        while True:
            client = self._client
            if client is None:
                return
            try:
                await client.wait_dead()
            except asyncio.CancelledError:
                raise
            except BaseException as err:  # noqa: BLE001 - AgentError et al.
                death = err
            else:  # pragma: no cover - wait_dead only returns by raising
                death = AgentError("agent channel closed")
            if self._closed:
                return
            if self._client is not client:
                # A warm handoff moved the session while we waited: the
                # death belongs to the RETIRED generation (the preempted
                # gang finally going away), not the live one.
                continue
            if self._in_handoff:
                # The old gang died mid-handoff; let the handoff finish —
                # its replay owns the streams — then watch the new client.
                while self._in_handoff and not self._closed:
                    await asyncio.sleep(0.05)
                if self._client is not client:
                    continue
            obs_events.emit(
                "serve.session_lost",
                sid=self.sid,
                address=self.address,
                error=repr(death),
            )
            if not await self._reconnect(death):
                return

    async def _reconnect(self, death: BaseException) -> bool:
        """Tear down, re-lease, re-open, replay — or fail every stream."""
        self._ready.clear()
        self._changed()
        old_client, old_sid = self._client, self._sid_g
        if old_client is not None:
            old_client.unwatch_serve(old_sid)
        try:
            await self.executor._discard_workers(self._conns)
        except Exception:  # noqa: BLE001 - teardown is best-effort
            pass
        fault, fault_label = classify_error(death)
        HEALTH.record_fault(
            self.sid, label=fault_label or fault.name.lower(),
            group=self._health_group,
        )
        failure: BaseException = death
        if fault is FaultClass.TRANSIENT:
            policy = RetryPolicy(
                max_retries=self.retries,
                base_delay=getattr(self.executor, "retry_base_delay", 0.25),
                max_delay=getattr(self.executor, "retry_max_delay", 10.0),
            )
            for attempt in range(self.retries + 1):
                if self._closed:
                    return False
                try:
                    await self._open_generation()
                except asyncio.CancelledError:
                    raise
                except (
                    AgentError, TransportError, ServeError, OSError,
                    ValueError,
                ) as err:
                    failure = err
                    fault, _label = classify_error(err)
                    if fault is not FaultClass.TRANSIENT:
                        break
                    if attempt < self.retries:
                        await asyncio.sleep(policy.delay(attempt))
                else:
                    self.reconnects += 1
                    SERVE_RECONNECTS_TOTAL.inc()
                    obs_events.emit(
                        "serve.session_reopened",
                        sid=self.sid,
                        address=self.address,
                        generation=self.generation,
                        replayed=len(self._requests),
                    )
                    await self._replay_adapters()
                    await self._replay_in_flight()
                    self._ready.set()
                    self._changed()
                    return True
        # Permanent refusal or retry budget spent: the front-end may take
        # the in-flight requests (a replica set drains them onto
        # survivors); otherwise every stream fails with the cause.  New
        # requests are refused either way until the caller closes.
        self._failed = failure
        handled = False
        if self._on_failed is not None:
            try:
                handled = bool(self._on_failed(self, failure))
            except Exception:  # noqa: BLE001 - router hooks never fatal
                app_log.exception("serve on_failed hook failed")
        if not handled:
            for rid, request in list(self._requests.items()):
                self._finish(rid, "error")
                request._fail(ServeError(
                    f"session {self.sid} died and could not be re-opened: "
                    f"{failure}"
                ))
        self._ready.set()
        self._drop_live()
        self._changed()
        return False

    async def _replay_in_flight(self) -> None:
        """Re-send unfinished requests on the fresh generation.

        The new session streams each from idx 0; the splice in
        :meth:`_on_token` drops the already-delivered prefix, so callers
        observe every token exactly once with none lost.
        """
        for request in list(self._requests.values()):
            try:
                await self._send_request(request)
            except BaseException as err:  # noqa: BLE001 - fail just this one
                self._finish(request.rid, "error")
                request._fail(ServeError(
                    f"replay of {request.rid} failed: {err!r}"
                ))

    # -- close --------------------------------------------------------------

    async def close(self, timeout: float = 30.0) -> dict:
        """Drain and close the session; returns the ``serve_closed`` stats.

        The worker finishes every admitted AND queued request before
        acking (their tokens keep streaming during the drain); requests
        that raced a dead channel past the retry budget have already
        failed.  Idempotent.
        """
        if self._closed:
            return {"served": self.served}
        self._closed = True
        if self._supervisor is not None:
            self._supervisor.cancel()
        closed_event: dict = {"served": self.served}
        client, sid_g = self._client, self._sid_g
        if client is not None and self._failed is None:
            try:
                closed_event = await client.serve_close(sid_g, timeout)
            except (AgentError, TransportError, asyncio.TimeoutError) as err:
                app_log.debug("serve_close %s failed: %s", sid_g, err)
            client.unwatch_serve(sid_g)
        for rid, request in list(self._requests.items()):
            self._finish(rid, "error")
            request._fail(ServeError(f"session {self.sid} closed"))
        handles = getattr(self.executor, "_serve_handles", None)
        if handles is not None:
            handles.pop(self.sid, None)
        journal_mod.record("session_closed", sid=self.sid, sync=True)
        self._drop_live()
        obs_events.emit(
            "serve.session_closed",
            sid=self.sid,
            served=int(closed_event.get("served") or 0),
        )
        self._changed()
        return closed_event

    def _drop_live(self) -> None:
        if self._counted_live:
            self._counted_live = False
            SERVE_SESSIONS.dec()
            if self._pool is not None:
                self._pool.release()
        # Stale-series reap: a retired session's gauges must leave the
        # registry with it, or /metrics grows one orphan series pair per
        # session for the process lifetime under session churn.  The
        # worker-occupancy series go too once no other live session
        # shares the worker (its heartbeats stop carrying a serve block
        # the moment the last session closes, freezing stale values).
        # One forced history sample FIRST: a short-lived session could
        # otherwise live and die entirely between two sampler ticks,
        # leaving no trace of its gauges in the /history timeline.
        try:
            from ..obs.history import HISTORY

            HISTORY.sample(force=True)
        except Exception:  # noqa: BLE001 - observability never fatal
            pass
        HEALTH.drop(self.sid)
        SERVE_QUEUE_DEPTH.remove(session=self.sid)
        SERVE_TOKENS_PER_S.remove(session=self.sid)
        SERVE_PREFIX_HITS.remove(session=self.sid)
        SERVE_PREFIX_MISSES.remove(session=self.sid)
        SERVE_PREFILL_POSITIONS.remove(session=self.sid)
        SERVE_SPEC_ACCEPT_RATE.remove(session=self.sid)
        for mode in _SERVING_MODES:
            SERVE_MODE_TOKENS.remove(session=self.sid, mode=mode)
        # Adapter label set is OPEN — reap exactly the series this
        # supervisor created (tracked in _on_stats), plus the per-session
        # attachment gauge, so a churned multi-adapter session leaves no
        # stale adapter series behind.
        SERVE_ADAPTERS.remove(session=self.sid)
        for adapter in self._adapter_series:
            SERVE_ADAPTER_TOKENS.remove(session=self.sid, adapter=adapter)
            SERVE_ADAPTER_REQUESTS_TOTAL.remove(
                session=self.sid, adapter=adapter
            )
        self._adapter_series.clear()
        if self.replica_of is not None:
            SERVE_REPLICA_IN_FLIGHT.remove(
                set=self.replica_of[0], replica=self.replica_of[1]
            )
            SERVE_REPLICA_REQUESTS_TOTAL.remove(
                set=self.replica_of[0], replica=self.replica_of[1]
            )
        handles = getattr(self.executor, "_serve_handles", None) or {}
        if self.address and not any(
            h is not self and getattr(h, "address", "") == self.address
            for h in list(handles.values())
        ):
            for state in ("sessions", "slots", "busy", "queued"):
                SERVE_WORKER_SLOTS.remove(worker=self.address, state=state)

    # -- profiling ----------------------------------------------------------

    async def capture_profile(self, duration_s: float = 2.0) -> dict:
        """Capture a ``jax.profiler`` trace of this session's resident
        runtime while it serves live traffic.

        Records for ``duration_s`` inside the worker process holding the
        model (the pool server, or the native agent's ``--serve-child``
        runner), stages the trace back as a content-addressed artifact and
        digest-verifies it — no launch fallback, no second process.
        Raises :class:`ServeError` when the capture fails (session down,
        another trace already active, jax unavailable on the worker).
        """
        await self._await_ready()
        client, conns = self._client, self._conns
        if client is None or not conns:
            raise ServeError(f"session {self.sid} has no live runtime")
        profile_id = f"{self.sid}-prof{uuid.uuid4().hex[:6]}"
        sid = self._sid_g if client.mode != "pool" else ""
        started = await self.executor._start_resident_profile(
            client, profile_id, sid=sid
        )
        if not started:
            raise ServeError(
                f"profiler start refused on session {self.sid} (busy or "
                "unavailable)"
            )
        info = await self.executor._finish_capture(
            client, conns[0], profile_id, duration_s, sid=sid
        )
        if not info:
            raise ServeError(
                f"profile capture on session {self.sid} produced no "
                "artifact"
            )
        return {"sid": self.sid, "duration_s": float(duration_s), **info}
