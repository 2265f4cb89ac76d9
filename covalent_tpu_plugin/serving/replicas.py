"""Horizontally scaled serving: N replica sessions behind one handle.

One resident session's throughput ceiling is one engine's slot count; a
:class:`ReplicaSet` raises it by opening N sessions of the SAME engine
factory across fleet pools and fronting them with a session-aware
router.  Each replica is one :class:`~.supervisor.SessionSupervisor` —
the exact reconnect/exactly-once-replay machinery a single
:class:`~.handle.ServeHandle` runs — so horizontal scale adds no new
failure semantics, only placement:

* **Least-loaded placement, DRR tie-break.**  Every request passes
  through a per-tenant :class:`~..fleet.queue.FairWorkQueue` (the fleet
  scheduler's deficit-round-robin, reused verbatim): under contention
  the DRR decides *whose* request dispatches next, and the least-loaded
  open replica receives it (rotation breaks exact load ties).  With
  free capacity the queue is pass-through — submit, pop, place — so the
  uncontended path stays a dict lookup and a compare, not a scheduler.
* **Sticky session ids.**  ``request(..., sticky="user-42")`` pins a
  multi-turn caller to one replica (engine-side prefix caches are
  per-replica), refreshed on use and expired after ``sticky_ttl_s``.  A
  pin survives its replica's reconnect (the supervisor keeps the
  replica's identity across generations); only a replica death past its
  retry budget re-pins.
* **Per-replica health + drain-on-death.**  New requests only route to
  ``open`` replicas; a reconnecting replica's backlog waits for it
  (sticky) or flows to survivors (unpinned).  A replica that dies past
  its retry budget hands its in-flight requests back
  (``detach_requests``) and the router re-routes them onto survivors —
  the requests' own token high-water marks make the cross-replica
  replay exactly-once, the same ``idx`` splice a same-replica reconnect
  uses.
* **Warm-up affinity.**  Replica placement prefers pools already
  holding the factory's CAS digest (zero re-staging), then warm gangs,
  then free capacity — the serving analog of the scheduler's fn-digest
  affinity.

``open_replica_set(targets, factory, replicas=1)`` with one target
degenerates to today's single-session behavior (one supervisor, pass-
through router); ``open_session`` remains the unchanged one-session API.
"""

from __future__ import annotations

import asyncio
import collections
import os
import time
import uuid
from typing import Any, Callable

import cloudpickle

from ..cache import bytes_digest
from ..fleet import journal as journal_mod
from ..fleet.health import DEGRADED, HEALTH, PROBING, QUARANTINED
from ..fleet.queue import DEFAULT_TENANT, FairWorkQueue, QueueFullError, WorkItem
from ..obs import events as obs_events
from ..obs.trace import Span, record_span
from ..utils.log import app_log
from .metrics import (
    SERVE_HEDGES_TOTAL,
    SERVE_REPLICAS,
    SERVE_ROUTER_DECISION_SECONDS,
    SERVE_ROUTER_DECISIONS_TOTAL,
    SERVE_ROUTER_QUEUE_DEPTH,
)
from .supervisor import (
    ServeError,
    ServeRequest,
    ServeRequestRejected,
    SessionSupervisor,
)

__all__ = [
    "ReplicaView",
    "ReplicaRouter",
    "ReplicaSet",
    "open_replica_set",
]

#: Router states a replica-set member can be in (the SERVE_REPLICAS
#: gauge's closed label set).
_REPLICA_STATES = ("open", "reconnecting", "failed", "closed")


class ReplicaView:
    """One replica's routing-relevant shape: id, health, load, capacity.

    Deliberately tiny and data-only so the router is unit-testable with
    fake fleets and a fake clock — no supervisor, no I/O.
    """

    __slots__ = (
        "rid", "open", "alive", "load", "capacity", "health",
        "degraded", "quarantined",
    )

    def __init__(
        self, rid: str, *, open: bool, load: int, capacity: int,
        alive: bool | None = None, health: float = 1.0,
        degraded: bool = False, quarantined: bool = False,
    ) -> None:
        self.rid = rid
        self.open = bool(open)
        #: open OR recovering: a sticky pin to this replica still holds.
        self.alive = bool(open if alive is None else alive)
        self.load = int(load)
        self.capacity = max(1, int(capacity))
        #: continuous health score in [0, 1] (fleet.health).
        self.health = float(health)
        #: gray-degraded: routable as LAST RESORT only — a healthy
        #: replica with headroom always wins over it.
        self.degraded = bool(degraded)
        #: quarantined: receives NO new traffic; sticky pins drain off it
        #: (re-pin on next use) and only a canary probe readmits it.
        self.quarantined = bool(quarantined)


class ReplicaRouter:
    """Session-aware request router over a set of replica views.

    Synchronous and clock-injectable: :meth:`submit` admits one request
    item (bounded — a full queue sheds, the same capacity verdict the
    worker-side admission queue renders), :meth:`pump` drains the DRR
    queue onto whatever open replicas have headroom and returns the
    ``(item, replica_id, outcome)`` assignments.  The caller (the
    replica set) performs the actual submissions and re-pumps on every
    completion or health transition.

    Sticky semantics: a pinned item only ever places on its pinned
    replica while that replica is *alive* (open or reconnecting) —
    waiting out a reconnect rather than abandoning the replica's warm
    state — and re-pins to a fresh least-loaded choice once the replica
    is gone.  Pins expire ``sticky_ttl_s`` after their last use.
    """

    def __init__(
        self,
        *,
        weights: dict[str, float] | None = None,
        sticky_ttl_s: float = 300.0,
        queue_max: int = 0,
        clock: Callable[[], float] = time.monotonic,
    ) -> None:
        self._clock = clock
        self.sticky_ttl_s = float(sticky_ttl_s)
        self._queue = FairWorkQueue(
            max_depth=queue_max, policy="reject",
            weights=weights, clock=clock,
            # The router's backlog moves its OWN gauge, never the fleet
            # scheduler's (two queues on one series would fight).
            depth_gauge=SERVE_ROUTER_QUEUE_DEPTH,
        )
        #: sticky key -> [replica_id, last_used] (TTL-expired lazily).
        self._sticky: dict[str, list] = {}
        #: prefix key -> replica id that last served a request sharing
        #: that prompt prefix (bounded FIFO): requests carrying the same
        #: key steer to the replica whose engine-side prefix tree is
        #: already warm for it.  A *preference*, never a pin — sticky
        #: sids rank above it, and it only engages when the remembered
        #: replica is open with headroom, so DRR fairness (which decides
        #: WHOSE request pops) is untouched.
        self._prefix_sites: "collections.OrderedDict[str, str]" = (
            collections.OrderedDict()
        )
        self._prefix_sites_max = 1024
        #: adapter name -> replica ids whose engine holds that adapter
        #: resident.  Unlike prefix affinity this is a CONSTRAINT when
        #: known: a replica without the adapter refuses the request
        #: outright, so placement restricts to residents (and defers
        #: when no resident has headroom) rather than merely preferring
        #: them.  An adapter the router has no sites for places
        #: unconstrained — the attach-to-all default, or a caller
        #: naming an unknown adapter (the worker's clean refusal is the
        #: right answer there, not a router stall).
        self._adapter_sites: dict[str, set[str]] = {}
        #: rotation cursor for exact load ties, so equal replicas share.
        self._rr = 0

    # -- introspection ------------------------------------------------------

    @property
    def queued(self) -> int:
        return len(self._queue)

    def backlog(self) -> dict[str, int]:
        return self._queue.backlog()

    def sticky_count(self) -> int:
        self._expire_sticky()
        return len(self._sticky)

    def sticky_target(self, key: str) -> str | None:
        """The live pin for ``key`` (refreshes nothing; expires lazily)."""
        entry = self._sticky.get(key)
        if entry is None:
            return None
        if self._clock() - entry[1] > self.sticky_ttl_s:
            del self._sticky[key]
            return None
        return entry[0]

    def _expire_sticky(self) -> None:
        now = self._clock()
        for key in [
            k for k, (_, used) in self._sticky.items()
            if now - used > self.sticky_ttl_s
        ]:
            del self._sticky[key]

    def pin(self, key: str, replica_id: str) -> None:
        self._sticky[key] = [replica_id, self._clock()]

    def set_queue_max(self, depth: int) -> None:
        """Resize the admission bound (the set does this once replica
        capacity is known; 0 = unbounded)."""
        self._queue.max_depth = max(0, int(depth))

    def forget_replica(self, replica_id: str) -> None:
        """Drop every pin to a retired replica (its pins re-place)."""
        for key in [
            k for k, (rid, _) in self._sticky.items() if rid == replica_id
        ]:
            del self._sticky[key]
        for key in [
            k for k, rid in self._prefix_sites.items()
            if rid == replica_id
        ]:
            del self._prefix_sites[key]
        for name in list(self._adapter_sites):
            self._adapter_sites[name].discard(replica_id)
            if not self._adapter_sites[name]:
                del self._adapter_sites[name]

    def record_prefix_site(self, prefix_key: str, replica_id: str) -> None:
        """Remember which replica last warmed ``prefix_key`` (bounded)."""
        if not prefix_key:
            return
        self._prefix_sites[prefix_key] = replica_id
        self._prefix_sites.move_to_end(prefix_key)
        while len(self._prefix_sites) > self._prefix_sites_max:
            self._prefix_sites.popitem(last=False)

    def prefix_site(self, prefix_key: str) -> str | None:
        return self._prefix_sites.get(prefix_key)

    def record_adapter_site(self, adapter: str, replica_id: str) -> None:
        """Mark ``replica_id``'s engine as holding ``adapter`` resident."""
        if adapter:
            self._adapter_sites.setdefault(adapter, set()).add(replica_id)

    def drop_adapter_site(
        self, adapter: str, replica_id: str | None = None
    ) -> None:
        """Forget residency — one replica's, or (default) everywhere."""
        if replica_id is None:
            self._adapter_sites.pop(adapter, None)
            return
        sites = self._adapter_sites.get(adapter)
        if sites is not None:
            sites.discard(replica_id)
            if not sites:
                del self._adapter_sites[adapter]

    def adapter_sites(self, adapter: str) -> set[str]:
        return set(self._adapter_sites.get(adapter) or ())

    # -- admission + placement ----------------------------------------------

    def submit(self, item: WorkItem) -> None:
        """Admit one request item; raises :class:`QueueFullError` at the
        bound (the caller sheds it as ``serve_admission_shed``)."""
        self._queue.put(item)

    def remove(self, predicate) -> list[WorkItem]:
        return self._queue.remove(predicate)

    def drain(self) -> list[WorkItem]:
        return self._queue.drain()

    def pump(
        self, views: dict[str, ReplicaView]
    ) -> list[tuple[WorkItem, str, str]]:
        """Assign queued items to replicas with headroom, DRR-fairly.

        Pops at most the current depth (one DRR visit per queued item per
        pump): an item whose target has no headroom — or whose sticky
        replica is mid-reconnect — requeues with its original enqueue
        stamp, so fairness age and ``queued`` accounting survive the
        deferral.  Returns ``(item, replica_id, outcome)`` per placement,
        ``outcome`` in ``{"sticky", "prefix_affinity", "least_loaded"}``.
        """
        # Quarantined replicas get NO new traffic: they are excluded from
        # headroom entirely (the canary probe path is their only road
        # back), so every placement rule below — sticky, prefix, least-
        # loaded — routes around them by construction.
        headroom = {
            rid: view.capacity - view.load
            for rid, view in views.items()
            if view.open and not view.quarantined
        }
        assigned: list[tuple[WorkItem, str, str]] = []
        if not headroom:
            return assigned
        deferred: list[WorkItem] = []
        for _ in range(len(self._queue)):
            if not any(free > 0 for free in headroom.values()):
                # Out of lanes: STOP popping.  Draining the rest just to
                # requeue it would reset the DRR lanes' deficit state
                # every pump and hand the head tenant the whole trickle.
                break
            item = self._queue.pop()
            if item is None:
                break
            sticky = str(item.task_metadata.get("sticky") or "")
            prefix_key = str(item.task_metadata.get("prefix_key") or "")
            adapter = str(item.task_metadata.get("adapter") or "")
            # Residency constraint: when the router KNOWS where this
            # request's adapter lives, only those replicas are eligible
            # — anywhere else refuses it outright (unknown_adapter).
            sites = self._adapter_sites.get(adapter) if adapter else None
            constrained = bool(sites)

            def _eligible(rid: str) -> bool:
                return not constrained or rid in sites

            target = None
            outcome = "least_loaded"
            if sticky:
                pinned = self.sticky_target(sticky)
                if pinned is not None:
                    view = views.get(pinned)
                    if (
                        view is not None and view.alive
                        and not view.quarantined
                        # A pin at a replica WITHOUT the adapter falls
                        # through to a fresh (resident) placement and
                        # re-pins there: waiting on the pinned replica
                        # would wait for a refusal.
                        and _eligible(pinned)
                    ):
                        if headroom.get(pinned, 0) > 0:
                            target, outcome = pinned, "sticky"
                        else:
                            # Pinned replica full or reconnecting: wait
                            # for IT (warm per-replica state is the whole
                            # point of the pin) instead of re-placing.
                            deferred.append(item)
                            continue
                    # else: the pin points at a dead OR quarantined
                    # replica — fall through to a fresh placement and
                    # re-pin below (the sticky drain: a browned-out
                    # replica's pinned sessions move off it rather than
                    # waiting out a reconnect that never comes).
            if target is None and prefix_key:
                # Prefix affinity ranks BELOW sticky and above
                # least-loaded, and unlike a pin it never defers: a warm
                # prefix tree is worth steering toward, not waiting on.
                site = self.prefix_site(prefix_key)
                if (
                    site is not None and headroom.get(site, 0) > 0
                    and _eligible(site)
                ):
                    view = views.get(site)
                    if view is not None and view.open:
                        target, outcome = site, "prefix_affinity"
            if target is None:
                pool = (
                    {
                        rid: free for rid, free in headroom.items()
                        if rid in sites
                    }
                    if constrained else headroom
                )
                target = self._least_loaded(views, pool)
                if target is None:
                    # Constrained and no resident lane free: wait for
                    # one (the adapter IS attached somewhere) rather
                    # than burning the request on a certain refusal.
                    deferred.append(item)
                    continue
                if constrained:
                    outcome = "adapter_affinity"
                if sticky:
                    self.pin(sticky, target)
            if outcome == "sticky":
                # Refresh the pin's TTL on use: a multi-turn caller stays
                # put as long as its turns keep landing.
                self.pin(sticky, target)
            if prefix_key:
                self.record_prefix_site(prefix_key, target)
            headroom[target] -= 1
            assigned.append((item, target, outcome))
        for item in deferred:
            # enqueued_at survives a requeue (FairWorkQueue keeps the
            # first stamp), so deferral never resets fairness age.
            self._queue.put(item)
        return assigned

    def _least_loaded(
        self, views: dict[str, ReplicaView], headroom: dict[str, int]
    ) -> str | None:
        """The open replica with the most free lanes (ties rotate).

        Health-aware: gray-degraded replicas are LAST-RESORT — they only
        receive work when no healthy replica has headroom.  Routing a
        request to a 10x-slower replica because it happens to be least
        loaded is exactly the tail-latency trap this avoids.
        """
        candidates = [
            rid for rid, free in headroom.items() if free > 0
        ]
        if not candidates:
            return None
        healthy = [rid for rid in candidates if not views[rid].degraded]
        pool = healthy or candidates
        # Effective load folds in this pump's own assignments (headroom
        # already decremented), so one burst spreads instead of piling
        # onto the momentarily-least-loaded replica.
        best = min(
            views[rid].capacity - headroom[rid] for rid in pool
        )
        tied = [
            rid for rid in pool
            if views[rid].capacity - headroom[rid] == best
        ]
        self._rr += 1
        return tied[self._rr % len(tied)]


class ReplicaSet:
    """N supervised serving sessions of one engine factory, one front.

    Build through :func:`open_replica_set`.  The request surface mirrors
    :class:`~.handle.ServeHandle.request` plus ``sticky=`` (the
    multi-turn session id); streams, results, deadlines, rejection
    classification, and exactly-once delivery are all the supervisor's —
    identical to the single-session tier.
    """

    def __init__(
        self,
        targets: list[Any],
        factory: Any,
        *,
        replicas: int | None = None,
        name: str = "",
        sticky_ttl_s: float | None = None,
        router_queue_max: int | None = None,
        tenant_weights: dict[str, float] | None = None,
        prefer_stable: bool = False,
        **session_options: Any,
    ) -> None:
        if not targets:
            raise ValueError("a replica set needs at least one target")
        self.name = name or f"rset-{uuid.uuid4().hex[:8]}"
        self.factory = factory
        self._targets = [self._split_target(t) for t in targets]
        self.replicas_wanted = int(
            replicas if replicas is not None else len(self._targets)
        )
        if self.replicas_wanted < 1:
            raise ValueError(
                f"replicas must be >= 1, got {self.replicas_wanted}"
            )
        #: SLO-critical placement: rank non-preemptible (stable) pool
        #: targets ahead of spot ones, so serving replicas pin to
        #: capacity that will not be reclaimed under them.  The autoscale
        #: controller sets this on the sets it manages as SLO-critical.
        self.prefer_stable = bool(prefer_stable)
        self._session_options = dict(session_options)
        self._router_queue_max = router_queue_max
        self.router = ReplicaRouter(
            weights=tenant_weights,
            sticky_ttl_s=(
                300.0 if sticky_ttl_s is None else float(sticky_ttl_s)
            ),
            queue_max=0,  # resized once replica capacity is known
        )
        #: replica id -> supervisor (dead replicas leave; closed leave).
        self._replicas: dict[str, SessionSupervisor] = {}
        #: replica id -> (executor, pool) it was placed on.
        self._placements: dict[str, tuple[Any, Any]] = {}
        self._payload: bytes | None = None
        self._digest = ""
        self._next_rid = 0
        self._next_replica = 0
        self._closed = False
        #: scale-to-zero: True between a drain-to-zero (scale_to(0)) and
        #: the re-warm the next request (or explicit scale-up) triggers.
        self._suspended = False
        #: replica count a demand-triggered resume re-opens (the
        #: controller grows it further from trends once traffic flows).
        self._resume_to = 1
        #: serializes scale transitions against each other AND against a
        #: request arriving mid-teardown — such a request waits for the
        #: drain to finish, then re-warms; it is never dropped.
        self._scale_lock = asyncio.Lock()
        self._pump_tasks: set[asyncio.Task] = set()
        #: recent router decision walls (``status()`` reads the same
        #: numbers the histogram observes).
        self.decision_s: collections.deque = collections.deque(maxlen=4096)
        # -- tail-latency hedging ------------------------------------------
        # A deterministic (temperature=0), non-sticky request whose TTFT
        # exceeds the set's adaptive percentile is speculatively re-issued
        # on the next-healthiest replica; first token stream wins, the
        # loser is cancelled through the exactly-once idx splice so the
        # byte stream is identical either way.  Budgeted: hedges stay
        # under COVALENT_TPU_HEDGE_BUDGET_PCT of issued requests.
        self._hedge_enabled = os.environ.get(
            "COVALENT_TPU_HEDGE", "on"
        ).strip().lower() not in ("off", "0", "false", "disabled")
        self._hedge_percentile = float(
            os.environ.get("COVALENT_TPU_HEDGE_PERCENTILE", "95") or 95
        )
        self._hedge_min_s = float(
            os.environ.get("COVALENT_TPU_HEDGE_MIN_S", "0.05") or 0.05
        )
        self._hedge_budget_pct = float(
            os.environ.get("COVALENT_TPU_HEDGE_BUDGET_PCT", "5") or 5
        )
        #: recent time-to-first-token samples (both arms feed it).
        self._ttft_ring: collections.deque = collections.deque(maxlen=512)
        self._hedge_issued = 0
        self._hedge_wins = 0
        self._requests_issued = 0

    @staticmethod
    def _split_target(target: Any) -> tuple[Any, Any]:
        """(executor, pool-or-None) from a Pool or a bare executor."""
        if hasattr(target, "spec") and hasattr(target, "executor"):
            return target.executor, target
        return target, None

    # -- views --------------------------------------------------------------

    @property
    def state(self) -> str:
        if self._closed:
            return "closed"
        states = {sup.state for sup in self._replicas.values()}
        if "open" in states:
            return "open"
        if "reconnecting" in states:
            return "reconnecting"
        if self._suspended:
            return "suspended"
        return "failed"

    @property
    def suspended(self) -> bool:
        """Scaled to zero: no live replicas, re-warms on first demand."""
        return self._suspended and not any(
            s.alive for s in self._replicas.values()
        )

    @property
    def live_replicas(self) -> int:
        """Replicas that are open or recovering (the autoscale view)."""
        return len([s for s in self._replicas.values() if s.alive])

    @property
    def decode_slots(self) -> int:
        """Aggregate engine slots across live replicas — the honest
        concurrency capacity (the router's per-replica view adds the
        admission queue on top; a utilization target must not)."""
        return sum(
            max(1, sup.slots)
            for sup in self._replicas.values()
            if sup.alive
        )

    @property
    def queued(self) -> int:
        """Requests waiting in the router's DRR queue."""
        return self.router.queued

    @property
    def supervisors(self) -> dict[str, SessionSupervisor]:
        return dict(self._replicas)

    @property
    def in_flight(self) -> int:
        return sum(sup.in_flight for sup in self._replicas.values())

    @property
    def served(self) -> int:
        return sum(sup.served for sup in self._replicas.values())

    @property
    def reconnects(self) -> int:
        return sum(sup.reconnects for sup in self._replicas.values())

    def _views(self) -> dict[str, ReplicaView]:
        views: dict[str, ReplicaView] = {}
        for rid, sup in self._replicas.items():
            # A replica's routable capacity mirrors the worker's own
            # bound (engine slots + admission queue): the router sheds
            # before the worker would, so worker-side sheds only happen
            # to callers bypassing the set.
            capacity = max(1, sup.slots) + max(0, sup.queue_max)
            st = HEALTH.state(sup.sid)
            views[rid] = ReplicaView(
                rid,
                open=sup.routable,
                alive=sup.alive,
                load=sup.in_flight,
                capacity=capacity,
                health=HEALTH.score(sup.sid),
                # PROBING counts as degraded too: the canary is in
                # flight, not passed — the replica routes last-resort
                # (able to take the probe plus overflow) until the
                # verdict readmits it to PROBATION.
                degraded=(st in (DEGRADED, PROBING)),
                quarantined=(st == QUARANTINED),
            )
            # Quarantined replicas only come back via a canary probe:
            # allow_probe is single-flight with exponential dwell, so at
            # most one cheap ping is in flight per quarantined replica.
            if st == QUARANTINED and sup.alive and HEALTH.allow_probe(sup.sid):
                self._spawn_canary(sup)
        return views

    def _spawn_canary(self, sup: SessionSupervisor) -> None:
        """Probe a quarantined replica with a cheap ping; report verdict."""

        async def _probe() -> None:
            ok = await sup.canary()
            HEALTH.record_probe(sup.sid, ok)

        try:
            task = asyncio.ensure_future(_probe())
        except RuntimeError:
            # No running loop (sync status path) — release the probe slot
            # WITHOUT a verdict so the next pump retries: no probe ran,
            # so nothing may readmit OR lengthen the quarantine dwell.
            HEALTH.release_probe(sup.sid)
            return
        self._pump_tasks.add(task)
        task.add_done_callback(
            lambda t: (
                self._pump_tasks.discard(t),
                t.cancelled() or t.exception(),
            )
        )

    def status(self) -> dict[str, Any]:
        """The set's contribution to operator views."""
        decisions = sorted(self.decision_s)
        p50 = decisions[len(decisions) // 2] if decisions else 0.0
        return {
            "name": self.name,
            "state": self.state,
            **({"suspended": True} if self.suspended else {}),
            "replicas": {
                rid: sup.status() for rid, sup in self._replicas.items()
            },
            "in_flight": self.in_flight,
            "served": self.served,
            "reconnects": self.reconnects,
            "queued": self.router.queued,
            "sticky": self.router.sticky_count(),
            **(
                {"adapters": self.adapter_residency()}
                if any(s.adapters for s in self._replicas.values())
                else {}
            ),
            "router_decision_p50_ms": round(p50 * 1e3, 4),
            "hedge": {
                "enabled": self._hedge_enabled,
                "issued": self._hedge_issued,
                "wins": self._hedge_wins,
                "threshold_s": round(self._hedge_threshold_s(), 4),
            },
        }

    def _publish_replica_states(self) -> None:
        counts = {state: 0 for state in _REPLICA_STATES}
        for sup in self._replicas.values():
            counts[sup.state] = counts.get(sup.state, 0) + 1
        for state in _REPLICA_STATES:
            SERVE_REPLICAS.labels(set=self.name, state=state).set(
                counts[state]
            )

    # -- open / placement ---------------------------------------------------

    async def _open(self) -> "ReplicaSet":
        with Span("serve.replica_set_open", {"set": self.name}):
            self._payload = await asyncio.to_thread(
                cloudpickle.dumps, self.factory
            )
            self._digest = bytes_digest(self._payload)
            opened = await asyncio.gather(
                *(self._open_replica() for _ in range(self.replicas_wanted)),
                return_exceptions=True,
            )
        failures = [r for r in opened if isinstance(r, BaseException)]
        if len(failures) == len(opened):
            raise ServeError(
                f"replica set {self.name}: every replica open failed"
            ) from failures[0]
        for failure in failures:
            app_log.warning(
                "replica set %s: a replica failed to open (%r); "
                "continuing degraded", self.name, failure,
            )
        if self._router_queue_max is None:
            # Default admission bound: the whole set's worker-side
            # capacity again as router backlog — past that, shedding is
            # the honest verdict (same rationale as the worker queue).
            total = sum(
                view.capacity for view in self._views().values()
            )
            self.router.set_queue_max(max(1, total))
        else:
            self.router.set_queue_max(self._router_queue_max)
        self._publish_replica_states()
        journal_mod.record(
            "replica_set", name=self.name, replicas=self.replicas_wanted
        )
        obs_events.emit(
            "serve.replica_set_opened",
            set=self.name,
            replicas=len(self._replicas),
            wanted=self.replicas_wanted,
        )
        return self

    def _rank_targets(self) -> list[tuple[Any, Any]]:
        """Placement order for the next replica.

        Spread first (fewest replicas of THIS set already on the
        target); under ``prefer_stable`` non-preemptible pools beat spot
        ones next (SLO-critical serving pins to capacity that will not
        be reclaimed — ahead even of staging affinity: re-staging a
        factory is cheap, losing a replica mid-burn is not); then the
        serving analog of fn-digest affinity: a target whose gang
        already holds the factory's CAS digest re-opens with zero
        staging, then warm gangs over cold, then free pool slots.
        """
        assigned: dict[int, int] = {}
        for executor, _pool in self._placements.values():
            assigned[id(executor)] = assigned.get(id(executor), 0) + 1

        def rank(entry: tuple[Any, Any]):
            executor, pool = entry
            # Pool targets go through the Pool's own probe (it guards
            # cold/stub executors); bare executors are probed directly.
            holds = getattr(
                pool if pool is not None else executor,
                "holds_serve_digest", None,
            )
            affinity = False
            if holds is not None:
                try:
                    affinity = bool(holds(self._digest))
                except Exception:  # noqa: BLE001 - ranking is best-effort
                    affinity = False
            # getattr: unit tests build bare sets via __new__.
            spot = bool(
                getattr(self, "prefer_stable", False)
                and pool is not None
                and getattr(pool, "preemptible", False)
            )
            warm = bool(getattr(executor, "is_warm", False))
            free = pool.free_slots if pool is not None else 0
            return (
                assigned.get(id(executor), 0),
                spot,
                not affinity,
                not warm,
                -free,
            )

        return sorted(self._targets, key=rank)

    async def _open_replica(self) -> SessionSupervisor:
        index = self._next_replica
        self._next_replica += 1
        replica_id = f"r{index}"
        executor, pool = self._rank_targets()[0]
        self._placements[replica_id] = (executor, pool)
        supervisor = SessionSupervisor(
            executor,
            sid=f"{self.name}:{replica_id}",
            pool=pool,
            replica_of=(self.name, replica_id),
            on_change=self._on_replica_change,
            on_failed=self._on_replica_failed,
            **self._session_options,
        )
        self._replicas[replica_id] = supervisor
        try:
            assert self._payload is not None
            await supervisor.open(self._payload, self._digest)
        except BaseException:
            self._replicas.pop(replica_id, None)
            self._placements.pop(replica_id, None)
            raise
        journal_mod.record(
            "replica", set=self.name, sid=supervisor.sid, replica=index
        )
        self._publish_replica_states()
        return supervisor

    # -- requests -----------------------------------------------------------

    async def request(
        self,
        prompt,
        params: dict | None = None,
        deadline_s: float | None = None,
        tenant: str = "",
        sticky: str = "",
    ) -> ServeRequest:
        """Submit one request through the router; returns its stream.

        ``sticky`` names the caller's multi-turn session: its requests
        pin to one replica until ``sticky_ttl_s`` of silence (or the
        replica's death).  A request the router cannot place immediately
        waits in the per-tenant DRR queue and dispatches as lanes free —
        its stream just starts later.  A full router queue sheds with
        :class:`ServeRequestRejected` (``serve_admission_shed``).

        A set scaled to zero (``scale_to(0)``) re-warms here: the first
        request after the idle teardown waits out any still-draining
        suspension (mid-teardown requests are never dropped), opens a
        fresh replica, and streams normally — cold-start latency, no
        error.

        ``params`` ride to the engine verbatim; beyond the sampling
        knobs this includes the per-request ``quality`` selector
        (``"exact"`` or a decode-mode name — see
        ``models.serve.ContinuousEngine``): engines with lane groups
        route the request to the matching quantized lane, and ANY
        refusal (unknown name, unbuilt group) falls back to the
        bit-exact fp lane rather than rejecting.
        """
        if self._closed:
            raise ServeError(f"replica set {self.name} is closed")
        if not any(s.alive for s in self._replicas.values()):
            if self._suspended:
                await self._ensure_live()
            else:
                raise ServeError(
                    f"replica set {self.name} has no live replicas"
                )
        self._next_rid += 1
        rid = f"{self.name}-r{self._next_rid}"
        request = ServeRequest(
            rid,
            [int(t) for t in prompt],
            params,
            (
                self._default_deadline_s()
                if deadline_s is None
                else deadline_s
            ),
            tenant,
        )
        request.sticky = sticky
        await self._prepare_request(request)
        item = WorkItem(
            fn=None, args=(), kwargs={},
            task_metadata={
                "request": request, "sticky": sticky,
                "prefix_key": request.prefix_key,
                "adapter": str((params or {}).get("adapter") or ""),
            },
            tenant=tenant or DEFAULT_TENANT,
        )
        t0 = time.perf_counter()
        try:
            self.router.submit(item)
        except QueueFullError as err:
            SERVE_ROUTER_DECISIONS_TOTAL.labels(outcome="shed").inc()
            rejection = ServeRequestRejected(
                rid, "serve_admission_shed", str(err)
            )
            request._fail(rejection)
            raise rejection from None
        if self.suspended:
            # A scale_to(0) drained the set between the alive-check at
            # the top and this submit — the ``_prepare_request`` hook is
            # a real suspension point (a disaggregated prefill round
            # trip) — and the drain's own queued-demand check ran before
            # this item existed.  Re-warm NOW rather than leaving the
            # item in a queue nothing pumps; a failed re-warm unqueues
            # and fails it loudly.
            try:
                await self._ensure_live()
            except BaseException:
                self.router.remove(
                    lambda it: it.task_metadata.get("request") is request
                )
                if not request.done:
                    request._fail(ServeError(
                        f"replica set {self.name}: re-warm failed"
                    ))
                raise
        assignments = self.router.pump(self._views())
        elapsed = time.perf_counter() - t0
        self.decision_s.append(elapsed)
        SERVE_ROUTER_DECISION_SECONDS.observe(elapsed)
        placed = {id(i) for i, _, _ in assignments}
        # The router hop is its own waterfall row (distinct from the
        # tiling ``route`` segment, which also absorbs DRR queue time):
        # a request that waited out a full queue shows a long segment
        # but a short hop, and the difference IS the diagnosis.
        record_span(
            "serve.router_hop",
            trace_id=request.span.trace_id,
            parent_id=request.span.span_id,
            start_ts=time.time() - elapsed,
            duration_s=elapsed,
            attributes={
                "rid": rid,
                "outcome": (
                    "placed" if id(item) in placed else "queued"
                ),
            },
        )
        if id(item) not in placed:
            SERVE_ROUTER_DECISIONS_TOTAL.labels(outcome="queued").inc()
        await self._dispatch_assignments(assignments)
        self._requests_issued += 1
        if self._hedge_eligible(request):
            task = asyncio.ensure_future(self._hedge_watch(request))
            self._pump_tasks.add(task)
            task.add_done_callback(
                lambda t: (
                    self._pump_tasks.discard(t),
                    t.cancelled() or t.exception(),
                )
            )
        return request

    # -- multi-adapter registry ---------------------------------------------

    def adapter_residency(self) -> dict[str, list[str]]:
        """adapter name -> replica ids whose engine holds it resident."""
        residency: dict[str, list[str]] = {}
        for rid, sup in self._replicas.items():
            for name in sup.adapters:
                residency.setdefault(name, []).append(rid)
        return {name: sorted(rids) for name, rids in residency.items()}

    async def attach_adapter(
        self,
        name: str,
        payload: Any = None,
        *,
        path: str = "",
        digest: str = "",
        rank: int | None = None,
        alpha: float = 16.0,
        replicas: int = 0,
        timeout_s: float | None = None,
    ) -> dict[str, dict]:
        """Attach a named adapter across the set, spread by load.

        ``replicas=0`` (default) attaches everywhere — any replica can
        then serve the adapter and routing stays unconstrained.
        ``replicas=N`` attaches to only the N LEAST-LOADED open replicas
        (capacity consolidation: a long-tail adapter does not need every
        engine's bank slots), and the router learns the residency sites
        so requests naming the adapter place onto — and wait for — the
        replicas that actually hold it.  Returns replica id -> worker
        ack; a replica that refuses (bank full) is skipped with its
        error in the map, not fatal, as long as at least one attach
        lands.
        """
        open_replicas = [
            (rid, sup) for rid, sup in self._replicas.items()
            if sup.routable
        ]
        if not open_replicas:
            raise ServeError(
                f"replica set {self.name} has no open replica to attach "
                f"adapter {name!r} to"
            )
        open_replicas.sort(key=lambda pair: pair[1].in_flight)
        count = int(replicas) if replicas else len(open_replicas)
        chosen = open_replicas[:max(1, count)]
        spread = bool(replicas) and len(chosen) < len(open_replicas)
        acks: dict[str, dict] = {}
        landed = 0
        for rid, sup in chosen:
            try:
                acks[rid] = await sup.attach_adapter(
                    name, payload, path=path, digest=digest, rank=rank,
                    alpha=alpha, timeout_s=timeout_s,
                )
                landed += 1
                if spread:
                    self.router.record_adapter_site(str(name), rid)
            except BaseException as err:
                if isinstance(err, asyncio.CancelledError):
                    raise
                acks[rid] = {"error": repr(err)}
                app_log.warning(
                    "adapter %r attach on replica %s failed: %r",
                    name, rid, err,
                )
        if not landed:
            raise ServeError(
                f"adapter {name!r} attached to no replica of {self.name}: "
                f"{acks}"
            )
        if not spread:
            # Resident everywhere that matters: lift any stale routing
            # constraint from a previous partial attachment.
            self.router.drop_adapter_site(str(name))
        return acks

    async def detach_adapter(
        self, name: str, timeout_s: float = 30.0
    ) -> dict[str, dict]:
        """Detach a named adapter from every replica holding it."""
        acks: dict[str, dict] = {}
        for rid, sup in list(self._replicas.items()):
            if name not in sup.adapters:
                continue
            try:
                acks[rid] = await sup.detach_adapter(
                    name, timeout_s=timeout_s
                )
            except BaseException as err:
                if isinstance(err, asyncio.CancelledError):
                    raise
                acks[rid] = {"error": repr(err)}
        self.router.drop_adapter_site(str(name))
        return acks

    async def _prepare_request(self, request: ServeRequest) -> None:
        """Pre-dispatch hook: a disaggregated set runs the prefill tier
        here (attaching the KV bundle and prefix key) before the router
        ever sees the request.  The base set does nothing."""

    def _default_deadline_s(self) -> float:
        for sup in self._replicas.values():
            return sup.default_deadline_s
        return 0.0

    async def _dispatch_assignments(
        self, assignments: list[tuple[WorkItem, str, str]]
    ) -> None:
        for item, replica_id, outcome in assignments:
            SERVE_ROUTER_DECISIONS_TOTAL.labels(outcome=outcome).inc()
            request = item.task_metadata["request"]
            supervisor = self._replicas.get(replica_id)
            if supervisor is None or not supervisor.alive:
                self._reroute(request, item.task_metadata.get("sticky", ""))
                continue
            try:
                await supervisor.submit(
                    request, fail_on_error=False, wait_ready=False,
                )
            except Exception as err:  # noqa: BLE001 - re-route, not fail
                if request.done:
                    continue
                app_log.debug(
                    "replica %s submit failed (%s); re-routing %s",
                    replica_id, err, request.rid,
                )
                self._reroute(
                    request, item.task_metadata.get("sticky", "")
                )

    def _reroute(self, request: ServeRequest, sticky: str = "") -> None:
        """Queue a request again after its replica died under it.

        The sticky key defaults to the one the request was submitted
        with, so a drain-on-death re-route keeps (or re-establishes) the
        caller's pin on whatever survivor takes the stream.
        """
        sticky = sticky or request.sticky
        if request.done:
            return
        live = [s for s in self._replicas.values() if s.alive]
        if not live or self._closed:
            request._fail(ServeError(
                f"replica set {self.name}: no live replica to re-route "
                f"{request.rid} onto"
            ))
            return
        SERVE_ROUTER_DECISIONS_TOTAL.labels(outcome="failover").inc()
        item = WorkItem(
            fn=None, args=(), kwargs={},
            task_metadata={
                "request": request, "sticky": sticky,
                "prefix_key": request.prefix_key,
                "adapter": str(
                    (request.params or {}).get("adapter") or ""
                ),
            },
            tenant=request.tenant or DEFAULT_TENANT,
        )
        try:
            self.router.submit(item)
        except QueueFullError as err:
            request._fail(ServeRequestRejected(
                request.rid, "serve_admission_shed", str(err)
            ))
            return
        self._schedule_pump()

    # -- tail-latency hedging -----------------------------------------------

    def _hedge_eligible(self, request: ServeRequest) -> bool:
        """Only deterministic, un-pinned requests may hedge: a sampled
        (temperature>0) stream would diverge between arms, and a sticky
        request's KV/session locality belongs to its pinned replica."""
        if not self._hedge_enabled or request.sticky:
            return False
        params = request.params or {}
        if params.get("temperature"):
            return False
        return len([s for s in self._replicas.values() if s.alive]) > 1

    def _hedge_threshold_s(self) -> float:
        """Adaptive trigger: the set's recent TTFT percentile, floored at
        COVALENT_TPU_HEDGE_MIN_S.  With too few samples the threshold is
        deliberately conservative (1s) — warm-up latency is not a gray
        failure."""
        ring = sorted(self._ttft_ring)
        if len(ring) < 8:
            return max(self._hedge_min_s, 1.0)
        k = min(
            len(ring) - 1,
            int(len(ring) * self._hedge_percentile / 100.0),
        )
        return max(self._hedge_min_s, ring[k])

    async def _hedge_watch(self, request: ServeRequest) -> None:
        """Arm the hedge timer for one request: if no first token lands
        within the adaptive threshold, speculatively re-issue it on the
        next-healthiest replica.  Both arms feed the TTFT ring."""
        threshold = self._hedge_threshold_s()
        t0 = time.monotonic()
        try:
            await asyncio.wait_for(request.first_token.wait(), threshold)
        except asyncio.TimeoutError:
            if not request.done and not self._closed:
                await self._launch_hedge(request)
        finally:
            await request.first_token.wait()
            self._ttft_ring.append(
                request.ttft_s
                if request.ttft_s is not None
                else time.monotonic() - t0
            )

    async def _launch_hedge(self, request: ServeRequest) -> None:
        """Issue the speculative second arm and arbitrate the winner.

        The SAME ServeRequest is submitted to a second supervisor: both
        arms feed one token buffer through the exactly-once idx splice,
        so duplicate chunks drop and the stream is byte-identical no
        matter which arm wins.  The first arm to deliver a token is the
        winner (``request.served_by``); the loser's lane is released
        with a fire-and-forget ``serve_cancel`` (``abandon``)."""
        if self._hedge_issued + 1 > max(
            1.0, self._requests_issued * self._hedge_budget_pct / 100.0
        ):
            SERVE_HEDGES_TOTAL.labels(outcome="budget").inc()
            return
        primary = next(
            (
                sup for sup in self._replicas.values()
                if request.rid in sup._requests
            ),
            None,
        )
        views = self._views()
        candidates = [
            sup for rid, sup in self._replicas.items()
            if sup.routable
            and sup is not primary
            and not views[rid].quarantined
            and views[rid].capacity - views[rid].load > 0
        ]
        if not candidates:
            SERVE_HEDGES_TOTAL.labels(outcome="no_target").inc()
            return
        candidates.sort(
            key=lambda sup: (
                HEALTH.rank(sup.sid),
                -HEALTH.score(sup.sid),
                sup.in_flight,
            )
        )
        target = candidates[0]
        request.hedged = True
        self._hedge_issued += 1
        SERVE_HEDGES_TOTAL.labels(outcome="launched").inc()
        obs_events.emit(
            "serve.hedge",
            set=self.name,
            rid=request.rid,
            primary=(primary.sid if primary is not None else ""),
            target=target.sid,
        )
        try:
            await target.submit(
                request, fail_on_error=False, wait_ready=False
            )
        except BaseException:
            # The hedge arm failing to launch is not the request's
            # problem — the primary is still streaming.
            self._hedge_issued -= 1
            SERVE_HEDGES_TOTAL.labels(outcome="no_target").inc()
            return
        await request.first_token.wait()
        winner = request.served_by
        if winner == target.sid:
            self._hedge_wins += 1
            SERVE_HEDGES_TOTAL.labels(outcome="won").inc()
            if primary is not None:
                primary.abandon(request.rid)
                # The winner's TTFT lands on the winner's health record;
                # the primary would otherwise accrue NO signal from a
                # request that hedged away.  Charge it the censored
                # observation (it had not delivered by now — a lower
                # bound on its true TTFT) plus a straggler fault, so a
                # replica losing hedge after hedge degrades instead of
                # staying invisible to the health monitor.
                if request.t_dispatched is not None:
                    HEALTH.record_latency(
                        primary.sid,
                        time.monotonic() - request.t_dispatched,
                        group=self.name,
                    )
                HEALTH.record_fault(
                    primary.sid, label="hedge_lost", group=self.name
                )
        else:
            SERVE_HEDGES_TOTAL.labels(outcome="lost").inc()
            target.abandon(request.rid)

    # -- health hooks (supervisor callbacks, event-loop context) ------------

    def _on_replica_change(self, _supervisor: SessionSupervisor) -> None:
        self._publish_replica_states()
        if not self._closed and self.router.queued:
            self._schedule_pump()

    def _on_replica_failed(
        self, supervisor: SessionSupervisor, failure: BaseException
    ) -> bool:
        """Drain-on-death: a replica past its retry budget hands its
        in-flight requests here; survivors absorb them exactly-once (the
        requests keep their token high-water marks, so the fresh
        replica's from-zero streams splice with no duplicate and no
        hole).  Returns True — the supervisor must not fail them."""
        replica_id = (
            supervisor.replica_of[1]
            if supervisor.replica_of
            else supervisor.sid
        )
        detached = supervisor.detach_requests()
        self.router.forget_replica(replica_id)
        obs_events.emit(
            "serve.replica_failed",
            set=self.name,
            replica=replica_id,
            error=repr(failure),
            rerouted=len(detached),
        )
        for request in detached:
            self._reroute(request)
        if not any(s.alive for s in self._replicas.values()):
            # The LAST replica just died: nothing will ever pump the
            # router queue again, so its waiters fail now with the cause
            # instead of hanging until the set closes.
            for item in self.router.drain():
                request = item.task_metadata.get("request")
                if request is not None and not request.done:
                    request._fail(ServeError(
                        f"replica set {self.name} has no live replicas: "
                        f"{failure}"
                    ))
        self._publish_replica_states()
        return True

    def _schedule_pump(self) -> None:
        task = asyncio.ensure_future(self._pump())
        self._pump_tasks.add(task)
        task.add_done_callback(
            lambda t: (
                self._pump_tasks.discard(t),
                None if t.cancelled() else t.exception(),
            )
        )

    async def _pump(self) -> None:
        if self._closed:
            return
        t0 = time.perf_counter()
        assignments = self.router.pump(self._views())
        if assignments:
            elapsed = time.perf_counter() - t0
            self.decision_s.append(elapsed / len(assignments))
            SERVE_ROUTER_DECISION_SECONDS.observe(
                elapsed / len(assignments)
            )
            await self._dispatch_assignments(assignments)

    # -- scaling ------------------------------------------------------------

    async def scale_to(self, replicas: int) -> int:
        """Grow or shrink the live replica count; returns the new count.

        Scale-up opens fresh sessions on affinity-ranked targets
        (concurrently); scale-down retires the least-loaded replicas —
        each stops receiving new work, drain-closes (the worker finishes
        every admitted and queued request first), releases its fleet
        capacity pin, and reaps its per-session AND per-replica metric
        series through the supervisor's ``_drop_live``.

        ``scale_to(0)`` is **scale-to-zero**: every replica drain-closes
        and the set suspends — the next :meth:`request` (or a later
        scale-up) re-warms it from the staged factory payload.  A request
        racing the teardown waits for the drain and re-warms; it is
        never dropped, and its stream is exactly-once like any other.
        """
        if self._closed:
            raise ServeError(f"replica set {self.name} is closed")
        replicas = int(replicas)
        if replicas < 0:
            raise ValueError(f"replicas must be >= 0, got {replicas}")
        async with self._scale_lock:
            count = await self._scale_locked(replicas)
        journal_mod.record(
            "replica_set", name=self.name, replicas=self.replicas_wanted
        )
        return count

    async def _scale_locked(self, replicas: int) -> int:
        live = {
            rid: sup for rid, sup in self._replicas.items() if sup.alive
        }
        if replicas == 0:
            # Remember the width a demand-triggered resume restores; the
            # flag is up BEFORE the drain so a request arriving
            # mid-teardown queues behind the lock and re-warms after.
            self._resume_to = max(1, min(self.replicas_wanted, len(live)))
            self._suspended = True
            for rid in list(live):
                await self._retire_replica(rid)
            self.replicas_wanted = 0
            if self.router.queued:
                # Demand slipped in while the drain held the lock (a
                # request that still saw a live replica queued into the
                # router, whose items only worker-ADMITTED drains
                # finish): a suspended set never pumps, so those waiters
                # would hang until unrelated new traffic re-warmed it.
                # Queued requests ARE demand — re-warm immediately
                # instead of suspending over them.  A re-warm that opens
                # NOTHING fails the stranded waiters loudly (the set
                # stays suspended and resumable).
                revived = await self._scale_locked(max(1, self._resume_to))
                if revived == 0:
                    self._suspended = True
                    for item in self.router.drain():
                        request = item.task_metadata.get("request")
                        if request is not None and not request.done:
                            request._fail(ServeError(
                                f"replica set {self.name}: re-warm "
                                f"failed with queued requests"
                            ))
                return revived
            self._publish_replica_states()
            obs_events.emit(
                "serve.replica_set_suspended",
                set=self.name,
                resume_to=self._resume_to,
            )
            return 0
        resumed = self._suspended
        self._suspended = False
        if replicas > len(live):
            grow = replicas - len(live)
            results = await asyncio.gather(
                *(self._open_replica() for _ in range(grow)),
                return_exceptions=True,
            )
            for failure in results:
                if isinstance(failure, BaseException):
                    app_log.warning(
                        "replica set %s scale-up open failed: %r",
                        self.name, failure,
                    )
            self._schedule_pump()
        elif replicas < len(live):
            victims = sorted(
                live, key=lambda rid: live[rid].in_flight
            )[: len(live) - replicas]
            for rid in victims:
                await self._retire_replica(rid)
        self.replicas_wanted = replicas
        self._publish_replica_states()
        now_live = len([
            s for s in self._replicas.values() if s.alive
        ])
        if resumed and now_live == 0:
            # Every resume open failed: stay suspended so the NEXT
            # demand retries the re-warm instead of hitting a dead,
            # unresumable set.
            self._suspended = True
        elif resumed:
            obs_events.emit(
                "serve.replica_set_resumed",
                set=self.name,
                replicas=now_live,
            )
        obs_events.emit(
            "serve.replica_set_scaled",
            set=self.name,
            replicas=now_live,
        )
        return now_live

    async def _ensure_live(self) -> None:
        """Re-warm a suspended set on first demand (scale-to-zero exit).

        Serialized behind the scale lock: a request that raced a
        still-draining ``scale_to(0)`` waits here for the drain, then
        re-opens ``_resume_to`` replicas and proceeds.  A re-warm that
        opens nothing raises (the caller's request fails loudly instead
        of queueing into a set nothing will ever pump); the set stays
        suspended so the next demand retries.
        """
        async with self._scale_lock:
            if self._closed:
                raise ServeError(f"replica set {self.name} is closed")
            if any(s.alive for s in self._replicas.values()):
                return
            if not self._suspended:
                raise ServeError(
                    f"replica set {self.name} has no live replicas"
                )
            revived = await self._scale_locked(max(1, self._resume_to))
            if revived == 0:
                raise ServeError(
                    f"replica set {self.name}: scale-to-zero re-warm "
                    f"failed to open a replica"
                )

    async def _retire_replica(self, replica_id: str) -> None:
        supervisor = self._replicas.pop(replica_id, None)
        self._placements.pop(replica_id, None)
        if supervisor is None:
            return
        self.router.forget_replica(replica_id)
        journal_mod.record(
            "replica", set=self.name, sid=supervisor.sid, state="closed"
        )
        try:
            await supervisor.close()
        except Exception as err:  # noqa: BLE001 - teardown is best-effort
            app_log.warning(
                "replica %s:%s close failed: %s",
                self.name, replica_id, err,
            )

    # -- close --------------------------------------------------------------

    async def close(self, timeout: float = 30.0) -> dict:
        """Drain and close every replica; returns merged closed stats."""
        if self._closed:
            return {"served": self.served}
        self._closed = True
        for task in list(self._pump_tasks):
            task.cancel()
        for item in self.router.drain():
            request = item.task_metadata.get("request")
            if request is not None and not request.done:
                request._fail(
                    ServeError(f"replica set {self.name} closed")
                )
        served = 0
        closes = await asyncio.gather(
            *(
                sup.close(timeout)
                for sup in list(self._replicas.values())
            ),
            return_exceptions=True,
        )
        for closed in closes:
            if isinstance(closed, dict):
                served += int(closed.get("served") or 0)
        for state in _REPLICA_STATES:
            SERVE_REPLICAS.remove(set=self.name, state=state)
        obs_events.emit(
            "serve.replica_set_closed", set=self.name, served=served
        )
        return {"served": served}


async def open_replica_set(
    targets: Any,
    factory: Any,
    *,
    replicas: int | None = None,
    name: str = "",
    sticky_ttl_s: float | None = None,
    router_queue_max: int | None = None,
    tenant_weights: dict[str, float] | None = None,
    prefer_stable: bool = False,
    **session_options: Any,
) -> ReplicaSet:
    """Open ``replicas`` sessions of one factory behind a routing front.

    ``targets`` is a list of fleet ``Pool``\\ s and/or ``TPUExecutor``\\ s
    (one entry also works); ``replicas`` defaults to ``len(targets)``.
    Replicas place onto targets spread-first, then by factory-digest
    affinity / warmth / free slots; a pool-backed replica pins one of its
    pool's capacity slots for its lifetime.  ``session_options`` are the
    per-session knobs ``open_session`` takes (``queue_max``,
    ``default_deadline_s``, ``stats_interval_s``, ``open_timeout_s``,
    ``retries``).
    """
    if not isinstance(targets, (list, tuple)):
        targets = [targets]
    replica_set = ReplicaSet(
        list(targets),
        factory,
        replicas=replicas,
        name=name,
        sticky_ttl_s=sticky_ttl_s,
        router_queue_max=router_queue_max,
        tenant_weights=tenant_weights,
        prefer_stable=prefer_stable,
        **session_options,
    )
    return await replica_set._open()
