"""Disaggregated prefill/decode serving: a KV transfer plane over replicas.

Prefill is compute-bound (one big batched pass over the prompt), decode
is memory-bound (one tiny step per token, thousands of times); a replica
doing both lets long-prompt admissions head-of-line block every
interactive stream sharing its engine loop (the Gemma-on-TPU serving
study in PAPERS.md grounds the split's throughput/latency methodology).
A :class:`DisaggregatedSet` separates the phases across the replica set
it already is:

* **Prefill tier** — the first ``prefill_replicas`` members open on
  prefill-ranked pools (``PoolSpec.role == "prefill"``) and never
  receive router traffic.  A long-prompt request runs
  ``engine.prefill_only`` there: the admission prefill's exact
  computation, packaged as a serializable **KV bundle** (cache lane +
  cursor + first token + rng/sampling state).
* **KV transfer through the CAS** — the bundle is content-addressed
  (sha256) end to end: the worker announces its digest, the dispatcher
  re-hashes the received bytes before trusting them, and the decode
  worker verifies again before unpickling.  Transfer rides a raw binary
  frame body on the agent channel when the decode channel negotiated
  frames (the gang-local fast path), or a CAS put — digest-named,
  single-flighted, deduped across identical prompts — referenced by
  path across pools.
* **Decode tier** — the router (sticky > prefix-affinity > least-loaded,
  per-tenant DRR order, unchanged) places the request on a decode
  replica whose engine scatters the imported lane straight into a slot
  (``admit_from_kv``) and goes directly to token generation.  Greedy
  streams are bit-identical to the non-disaggregated path (oracle-
  asserted in ``tests/test_continuous.py``).
* **Degrade, never error** — a dead/slow prefill tier, a digest
  mismatch, a torn transfer, or an engine refusing the bundle all fall
  back to a full prefill on the decode replica; the caller's stream is
  byte-identical either way, only slower.  Short prompts
  (< ``min_prompt_tokens``) skip the KV road entirely.
* **Quantized lanes compose** — request ``params`` (the per-request
  ``quality`` knob included) ride the prefill round trip verbatim, so a
  ``quality="kv_quant"`` request prefills on the prefill tier's matching
  lane group and ships an **int8 KV bundle** (~2-4x fewer bytes).  The
  bundle carries a quantization fingerprint next to the sampling
  fingerprint; a decode replica with no matching lane group refuses it
  and the request degrades to a full prefill there — same
  byte-identical-stream contract as every other degrade road.

``COVALENT_TPU_SERVE_DISAGG=0`` routes everything direct (kill switch);
``COVALENT_TPU_SERVE_DISAGG_MIN_PROMPT`` / ``_KV_TIMEOUT_S`` /
``_PREFILL`` tune the classification threshold, the prefill round-trip
budget, and the default prefill-tier width.
"""

from __future__ import annotations

import asyncio
import collections
import hashlib
import os
import time
import uuid
from typing import Any

from ..cache import prune_cas_dir
from ..obs import events as obs_events
from ..obs.trace import context_of
from ..utils.log import app_log
from .metrics import (
    SERVE_DISAGG_REQUESTS_TOTAL,
    SERVE_KV_TRANSFER_BYTES_TOTAL,
    SERVE_KV_TRANSFER_SECONDS,
    SERVE_KV_TRANSFERS_TOTAL,
)
from .replicas import ReplicaSet
from .supervisor import (
    ServeError,
    ServeRequest,
    SessionSupervisor,
    _env_number,
)

__all__ = [
    "DisaggregatedSet",
    "open_disaggregated_set",
]


def _disagg_enabled() -> bool:
    return os.environ.get(
        "COVALENT_TPU_SERVE_DISAGG", ""
    ).strip().lower() not in ("0", "off", "false", "no")


def _prefix_key(prompt: list) -> str:
    """Router affinity key: digest of the prompt's reusable prefix (all
    but the last token — exactly the prefix a repeated prompt hits in
    the engine's tree)."""
    if len(prompt) < 2:
        return ""
    return hashlib.sha256(
        (",".join(str(int(t)) for t in prompt[:-1])).encode()
    ).hexdigest()


class DisaggregatedSet(ReplicaSet):
    """A :class:`~.replicas.ReplicaSet` split into prefill and decode
    tiers, connected by CAS-addressed KV bundles.

    Build through :func:`open_disaggregated_set`.  The request surface
    is the replica set's unchanged; classification (prompt length vs
    ``min_prompt_tokens``), the prefill round trip, digest verification,
    and the degrade-to-full-prefill policy all run inside
    :meth:`_prepare_request` before the router sees the request.
    """

    def __init__(
        self,
        targets: list[Any],
        factory: Any,
        *,
        decode_replicas: int | None = None,
        prefill_replicas: int | None = None,
        min_prompt_tokens: int | None = None,
        kv_timeout_s: float | None = None,
        **set_options: Any,
    ) -> None:
        self.prefill_replicas = int(
            prefill_replicas
            if prefill_replicas is not None
            else _env_number("COVALENT_TPU_SERVE_DISAGG_PREFILL", 1, int)
        )
        if self.prefill_replicas < 1:
            raise ValueError(
                f"prefill_replicas must be >= 1, got {self.prefill_replicas}"
            )
        decode = int(
            decode_replicas
            if decode_replicas is not None
            else max(1, len(targets) - self.prefill_replicas)
        )
        if decode < 1:
            raise ValueError(f"decode_replicas must be >= 1, got {decode}")
        self.decode_replicas = decode
        self.min_prompt_tokens = int(
            min_prompt_tokens
            if min_prompt_tokens is not None
            else _env_number(
                "COVALENT_TPU_SERVE_DISAGG_MIN_PROMPT", 64, int
            )
        )
        self.kv_timeout_s = float(
            kv_timeout_s
            if kv_timeout_s is not None
            else _env_number("COVALENT_TPU_SERVE_DISAGG_KV_TIMEOUT_S", 30.0)
        )
        self.enabled = _disagg_enabled()
        #: replica id -> "prefill" | "decode".
        self._role_of: dict[str, str] = {}
        self._opening_role = ""
        #: prefill-role opens currently in flight (role is assigned by
        #: tier DEFICIT, not by replica index: a failed initial open
        #: must not permanently lose the prefill tier — the next open,
        #: scale-up included, re-fills it).
        self._prefill_opening = 0
        #: prefill work currently in flight per prefill replica id.
        self._prefill_load: collections.Counter = collections.Counter()
        #: transfer accounting for ``status()`` (the metrics' raw feed).
        self.kv_bytes_total = 0
        self.kv_transfer_s: collections.deque = collections.deque(
            maxlen=4096
        )
        self.requests_by_path: collections.Counter = collections.Counter()
        super().__init__(
            targets, factory,
            replicas=decode + self.prefill_replicas,
            **set_options,
        )

    # -- placement (role-aware) --------------------------------------------

    def _rank_targets(self) -> list[tuple[Any, Any]]:
        """Base affinity/warmth/spread ranking, re-sorted so targets
        whose pool declared the tier's role come first and opposite-role
        pools last (role-less pools stay neutral)."""
        ranked = super()._rank_targets()
        role = self._opening_role
        if not role:
            return ranked

        def mismatch(entry: tuple[Any, Any]) -> int:
            executor, pool = entry
            target_role = ""
            if pool is not None:
                target_role = str(
                    getattr(getattr(pool, "spec", None), "role", "") or ""
                )
            if not target_role:
                target_role = str(getattr(executor, "serve_role", "") or "")
            if not target_role:
                return 1
            return 0 if target_role == role else 2

        return sorted(ranked, key=mismatch)  # stable within classes

    async def _open_replica(self) -> SessionSupervisor:
        have = self._prefill_opening + sum(
            1 for rid, sup in self._replicas.items()
            if self._role_of.get(rid) == "prefill" and sup.alive
        )
        role = "prefill" if have < self.prefill_replicas else "decode"
        self._opening_role = role
        if role == "prefill":
            self._prefill_opening += 1
        try:
            supervisor = await super()._open_replica()
        finally:
            self._opening_role = ""
            if role == "prefill":
                self._prefill_opening -= 1
        if supervisor.replica_of is not None:
            self._role_of[supervisor.replica_of[1]] = role
        return supervisor

    def _views(self):
        """Router world view: decode replicas only — the prefill tier
        never receives routed decode work."""
        views = super()._views()
        return {
            rid: view for rid, view in views.items()
            if self._role_of.get(rid, "decode") == "decode"
        }

    def _decode_alive(self) -> bool:
        return any(
            sup.alive
            for rid, sup in self._replicas.items()
            if self._role_of.get(rid, "decode") == "decode"
        )

    # -- classification + prefill tier -------------------------------------

    async def request(
        self,
        prompt,
        params: dict | None = None,
        deadline_s: float | None = None,
        tenant: str = "",
        sticky: str = "",
    ) -> ServeRequest:
        if not self._closed and not self._decode_alive():
            raise ServeError(
                f"disaggregated set {self.name} has no live decode replicas"
            )
        return await super().request(
            prompt, params, deadline_s=deadline_s, tenant=tenant,
            sticky=sticky,
        )

    async def _prepare_request(self, request: ServeRequest) -> None:
        """Classify, prefill on the prefill tier, attach the KV bundle.

        Runs BEFORE the router pump, so a disaggregated request reaches
        the decode tier with its prefill already done (and its
        prefix-affinity key set).  Every failure mode lands in the same
        place: ``request.kv`` stays None and the decode replica runs the
        full prefill — never a user-visible error.
        """
        request.prefix_key = _prefix_key(request.prompt)
        if (
            not self.enabled
            or len(request.prompt) < self.min_prompt_tokens
        ):
            self.requests_by_path["direct"] += 1
            SERVE_DISAGG_REQUESTS_TOTAL.labels(path="direct").inc()
            return
        kv = await self._prefill_kv_for(request)
        # Checkpoint even on a failed round trip: the time was spent
        # either way, and the waterfall must attribute it to the prefill
        # hop rather than silently folding it into the route segment.
        request.t_prefill_done = time.monotonic()
        path = "disagg" if kv is not None else "fallback"
        self.requests_by_path[path] += 1
        SERVE_DISAGG_REQUESTS_TOTAL.labels(path=path).inc()
        request.kv = kv

    def _prefill_supervisor(self) -> tuple[str, SessionSupervisor] | None:
        candidates = [
            (rid, sup)
            for rid, sup in self._replicas.items()
            if self._role_of.get(rid) == "prefill" and sup.routable
        ]
        if not candidates:
            return None
        return min(
            candidates, key=lambda entry: self._prefill_load[entry[0]]
        )

    async def _prefill_kv_for(
        self, request: ServeRequest
    ) -> tuple[bytes, str] | None:
        """One prefill-tier round trip: returns ``(bundle, digest)`` or
        None after any failure (counted, evented, degraded)."""
        picked = self._prefill_supervisor()
        if picked is None:
            SERVE_KV_TRANSFERS_TOTAL.labels(outcome="fallback").inc()
            return None
        replica_id, supervisor = picked
        self._prefill_load[replica_id] += 1
        t0 = time.perf_counter()
        try:
            # Outer bound on the WHOLE round trip: prefill_kv's own
            # timeout only covers the serve_kv wait, while a replica
            # caught mid-reconnect blocks in _await_ready — a caller's
            # request must degrade on the KV budget, not wait out a
            # reconnect cycle.
            event = await asyncio.wait_for(
                supervisor.prefill_kv(
                    request.prompt, request.params,
                    rid=f"{request.rid}-kv{uuid.uuid4().hex[:6]}",
                    timeout_s=self.kv_timeout_s,
                    trace=context_of(request.span, rid=request.rid),
                ),
                self.kv_timeout_s + 5.0,
            )
        except Exception as err:  # noqa: BLE001 - degrade, never error
            SERVE_KV_TRANSFERS_TOTAL.labels(outcome="error").inc()
            obs_events.emit(
                "serve.kv_prefill_failed",
                set=self.name,
                replica=replica_id,
                rid=request.rid,
                error=repr(err),
            )
            app_log.debug(
                "disagg %s: prefill for %s failed on %s (%s); degrading "
                "to full prefill", self.name, request.rid, replica_id, err,
            )
            return None
        finally:
            self._prefill_load[replica_id] -= 1
        data = event.get("data_bytes")
        if not isinstance(data, (bytes, bytearray)) or not data:
            SERVE_KV_TRANSFERS_TOTAL.labels(outcome="error").inc()
            return None
        data = bytes(data)
        digest = hashlib.sha256(data).hexdigest()
        announced = str(event.get("digest") or "")
        if announced and digest != announced:
            # The wire (or the worker) handed us bytes that do not match
            # what the prefill engine hashed: a torn transfer.  The
            # decode replica re-prefills from the prompt — correctness
            # never rides an unverified bundle.
            SERVE_KV_TRANSFERS_TOTAL.labels(
                outcome="digest_mismatch"
            ).inc()
            obs_events.emit(
                "serve.kv_digest_mismatch",
                set=self.name,
                replica=replica_id,
                rid=request.rid,
                announced=announced[:12],
                received=digest[:12],
            )
            return None
        elapsed = time.perf_counter() - t0
        SERVE_KV_TRANSFERS_TOTAL.labels(outcome="ok").inc()
        SERVE_KV_TRANSFER_BYTES_TOTAL.inc(len(data))
        SERVE_KV_TRANSFER_SECONDS.observe(elapsed)
        self.kv_bytes_total += len(data)
        self.kv_transfer_s.append(elapsed)
        # Off the request path: the mirror is an audit/staging artifact
        # (the frames road never reads it back), so a multi-MB disk
        # write must not tax this request's TTFT.
        mirror = asyncio.ensure_future(asyncio.to_thread(
            self._mirror_to_cas, supervisor, data, digest
        ))
        mirror.add_done_callback(
            lambda t: None if t.cancelled() else t.exception()
        )
        return data, digest

    @staticmethod
    def _mirror_to_cas(
        supervisor: SessionSupervisor, data: bytes, digest: str
    ) -> None:
        """Content-addressed local CAS copy of every verified bundle (the
        artifact the cross-pool staging road ships from), byte-bounded by
        the executor's ``cas_max_bytes`` LRU prune."""
        try:
            root = os.path.join(supervisor.executor.cache_dir, "cas")
            os.makedirs(root, exist_ok=True)
            path = os.path.join(root, f"{digest}.kv")
            if not os.path.exists(path):
                tmp = f"{path}.tmp.{os.getpid()}.{os.urandom(4).hex()}"
                with open(tmp, "wb") as f:
                    f.write(data)
                os.replace(tmp, path)
            budget = int(
                getattr(supervisor.executor, "cas_max_bytes", 0) or 0
            )
            if budget > 0:
                prune_cas_dir(root, budget)
        except OSError as err:
            app_log.debug("KV CAS mirror write failed: %s", err)

    # -- health / scaling (decode-tier aware) -------------------------------

    def _on_replica_failed(
        self, supervisor: SessionSupervisor, failure: BaseException
    ) -> bool:
        handled = super()._on_replica_failed(supervisor, failure)
        if not self._decode_alive():
            # The base class drains the router queue only when EVERY
            # replica is gone; a live prefill tier with a dead decode
            # tier would otherwise leave queued requests hanging on a
            # pump that can never place them.
            for item in self.router.drain():
                request = item.task_metadata.get("request")
                if request is not None and not request.done:
                    request._fail(ServeError(
                        f"disaggregated set {self.name} has no live "
                        f"decode replicas: {failure}"
                    ))
        return handled

    async def scale_to(self, replicas: int) -> int:
        """Scale the DECODE tier to ``replicas`` members (the prefill
        tier stays at its configured width); returns the live decode
        count."""
        if self._closed:
            raise ServeError(f"replica set {self.name} is closed")
        replicas = int(replicas)
        if replicas < 1:
            raise ValueError(f"replicas must be >= 1, got {replicas}")
        live = {
            rid: sup for rid, sup in self._replicas.items()
            if sup.alive and self._role_of.get(rid, "decode") == "decode"
        }
        if replicas > len(live):
            grow = replicas - len(live)
            results = await asyncio.gather(
                *(self._open_replica() for _ in range(grow)),
                return_exceptions=True,
            )
            for failure in results:
                if isinstance(failure, BaseException):
                    app_log.warning(
                        "disagg set %s scale-up open failed: %r",
                        self.name, failure,
                    )
            self._schedule_pump()
        elif replicas < len(live):
            victims = sorted(
                live, key=lambda rid: live[rid].in_flight
            )[: len(live) - replicas]
            for rid in victims:
                await self._retire_replica(rid)
        self.replicas_wanted = self.prefill_replicas + replicas
        self._publish_replica_states()
        decode_live = len([
            rid for rid, sup in self._replicas.items()
            if sup.alive and self._role_of.get(rid, "decode") == "decode"
        ])
        obs_events.emit(
            "serve.replica_set_scaled",
            set=self.name,
            replicas=decode_live,
        )
        return decode_live

    # -- views --------------------------------------------------------------

    def status(self) -> dict[str, Any]:
        view = super().status()
        transfers = sorted(self.kv_transfer_s)
        view["roles"] = dict(self._role_of)
        view["min_prompt_tokens"] = self.min_prompt_tokens
        view["disagg_enabled"] = self.enabled
        view["requests_by_path"] = dict(self.requests_by_path)
        view["kv_bytes_total"] = self.kv_bytes_total
        view["kv_transfer_p50_ms"] = round(
            (transfers[len(transfers) // 2] if transfers else 0.0) * 1e3,
            4,
        )
        return view


async def open_disaggregated_set(
    targets: Any,
    factory: Any,
    *,
    decode_replicas: int | None = None,
    prefill_replicas: int | None = None,
    min_prompt_tokens: int | None = None,
    kv_timeout_s: float | None = None,
    name: str = "",
    sticky_ttl_s: float | None = None,
    router_queue_max: int | None = None,
    tenant_weights: dict[str, float] | None = None,
    **session_options: Any,
) -> DisaggregatedSet:
    """Open a prefill tier + a decode tier of one engine factory behind
    the replica-set router, connected by CAS-addressed KV bundles.

    ``targets`` is the same pool/executor list ``open_replica_set``
    takes; placement prefers pools whose spec declares the matching
    ``role`` (``"prefill"`` / ``"decode"``), then falls back to the
    affinity/warmth ranking.  ``decode_replicas`` defaults to
    ``len(targets) - prefill_replicas``; prompts shorter than
    ``min_prompt_tokens`` bypass the prefill tier entirely.
    """
    if not isinstance(targets, (list, tuple)):
        targets = [targets]
    disagg = DisaggregatedSet(
        list(targets),
        factory,
        decode_replicas=decode_replicas,
        prefill_replicas=prefill_replicas,
        min_prompt_tokens=min_prompt_tokens,
        kv_timeout_s=kv_timeout_s,
        name=name,
        sticky_ttl_s=sticky_ttl_s,
        router_queue_max=router_queue_max,
        tenant_weights=tenant_weights,
        **session_options,
    )
    await disagg._open()
    return disagg
