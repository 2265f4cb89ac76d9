"""Deterministic fault injection for any :class:`Transport`.

Production resilience claims are worthless untested, and real networks
produce faults neither deterministically nor on demand.  ``ChaosTransport``
wraps any transport backend and injects *scripted, seeded* faults — connect
errors, run errors, per-op delay, channel death after N ops (or on the
N-th command matching a substring), upload truncation — so the retry /
circuit-breaker / timeout machinery (resilience.py) is exercised by real
dispatches through the real lifecycle, reproducibly.

One :class:`ChaosPlan` is shared by every transport an executor creates, so
process-wide budgets like "exactly one channel death per fan-out"
(``max_faults=1``) are expressible.  Configuration is one environment
variable holding a comma-separated ``key=value`` spec::

    COVALENT_TPU_CHAOS="seed=7,drop_match=if test -f,max_faults=1"

Keys (all optional; unknown keys are rejected loudly — a typo'd chaos spec
silently injecting nothing would fake a green resilience test):

* ``seed``            — RNG seed for the probabilistic keys (default 0).
* ``delay``           — seconds of latency added to every op.
* ``connect_errors``  — fail the first N connect attempts.
* ``p_connect_error`` — probability a connect attempt fails.
* ``run_errors``      — fail the next N ``run`` calls (after any skip).
* ``p_run_error``     — probability any ``run`` call fails.
* ``drop_after``      — channel dies permanently after N successful ops.
* ``drop_match``      — channel dies on the next command containing this
  substring (pair with ``drop_match_skip=N`` to let N matches through).
* ``truncate_uploads``— corrupt the next N uploads (half the payload).
* ``preempt_after``   — models a TPU spot preemption: after N ops on one
  transport, SIGTERM is delivered to the worker processes the executor
  registered on it (``chaos_notify_pid``), then the channel drops after
  a ``preempt_grace``-second grace window — notice first, loss second,
  exactly the Cloud TPU preemption sequence.
* ``preempt_grace``   — seconds between the SIGTERM notice and channel
  death (default 1.0).
* ``jitter``          — seeded uniform extra latency in ``[0, jitter)``
  seconds added per op (gray mode: the link is alive but noisy).
* ``p_slow``          — probability an op hits the heavy tail: it sleeps
  ``slow_factor × max(delay, jitter, 0.01)`` seconds instead of its
  normal latency (gray mode: a browned-out worker, not a dead one).
* ``slow_factor``     — tail multiplier for ``p_slow`` (default 10).
* ``p_drop_op``       — probability a single op fails with a transport
  error WITHOUT killing the channel (gray mode: lossy-but-alive; the
  next op on the same transport works).
* ``max_faults``      — process-wide budget across ALL injected faults.

Every injected fault emits a ``chaos.fault`` event and increments
``covalent_tpu_chaos_faults_total{kind}`` so test assertions
can attribute recovery behavior to the faults that caused it.
"""

from __future__ import annotations

import asyncio
import os
import random
import time
from typing import Any

from ..obs import events as obs_events
from ..obs.metrics import REGISTRY
from ..utils.log import app_log
from .base import CommandResult, Transport, TransportError

__all__ = ["ChaosPlan", "ChaosTransport", "plan_from_env", "plan_from_spec"]

ENV_VAR = "COVALENT_TPU_CHAOS"

CHAOS_FAULTS_TOTAL = REGISTRY.counter(
    "covalent_tpu_chaos_faults_total",
    "Faults injected by ChaosTransport, by kind",
    ("kind",),
)

_INT_KEYS = (
    "seed", "connect_errors", "run_errors", "drop_after",
    "drop_match_skip", "truncate_uploads", "max_faults", "preempt_after",
)
_FLOAT_KEYS = (
    "delay", "p_connect_error", "p_run_error", "preempt_grace",
    "jitter", "p_slow", "slow_factor", "p_drop_op",
)
_STR_KEYS = ("drop_match",)


class ChaosPlan:
    """Shared, mutable fault script consumed by :class:`ChaosTransport`.

    Counter-based faults (``connect_errors``, ``drop_after``, ...) are
    deterministic; probability-based ones draw from one seeded RNG, so a
    fixed seed reproduces the same fault sequence for the same op order.
    """

    def __init__(
        self,
        seed: int = 0,
        delay: float = 0.0,
        connect_errors: int = 0,
        p_connect_error: float = 0.0,
        run_errors: int = 0,
        p_run_error: float = 0.0,
        drop_after: int = 0,
        drop_match: str = "",
        drop_match_skip: int = 0,
        truncate_uploads: int = 0,
        max_faults: int = 0,
        preempt_after: int = 0,
        preempt_grace: float = 1.0,
        jitter: float = 0.0,
        p_slow: float = 0.0,
        slow_factor: float = 10.0,
        p_drop_op: float = 0.0,
    ) -> None:
        self.seed = int(seed)
        self.delay = float(delay)
        self.connect_errors = int(connect_errors)
        self.p_connect_error = float(p_connect_error)
        self.run_errors = int(run_errors)
        self.p_run_error = float(p_run_error)
        self.drop_after = int(drop_after)
        self.drop_match = str(drop_match)
        self.drop_match_skip = int(drop_match_skip)
        self.truncate_uploads = int(truncate_uploads)
        self.max_faults = int(max_faults)  # 0 = unbounded
        self.preempt_after = int(preempt_after)
        self.preempt_grace = float(preempt_grace)
        self.jitter = float(jitter)
        self.p_slow = float(p_slow)
        self.slow_factor = float(slow_factor)
        self.p_drop_op = float(p_drop_op)
        self.rng = random.Random(self.seed)
        self.faults_injected = 0
        self._match_seen = 0
        self._jitter_announced = False

    @property
    def active(self) -> bool:
        """Whether this plan can inject anything at all."""
        return any((
            self.delay > 0, self.connect_errors > 0, self.p_connect_error > 0,
            self.run_errors > 0, self.p_run_error > 0, self.drop_after > 0,
            self.drop_match, self.truncate_uploads > 0,
            self.preempt_after > 0, self.jitter > 0, self.p_slow > 0,
            self.p_drop_op > 0,
        ))

    def slow_tail_s(self) -> float:
        """Seconds the heavy tail sleeps when a ``p_slow`` fault fires."""
        return self.slow_factor * max(self.delay, self.jitter, 0.01)

    def take_fault(self, kind: str, **detail: Any) -> bool:
        """Consume one unit of fault budget; False when the budget is spent."""
        if self.max_faults and self.faults_injected >= self.max_faults:
            return False
        self.faults_injected += 1
        CHAOS_FAULTS_TOTAL.labels(kind=kind).inc()
        obs_events.emit("chaos.fault", kind=kind, **detail)
        app_log.warning("chaos: injecting %s fault (%s)", kind, detail)
        return True


def plan_from_spec(spec: str) -> ChaosPlan | None:
    """Parse a ``key=value,key=value`` spec; None when empty/blank."""
    spec = (spec or "").strip()
    if not spec:
        return None
    kwargs: dict[str, Any] = {}
    for token in spec.split(","):
        token = token.strip()
        if not token:
            continue
        key, sep, value = token.partition("=")
        key = key.strip()
        if not sep:
            raise ValueError(f"chaos spec token {token!r} is not key=value")
        if key in _INT_KEYS:
            kwargs[key] = int(value)
        elif key in _FLOAT_KEYS:
            kwargs[key] = float(value)
        elif key in _STR_KEYS:
            kwargs[key] = value
        else:
            raise ValueError(
                f"unknown chaos spec key {key!r} "
                f"(known: {', '.join(_INT_KEYS + _FLOAT_KEYS + _STR_KEYS)})"
            )
    return ChaosPlan(**kwargs)


def plan_from_env() -> ChaosPlan | None:
    """Plan from ``COVALENT_TPU_CHAOS``; None when unset."""
    return plan_from_spec(os.environ.get(ENV_VAR, ""))


class ChaosTransport(Transport):
    """A transport whose faults are scripted by a shared :class:`ChaosPlan`.

    Semantics mirror a real broken channel: once a drop fires, *every*
    subsequent op on this transport raises (without consuming further fault
    budget) until the executor discards it and dials a fresh one — exactly
    the recovery path the resilience layer must drive.
    """

    #: A chaos wrapper *simulates a network* even over a shared-fs inner
    #: transport, so the codec layer negotiates compression through it and
    #: ``put_bundle`` deliberately rides the base-class implementation:
    #: its tar travels through THIS class's ``put`` (truncation faults
    #: corrupt it) and its unpack through ``run`` (drop faults kill it),
    #: exactly like a real wire.
    zero_wire = False

    def __init__(self, inner: Transport, plan: ChaosPlan) -> None:
        self.inner = inner
        self.plan = plan
        self.ops = 0
        self.dead = False
        #: worker process-group leaders the executor registered on this
        #: channel (chaos_notify_pid) — the preempt fault's SIGTERM targets.
        self.worker_pids: list[int] = []
        self._preempted = False
        self._dead_at: float | None = None

    @property
    def address(self) -> str:  # type: ignore[override]
        return self.inner.address

    def chaos_notify_pid(self, pid: int) -> None:
        """Register one worker pid launched over this channel (the
        executor calls this after dispatch) so a ``preempt_after`` fault
        can deliver its SIGTERM notice to the right process group."""
        if pid and pid not in self.worker_pids:
            self.worker_pids.append(int(pid))

    async def _deliver_preempt_notice(self) -> None:
        """SIGTERM every registered worker's process group via the INNER
        channel (the notice arrives even though this wrapper is about to
        drop): group first so the harness's own children get it, direct
        pid as the fallback for the pre-setsid race."""
        for pid in list(self.worker_pids):
            try:
                await self.inner.run(
                    f"kill -s TERM -- -{pid} 2>/dev/null || "
                    f"kill -s TERM {pid} 2>/dev/null || true"
                )
            except Exception as err:  # noqa: BLE001 - notice is best-effort
                app_log.debug("chaos: preempt notice to %s failed: %s",
                              pid, err)

    async def _gate(self, op: str, command: str = "") -> None:
        """Count one op; raise if the channel is (or now becomes) dead."""
        if self._dead_at is not None and time.monotonic() >= self._dead_at:
            # The preemption grace window elapsed: the VM is gone.
            self.dead = True
        if self.dead:
            raise TransportError(
                f"chaos: channel to {self.address} is dead"
            )
        if self.plan.delay > 0:
            await asyncio.sleep(self.plan.delay)
        if self.plan.jitter > 0:
            # Gray noise: seeded uniform extra latency on every op.  One
            # announcing fault (the first) rather than one per op — the
            # budget is for discrete faults, not continuous noise.
            if not self.plan._jitter_announced:
                self.plan._jitter_announced = True
                self.plan.take_fault(
                    "jitter", address=self.address, jitter_s=self.plan.jitter
                )
            await asyncio.sleep(self.plan.rng.random() * self.plan.jitter)
        self.ops += 1
        plan = self.plan
        if plan.p_slow > 0 and plan.rng.random() < plan.p_slow:
            if plan.take_fault(
                "slow", address=self.address, op=op,
                slow_s=round(plan.slow_tail_s(), 3),
            ):
                # Heavy tail: the op completes, just brutally late — the
                # gray failure a binary breaker never sees.
                await asyncio.sleep(plan.slow_tail_s())
        if plan.p_drop_op > 0 and plan.rng.random() < plan.p_drop_op:
            if plan.take_fault("drop_op", address=self.address, op=op):
                # Lossy-but-alive: THIS op fails, the channel survives.
                raise TransportError(
                    f"chaos: op {op} dropped on {self.address} "
                    "(channel still alive)"
                )
        if (
            plan.preempt_after
            and not self._preempted
            and self.ops > plan.preempt_after
            and plan.take_fault(
                "preempt", address=self.address, op=op, ops=self.ops,
                pids=list(self.worker_pids), grace_s=plan.preempt_grace,
            )
        ):
            # Spot preemption sequence: TERM notice now, channel loss
            # after the grace window.  Ops inside the window still work —
            # that is what lets a cooperative final checkpoint (and a
            # serving warm handoff) land before the loss.
            self._preempted = True
            self._dead_at = time.monotonic() + max(
                0.0, plan.preempt_grace
            )
            await self._deliver_preempt_notice()
        if plan.drop_after and self.ops > plan.drop_after:
            if plan.take_fault("drop", address=self.address, op=op, ops=self.ops):
                self.dead = True
                raise TransportError(
                    f"chaos: channel to {self.address} dropped after "
                    f"{self.ops - 1} ops"
                )
        if plan.drop_match and command and plan.drop_match in command:
            plan._match_seen += 1
            if plan._match_seen > plan.drop_match_skip and plan.take_fault(
                "drop", address=self.address, op=op, match=plan.drop_match
            ):
                self.dead = True
                raise TransportError(
                    f"chaos: channel to {self.address} dropped on command "
                    f"matching {plan.drop_match!r}"
                )

    # -- connect (driven by connect_with_retries via _open) ------------------

    async def _open(self) -> None:
        plan = self.plan
        fail = False
        if plan.connect_errors > 0:
            fail = plan.take_fault("connect", address=self.address)
            if fail:
                plan.connect_errors -= 1
        elif plan.p_connect_error > 0 and plan.rng.random() < plan.p_connect_error:
            fail = plan.take_fault("connect", address=self.address)
        if fail:
            raise ConnectionRefusedError(
                f"chaos: connect to {self.address} refused"
            )
        opener = getattr(self.inner, "_open", None)
        if opener is not None:
            await opener()

    # -- Transport interface -------------------------------------------------

    async def run(self, command: str, timeout: float | None = None) -> CommandResult:
        await self._gate("run", command)
        plan = self.plan
        fail = False
        if plan.run_errors > 0:
            fail = plan.take_fault("run", address=self.address, command=command[:80])
            if fail:
                plan.run_errors -= 1
        elif plan.p_run_error > 0 and plan.rng.random() < plan.p_run_error:
            fail = plan.take_fault("run", address=self.address, command=command[:80])
        if fail:
            raise TransportError(f"chaos: run failed on {self.address}")
        return await self.inner.run(command, timeout)

    async def put(self, local_path: str, remote_path: str) -> None:
        await self._gate("put")
        plan = self.plan
        if plan.truncate_uploads > 0 and plan.take_fault(
            "truncate", address=self.address, remote=remote_path
        ):
            plan.truncate_uploads -= 1
            with open(local_path, "rb") as f:
                payload = f.read()
            import tempfile

            # Ship half the bytes under the same remote name: the CAS
            # digest verification on the worker is what must catch this.
            with tempfile.NamedTemporaryFile(delete=False) as tmp:
                tmp.write(payload[: max(0, len(payload) // 2)])
                truncated = tmp.name
            try:
                await self.inner.put(truncated, remote_path)
            finally:
                os.unlink(truncated)
            return
        await self.inner.put(local_path, remote_path)

    async def get(self, remote_path: str, local_path: str) -> None:
        await self._gate("get")
        await self.inner.get(remote_path, local_path)

    # exists_batch / rename / remove deliberately NOT forwarded to the
    # inner transport: the base-class implementations ride self.run (one
    # gated round trip each), so a chaos-wrapped LocalTransport behaves
    # op-for-op like a real SSH wire — a shell exec per probe/publish —
    # instead of silently borrowing the inner backend's direct-filesystem
    # fast paths.  Faults still apply exactly once, via the run gate.

    async def start_process(self, command: str, describe: str = ""):
        await self._gate("start_process", command)
        return await self.inner.start_process(command, describe)

    async def close(self) -> None:
        await self.inner.close()
