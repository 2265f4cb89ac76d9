"""Client for the resident worker agent (``native/agent.cc``).

The reference's submit/status protocol costs one SSH round-trip per probe
(``covalent_ssh_plugin/ssh.py:383`` submit, ``ssh.py:402-406`` status,
``ssh.py:408-432`` poll loop).  The agent collapses all of that into one
persistent channel per worker: the executor writes a ``run`` command and the
agent *pushes* ``started``/``exit`` events the moment they happen — no poll
traffic, and task-completion latency bounded by the channel RTT instead of
the poll interval.

Deployment is self-contained: the single C++ source ships inside this
package, is uploaded to the worker's cache dir, and is compiled there by the
system compiler (cached by content hash, so compilation happens once per
worker per agent version).  Workers without a C++ toolchain simply raise
:class:`AgentError` and the executor falls back to the stateless
``nohup`` + poll protocol — the agent is an accelerator, never a
requirement.  Agent-launched tasks run in their own sessions, so even if the
agent or its channel dies mid-task, the fallback poller can resume
supervision using the PID from the ``started`` event.
"""

from __future__ import annotations

import asyncio
import base64
import hashlib
import json
import os
import shlex
import uuid
from functools import lru_cache
from pathlib import Path
from typing import Any

from .obs import events as obs_events
from .obs.metrics import REGISTRY
from .obs.trace import Span
from .transport import frames
from .transport.base import Transport, TransportError
from .utils.log import app_log

_AGENT_RPCS = REGISTRY.counter(
    "covalent_tpu_agent_rpcs_total",
    "Commands written to resident agent channels",
    ("cmd",),
)
_AGENT_EVENTS = REGISTRY.counter(
    "covalent_tpu_agent_events_total",
    "Events pushed by resident agent channels",
    ("event",),
)
AGENT_RESTARTS_TOTAL = REGISTRY.counter(
    "covalent_tpu_agent_restarts_total",
    "Cached agent channels discarded and restarted after a failed ping",
)
AGENT_FRAMES_TOTAL = REGISTRY.counter(
    "covalent_tpu_agent_frames_total",
    "Protocol messages on agent channels by verb and encoding "
    "(jsonl lines vs negotiated binary frames)",
    ("verb", "encoding"),
)
AGENT_WIRE_BYTES_TOTAL = REGISTRY.counter(
    "covalent_tpu_agent_wire_bytes_total",
    "Bytes on agent channels by direction (up/down) and encoding",
    ("direction", "encoding"),
)


def frames_env_enabled() -> bool:
    """Process-wide kill switch: COVALENT_TPU_AGENT_FRAMES=0 pins JSONL."""
    return os.environ.get(
        "COVALENT_TPU_AGENT_FRAMES", ""
    ).strip().lower() not in ("0", "off", "false", "no")


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


#: Same-event-loop-turn invoke batching by default (window 0: zero added
#: latency — only invokes already queued in the current turn coalesce);
#: a positive window trades a bounded wait for bigger batches.
_BATCH_WINDOW_S = max(0.0, _env_float(
    "COVALENT_TPU_RPC_BATCH_WINDOW_MS", 0.0
) / 1000.0)
_BATCH_MAX_OPS = max(1, int(_env_float("COVALENT_TPU_RPC_BATCH_MAX", 16)))

AGENT_SOURCE = Path(__file__).parent / "native" / "agent.cc"

#: Remote filename of the staged harness module.  Shared by the per-task
#: stager (StagedTask.remote_harness_file) and the pool server so the
#: resident interpreter always serves the same file task specs point at.
HARNESS_BASENAME = "covalent_tpu_harness.py"


class AgentError(TransportError):
    """Agent unavailable or its channel failed; callers fall back to polling."""


@lru_cache(maxsize=1)
def agent_source_hash() -> str:
    """Content hash naming the remote binary, so stale agents never run."""
    return hashlib.sha256(AGENT_SOURCE.read_bytes()).hexdigest()[:12]


async def ensure_agent_binary(conn: Transport, remote_cache: str) -> str:
    """Upload + compile the agent on the worker (idempotent, hash-cached).

    One round-trip when the binary already exists; upload + one compile
    round-trip the first time.  Raises :class:`AgentError` when the worker
    has no C++ compiler.
    """
    binary = f"{remote_cache}/agent_{agent_source_hash()}"
    q_binary = shlex.quote(binary)
    # mkdir rides the probe: this may run concurrently with (or before) the
    # executor preflight that normally creates the cache dir.
    probe = await conn.run(
        f"mkdir -p {shlex.quote(remote_cache)}; "
        f"test -x {q_binary} && echo HAVE || echo MISSING"
    )
    if "HAVE" in probe.stdout:
        return binary

    source = f"{binary}.cc"
    await conn.put(str(AGENT_SOURCE), source)
    # Unique tmp name + atomic mv so concurrent electrons can race safely.
    tmp = shlex.quote(f"{binary}.tmp.{uuid.uuid4().hex[:8]}")
    build = await conn.run(
        "CXX=$(command -v g++ || command -v c++ || command -v clang++) "
        "&& [ -n \"$CXX\" ] "
        f"&& $CXX -O2 -std=c++17 -o {tmp} {shlex.quote(source)} "
        f"&& mv {tmp} {q_binary}",
        timeout=120.0,
    )
    if build.exit_status != 0:
        raise AgentError(
            f"no agent on {conn.address}: compile failed or no C++ compiler "
            f"({build.stderr.strip()[:200]})"
        )
    return binary


async def start_pool_server(
    conn: Transport,
    remote_cache: str,
    python_path: str,
    conda_env: str = "",
    preload: str = "cloudpickle",
    timeout: float = 90.0,
    frames_enabled: bool | None = None,
    frames_codec: str = "",
) -> "AgentClient":
    """Start the harness forkserver (``harness.py --serve``) on a worker.

    The resident interpreter preloads ``preload`` modules once; each task
    then costs a fork instead of interpreter startup + imports.  The
    generous timeout covers a cold jax import on the worker.  Speaks the
    same protocol as the native agent, so the returned client is a drop-in
    (``mode == "pool"``).
    """
    from . import harness as harness_module

    remote_harness = f"{remote_cache}/{HARNESS_BASENAME}"
    try:
        await conn.run(f"mkdir -p {shlex.quote(remote_cache)}")
        await conn.put(harness_module.__file__, remote_harness)
    except TransportError as err:
        raise AgentError(f"cannot stage pool server on {conn.address}: {err}") from err

    command = (
        f"env COVALENT_TPU_POOL_PRELOAD={shlex.quote(preload)} "
        f"{python_path} {shlex.quote(remote_harness)} --serve"
    )
    if conda_env:
        command = (
            f'eval "$(conda shell.bash hook)" && conda activate '
            f"{shlex.quote(conda_env)} && {command}"
        )
    try:
        process = await conn.start_process(command, describe=f"pool@{conn.address}")
    except TransportError as err:
        raise AgentError(f"cannot start pool server on {conn.address}: {err}") from err
    client = AgentClient(process, conn.address)
    client.mode = "pool"
    try:
        await client.ping(timeout)
        await client.negotiate_frames(
            enabled=frames_enabled, codec=frames_codec
        )
    except AgentError:
        await client.close()
        raise
    return client


def orphan_rendezvous_path(remote_cache: str) -> str:
    """Where an orphaned pool server publishes its adoption coordinates."""
    return f"{remote_cache}/pool_orphan.json"


async def read_orphan_rendezvous(
    conn: Transport, remote_cache: str
) -> dict | None:
    """The worker's ``pool_orphan.json``, or None when no orphan waits."""
    import tempfile

    path = orphan_rendezvous_path(remote_cache)
    with tempfile.TemporaryDirectory(prefix="covalent-orphan-") as tmp:
        local = f"{tmp}/pool_orphan.json"
        try:
            await conn.get(path, local)
            with open(local, "r", encoding="utf-8") as fh:
                meta = json.load(fh)
        except (TransportError, OSError, ValueError):
            return None
    if not isinstance(meta, dict) or not meta.get("sock"):
        return None
    return meta


async def attach_pool_server(
    conn: Transport,
    remote_cache: str,
    python_path: str,
    sock_path: str,
    epoch: int,
    conda_env: str = "",
    timeout: float = 30.0,
    frames_enabled: bool | None = None,
    frames_codec: str = "",
) -> "AgentClient":
    """Adopt an orphaned pool server instead of starting a fresh one.

    Spawns the ``--attach`` stdio relay through the normal transport (so
    adoption works identically over SSH and local), sends the epoch-fenced
    adopt line, and waits for the orphan's re-attach ready banner.  The
    orphan refuses a stale epoch with an error event — surfaced here as an
    AgentError so the caller falls back to a fresh server.
    """
    remote_harness = f"{remote_cache}/{HARNESS_BASENAME}"
    command = f"{python_path} {shlex.quote(remote_harness)} --attach " \
              f"{shlex.quote(sock_path)}"
    if conda_env:
        command = (
            f'eval "$(conda shell.bash hook)" && conda activate '
            f"{shlex.quote(conda_env)} && {command}"
        )
    try:
        process = await conn.start_process(
            command, describe=f"adopt@{conn.address}"
        )
    except TransportError as err:
        raise AgentError(
            f"cannot start attach relay on {conn.address}: {err}"
        ) from err
    client = AgentClient(process, conn.address)
    client.mode = "pool"
    try:
        await client._send({"cmd": "adopt", "epoch": int(epoch)})

        def adopted(c: "AgentClient"):
            if c._banner.get("reattach"):
                return c._banner
            if c._error_codes.get("") == "stale_epoch":
                message = c._errors.pop("", "stale epoch")
                c._error_codes.pop("", None)
                raise AgentError(f"agent@{c.address}: adopt refused: "
                                 f"{message}")
            if c._error_codes.get("") == "attach_failed":
                message = c._errors.pop("", "attach failed")
                c._error_codes.pop("", None)
                raise AgentError(f"agent@{c.address}: {message}")
            return None

        await client._wait(adopted, timeout)
        await client.ping(timeout)
        await client.negotiate_frames(
            enabled=frames_enabled, codec=frames_codec
        )
    except AgentError:
        await client.close()
        raise
    return client


class AgentClient:
    """One agent channel to one worker, demultiplexing pushed events.

    A background reader drains the channel and files events by task id;
    any number of concurrent tasks can await their own ``started``/``exit``
    notifications.
    """

    #: "native" (C++ agent, argv exec) or "pool" (harness forkserver, spec).
    mode: str = "native"

    def __init__(self, process, address: str):
        self._process = process
        self.address = address
        self._started: dict[str, int] = {}
        self._exits: dict[str, tuple[int, int]] = {}
        self._errors: dict[str, str] = {}
        #: function digests this channel's resident runtime has registered
        #: (RPC dispatch); dies with the client, exactly like the remote
        #: registry dies with the agent process.
        self._registered: set[str] = set()
        #: digest -> (code, message) for a failed registration.
        self._register_errors: dict[str, tuple[str, str]] = {}
        #: task id -> pushed ``result`` event (RPC invocations).
        self._results: dict[str, dict] = {}
        self._pongs = 0
        self._dead: BaseException | None = None
        self._cond = asyncio.Condition()
        #: sink for backhauled telemetry lines: called ``(task_id, data)``
        #: for every FRESH event the agent's watch side-band pushes.  Set
        #: by the executor; exceptions are contained (observer contract).
        self.on_telemetry = None
        #: task id -> highest worker-event ``seq`` seen; a re-watch after a
        #: reconnect re-tails from offset 0, so duplicates are expected and
        #: dropped here.
        self._telemetry_seq: dict[str, int] = {}
        #: serving sessions: sid -> pushed serve_opened / serve_error /
        #: serve_closed events, and sid -> per-session telemetry sink
        #: (serve.token / serve.reject / serve.stats data routed here
        #: instead of :attr:`on_telemetry`).
        self._serve_opened: dict[str, dict] = {}
        self._serve_errors: dict[str, dict] = {}
        self._serve_closed: dict[str, dict] = {}
        self._serve_sinks: dict[str, Any] = {}
        #: "sid/rid" -> pushed ``serve_kv`` event (disaggregated prefill
        #: answers: KV bundle bytes as a raw frame body, or an error).
        self._serve_kv: dict[str, dict] = {}
        #: "sid/rid" -> pushed ``serve_resumed`` ack (recovery path).
        self._serve_resumed: dict[str, dict] = {}
        #: "kind:sid/adapter" -> pushed ``serve_attached``/``serve_detached``
        #: ack (the multi-adapter registry path; kind keeps an attach and a
        #: detach of the same adapter from settling each other's waiter).
        self._serve_attached: dict[str, dict] = {}
        #: "serve"/"task" -> latest pushed inventory answer (recovery path;
        #: one outstanding request per kind — the slot is cleared on send).
        self._inventories: dict[str, dict] = {}
        #: last ``epoch_ok`` ack from declare_epoch (worker-side fence).
        self._epoch_ack: dict | None = None
        #: resident-mode profiling: profile id -> pushed profile_started /
        #: profile_stopped / profile_error events.
        self._profile_started: dict[str, dict] = {}
        self._profile_stopped: dict[str, dict] = {}
        self._profile_errors: dict[str, dict] = {}
        #: binary frame negotiation: the runtime's ready banner (capability
        #: advertisement), the pushed `frames` ack, and the active state.
        self._banner: dict = {}
        self._frames_ack: dict | None = None
        self.frames_active = False
        self._frame_codec = ""
        #: task id -> structured code from an `error` event (bad_frame is
        #: torn content — the rejection must classify PERMANENT, not burn
        #: gang retries re-sending identical corrupt bytes).
        self._error_codes: dict[str, str] = {}
        #: invoke micro-batching: digest -> [(command, args_bytes)] queued
        #: this window; flushed as ONE multi_invoke frame per digest.
        self._pending_invokes: dict[str, list] = {}
        self._flush_scheduled = False
        self._flush_now = False
        #: live flusher tasks: the loop keeps only weak refs to tasks, so
        #: an unreferenced flusher could be GC'd mid-flight, stranding its
        #: waiters on their started timeouts.
        self._flush_tasks: set = set()
        self._reader = asyncio.create_task(self._read_loop())

    # -- lifecycle -----------------------------------------------------------

    @classmethod
    async def start(
        cls,
        conn: Transport,
        binary: str,
        timeout: float = 15.0,
        frames_enabled: bool | None = None,
        frames_codec: str = "",
    ) -> "AgentClient":
        try:
            process = await conn.start_process(
                shlex.quote(binary), describe=f"agent@{conn.address}"
            )
        except TransportError as err:
            raise AgentError(f"cannot start agent on {conn.address}: {err}") from err
        client = cls(process, conn.address)
        try:
            # A ping round-trip both consumes the ready banner and proves the
            # channel is live before any task is entrusted to it.
            await client.ping(timeout)
            await client.negotiate_frames(
                enabled=frames_enabled, codec=frames_codec
            )
        except AgentError:
            await client.close()
            raise
        return client

    @property
    def alive(self) -> bool:
        return self._dead is None and not self._reader.done()

    @property
    def banner_sessions(self) -> list[str]:
        """Session ids a re-adopted pool server announced in its banner
        (empty for a fresh start — only ``reattach`` banners carry them)."""
        return [str(s) for s in (self._banner.get("sessions") or [])]

    async def close(self) -> None:
        try:
            if self._dead is None:
                await self._process.write_line('{"cmd":"shutdown"}')
        except TransportError:
            pass
        self._reader.cancel()
        try:
            await self._reader
        except (asyncio.CancelledError, Exception):
            pass
        await self._process.close()

    # -- event plumbing ------------------------------------------------------

    async def _read_loop(self) -> None:
        try:
            while True:
                message = await self._process.read_event()
                if message[0] == "frame":
                    _kind, verb, flags, header, body = message
                    AGENT_FRAMES_TOTAL.labels(
                        verb=frames.VERB_NAMES.get(verb, str(verb)),
                        encoding="binary",
                    ).inc()
                    AGENT_WIRE_BYTES_TOTAL.labels(
                        direction="down", encoding="binary"
                    ).inc(frames.HEADER_LEN + len(header) + len(body))
                    try:
                        event = frames.decode_payload(flags, header, body)
                    except frames.FrameIntegrityError as err:
                        # The frame arrived length-intact, so this is torn
                        # CONTENT, not a channel fault: deliver a marked
                        # event so the waiter fails PERMANENT instead of
                        # the whole channel dying transient.
                        try:
                            event = json.loads(header.decode("utf-8"))
                        except ValueError:
                            raise TransportError(
                                f"agent@{self.address}: undecodable torn "
                                f"frame: {err}"
                            ) from err
                        event.pop("_body", None)
                        event["torn"] = repr(err)
                    # FrameError (bad header JSON) falls through to the
                    # generic handler below: the stream itself cannot be
                    # trusted past it, so the reader dies and waiters see
                    # a channel death.
                else:
                    line = message[1]
                    try:
                        event = json.loads(line)
                    except ValueError:
                        continue  # stray non-protocol output; ignore
                    kind0 = str(event.get("event")) if isinstance(
                        event, dict
                    ) else "?"
                    AGENT_FRAMES_TOTAL.labels(
                        verb=kind0, encoding="jsonl"
                    ).inc()
                    AGENT_WIRE_BYTES_TOTAL.labels(
                        direction="down", encoding="jsonl"
                    ).inc(len(line) + 1)
                if not isinstance(event, dict):
                    continue
                async with self._cond:
                    kind = event.get("event")
                    task_id = event.get("id", "")
                    _AGENT_EVENTS.labels(event=str(kind)).inc()
                    if kind == "telemetry":
                        self._handle_telemetry(task_id, event.get("data"))
                        continue  # side-band: no waiter state to notify
                    if kind == "telemetry_batch":
                        if event.get("torn"):
                            # Torn batch body: the records (and their
                            # rids) are unrecoverable — say so loudly
                            # instead of silently dropping what may be a
                            # stream's done marker.
                            app_log.warning(
                                "agent@%s: dropped torn telemetry batch "
                                "for %s: %s",
                                self.address, task_id, event["torn"],
                            )
                            obs_events.emit(
                                "agent.torn_telemetry_batch",
                                address=self.address,
                                task_id=str(task_id),
                                error=str(event["torn"]),
                            )
                            continue
                        # Coalesced side-band frame: unpack and feed each
                        # record through the exact per-record path — seq
                        # dedup, serve sinks, and the exactly-once idx
                        # splice downstream are untouched by batching.
                        records = event.get("records") or b"[]"
                        try:
                            parsed = json.loads(
                                records.decode("utf-8")
                                if isinstance(records, (bytes, bytearray))
                                else records
                            )
                        except (ValueError, UnicodeDecodeError):
                            parsed = []
                        for record in parsed if isinstance(
                            parsed, list
                        ) else []:
                            self._handle_telemetry(task_id, record)
                        continue
                    if kind == "started":
                        self._started[task_id] = int(event["pid"])
                    elif kind == "multi_started":
                        pid = int(event.get("pid") or 0)
                        for tid in event.get("ids") or []:
                            self._started[str(tid)] = pid
                    elif kind == "ready":
                        self._banner = event
                    elif kind == "frames":
                        self._frames_ack = event
                    elif kind == "serve_opened":
                        self._serve_opened[task_id] = event
                    elif kind == "serve_error":
                        self._serve_errors[task_id] = event
                    elif kind == "serve_closed":
                        self._serve_closed[task_id] = event
                    elif kind == "serve_kv":
                        self._serve_kv[
                            f"{task_id}/{event.get('rid') or ''}"
                        ] = event
                        # Bound abandoned answers: a prefill whose waiter
                        # timed out leaves its (late) event unclaimed —
                        # drop oldest so a pathological session cannot
                        # grow this for the channel lifetime.
                        while len(self._serve_kv) > 256:
                            self._serve_kv.pop(
                                next(iter(self._serve_kv))
                            )
                    elif kind == "serve_resumed":
                        self._serve_resumed[
                            f"{task_id}/{event.get('rid') or ''}"
                        ] = event
                        while len(self._serve_resumed) > 1024:
                            self._serve_resumed.pop(
                                next(iter(self._serve_resumed))
                            )
                    elif kind in ("serve_attached", "serve_detached"):
                        self._serve_attached[
                            f"{kind}:{task_id}/"
                            f"{event.get('adapter') or ''}"
                        ] = event
                        while len(self._serve_attached) > 256:
                            self._serve_attached.pop(
                                next(iter(self._serve_attached))
                            )
                    elif kind == "serve_inventory":
                        self._inventories["serve"] = event
                    elif kind == "task_inventory":
                        self._inventories["task"] = event
                    elif kind == "epoch_ok":
                        self._epoch_ack = event
                    elif kind == "profile_started":
                        self._profile_started[task_id] = event
                    elif kind == "profile_stopped":
                        self._profile_stopped[task_id] = event
                    elif kind == "profile_error":
                        self._profile_errors[task_id] = event
                    elif kind == "exit":
                        self._exits[task_id] = (
                            int(event.get("code", -1)),
                            int(event.get("signal", 0)),
                        )
                    elif kind == "result":
                        self._results[task_id] = event
                    elif kind == "registered":
                        self._registered.add(str(event.get("digest") or ""))
                    elif kind == "register_error":
                        self._register_errors[
                            str(event.get("digest") or "")
                        ] = (
                            str(event.get("code") or "error"),
                            str(event.get("message") or "?"),
                        )
                    elif kind == "pong":
                        self._pongs += 1
                    elif kind == "error":
                        # id-less errors are log-only — EXCEPT the epoch
                        # fence refusal and a failed attach relay, which
                        # declare_epoch / attach_pool_server wait on.
                        if task_id or event.get("code") in (
                            "stale_epoch", "attach_failed"
                        ):
                            self._errors[task_id] = str(event.get("message", "?"))
                            if event.get("code"):
                                self._error_codes[task_id] = str(event["code"])
                        app_log.warning(
                            "agent@%s error: %s", self.address, event.get("message")
                        )
                    self._cond.notify_all()
        except asyncio.CancelledError:
            raise
        except BaseException as err:  # noqa: BLE001 - ANY reader death must
            # wake waiters: an unnotified exception here would leave
            # wait_exit() blocked forever (e.g. asyncssh.ConnectionLost is
            # neither TransportError nor OSError).
            obs_events.emit(
                "agent.channel_died", address=self.address, error=repr(err)
            )
            async with self._cond:
                self._dead = err
                self._cond.notify_all()

    def _handle_telemetry(self, task_id: str, data) -> None:
        """Dedup one backhauled event by ``seq`` and hand it to the sink.

        Worker events carry a per-process monotonically increasing ``seq``
        (harness ``_emit_worker_event``); a re-watch after channel loss
        replays the whole file, so everything at-or-below the high-water
        mark is a duplicate.  Events without a seq pass through — better a
        duplicate observation than a dropped one.
        """
        if not isinstance(data, dict):
            return
        seq = data.get("seq")
        if isinstance(seq, int):
            if seq <= self._telemetry_seq.get(task_id, 0):
                return
            self._telemetry_seq[task_id] = seq
        # Serving sessions own their side-band traffic: every record for a
        # watched sid (tokens, rejects, stats) routes to that session's
        # sink instead of the executor's generic backhaul handler.
        callback = self._serve_sinks.get(task_id) or self.on_telemetry
        if callback is None:
            return
        try:
            callback(task_id, data)
        except Exception as err:  # noqa: BLE001 - observers must not break
            app_log.debug("telemetry callback failed: %s", err)

    async def watch(self, task_id: str, path: str) -> None:
        """Start the telemetry side-band for one task's worker-local file.

        The agent tails ``path`` from offset 0 (flushing any backlog
        buffered while no channel was attached) and pushes each JSONL line
        as a ``telemetry`` event routed to :attr:`on_telemetry`.
        """
        await self._send({"cmd": "watch", "id": task_id, "path": path})

    async def unwatch(self, task_id: str) -> None:
        await self._send({"cmd": "unwatch", "id": task_id})

    async def _wait(self, predicate, timeout: float | None):
        """Await ``predicate(self)`` truthy, raising AgentError on channel death."""

        async def waiter():
            async with self._cond:
                while True:
                    if self._dead is not None:
                        raise AgentError(
                            f"agent@{self.address} channel died: {self._dead}"
                        )
                    value = predicate(self)
                    if value:
                        return value
                    await self._cond.wait()

        try:
            return await asyncio.wait_for(waiter(), timeout)
        except asyncio.TimeoutError:
            raise AgentError(f"agent@{self.address}: no event within {timeout}s")

    # -- commands ------------------------------------------------------------

    async def ping(self, timeout: float = 15.0) -> None:
        before = self._pongs
        await self._send({"cmd": "ping"})
        await self._wait(lambda c: c._pongs > before, timeout)

    async def negotiate_frames(
        self,
        timeout: float = 15.0,
        enabled: bool | None = None,
        codec: str = "",
    ) -> bool:
        """Switch the channel to binary frames when both ends are capable.

        Rides the ready-banner handshake (the same one-round-trip shape as
        the ``COVALENT_TPU_CODECS=`` pre-flight probe): a frame-capable
        runtime advertised ``frames`` in its banner — consumed before the
        ping ack, so this never races — and answers the ``frames`` command
        with an ack carrying the accepted body codec.  A silent banner (old
        or JSON-only runtime), a ``version: 0`` refusal (remote kill
        switch), or ``enabled=False`` (local kill switch /
        COVALENT_TPU_AGENT_FRAMES=0) all leave the channel on JSONL — the
        fallback is byte-equal, just slower.

        ``codec`` asks for per-frame BODY compression (zlib, the one codec
        every stdlib-only worker has).  Like the staging codec's download
        leg, it engages only when the operator pinned a codec: deflating a
        mid-size payload costs more CPU time than the base64+JSON parse it
        replaces, so it pays only where the wire (not the CPU) is the
        bottleneck — raw frames already drop the ~33% base64 inflation and
        both JSON legs for free.
        """
        if enabled is None:
            enabled = frames_env_enabled()
        if not enabled or not self._banner.get("frames"):
            return False
        codecs = self._banner.get("codecs") or []
        codec = "zlib" if codec == "zlib" and "zlib" in codecs else ""
        await self._send({
            "cmd": "frames", "version": frames.VERSION, "codec": codec,
        })
        ack = await self._wait(lambda c: c._frames_ack, timeout)
        if int(ack.get("version") or 0) >= 1:
            self.frames_active = True
            self._frame_codec = str(ack.get("codec") or "")
            obs_events.emit(
                "agent.frames_negotiated", address=self.address,
                codec=self._frame_codec,
            )
        return self.frames_active

    def _pop_rejection(self, task_id: str, what: str) -> AgentError | None:
        """Stored error event -> a rejection exception (or None).

        A definitive rejection means the task never started, so relaunch
        through the fallback path is safe.  A ``bad_frame`` code is torn
        content — identical bytes can never be re-sent successfully — and
        ``backend_held`` is a pool server refusing to fork out of a
        process that holds an XLA backend (no relaunch on that worker can
        get the accelerator either), so both rejections carry the
        duck-typed PERMANENT tag.
        """
        if task_id not in self._errors:
            return None
        message = self._errors.pop(task_id)
        code = self._error_codes.pop(task_id, "")
        rejection = AgentError(
            f"agent@{self.address} rejected {what} {task_id}: {message}"
        )
        rejection.rejected = True  # type: ignore[attr-defined]
        label = {
            "bad_frame": "agent_bad_frame",
            "backend_held": "runtime_holds_backend",
        }.get(code)
        if label:
            rejection.fault_label = label  # type: ignore[attr-defined]
            rejection.fault_transient = False  # type: ignore[attr-defined]
        return rejection

    async def run_task(
        self,
        task_id: str,
        argv: list[str] | None = None,
        cwd: str = "",
        env: dict[str, str] | None = None,
        log: str = "",
        timeout: float = 30.0,
        spec: str = "",
    ) -> int:
        """Launch a task; returns the remote PID from the ``started`` event.

        ``argv`` targets the native C++ agent (it execs the command);
        ``spec`` targets the harness pool server (it forks and runs the spec
        in the pre-warmed interpreter).  Exactly one must be given.
        """
        command: dict = {"cmd": "run", "id": task_id}
        if spec:
            command["spec"] = spec
        else:
            command["argv"] = list(argv or [])
        if cwd:
            command["cwd"] = cwd
        if env:
            command["env"] = {str(k): str(v) for k, v in env.items()}
        if log:
            command["log"] = log
        sent = False
        # The span times command-write -> `started` push: the agent-path
        # analog of submit_task's round-trip, and the number that proves
        # (or disproves) the resident runtime's launch-latency win.
        submit_span = Span(
            "agent.submit", {"address": self.address, "task_id": task_id}
        )
        submit_span.__enter__()
        try:
            await self._send(command)
            sent = True

            def ready(c: "AgentClient"):
                rejection = c._pop_rejection(task_id, "run")
                if rejection is not None:
                    # A definitive rejection means the task never forked:
                    # relaunching through the fallback path is safe.
                    raise rejection
                return c._started.get(task_id)

            # Pop on success: a resident client serves many electrons;
            # per-task entries must not accumulate for the channel's lifetime.
            pid = await self._wait(ready, timeout)
            self._started.pop(task_id, None)
            return pid
        except AgentError as err:
            # Once the run command left for the worker, the harness may
            # already be alive there even though we never saw `started` —
            # the caller must NOT relaunch (double harness), only abort.
            # Exception: an explicit error event proves it never started.
            err.maybe_started = sent and not getattr(  # type: ignore[attr-defined]
                err, "rejected", False
            )
            submit_span.record_error(err)
            raise
        finally:
            submit_span.end()

    async def wait_exit(
        self, task_id: str, timeout: float | None = None
    ) -> tuple[int, int]:
        """Block until the pushed exit event: ``(exit_code, term_signal)``."""
        event = await self._wait(lambda c: c._exits.get(task_id), timeout)
        self._exits.pop(task_id, None)
        return event

    # -- RPC execute-by-digest ----------------------------------------------

    @property
    def registered_digests(self) -> frozenset:
        """Function digests this channel's resident runtime holds."""
        return frozenset(self._registered)

    async def register_fn(
        self,
        digest: str,
        path: str,
        runner: list[str] | None = None,
        timeout: float = 60.0,
    ) -> None:
        """Register a CAS-staged cloudpickled function by its digest.

        The remote side verifies ``path``'s sha256 against ``digest``
        BEFORE unpickling and keeps the loaded callable for invoke-by-
        digest.  Idempotent per client: a digest this channel already
        registered is a no-op.  A digest mismatch (torn or stale CAS
        artifact) raises an :class:`AgentError` tagged PERMANENT via the
        duck-typed ``fault_label`` hook — re-registering identical bytes
        can never succeed, so the resilience layer must not burn gang
        retries on it.  ``runner`` (native agent only) names the argv the
        agent forks per invocation (``[python, harness, --rpc-child]``).
        """
        if digest in self._registered:
            return
        command: dict = {"cmd": "register_fn", "digest": digest, "path": path}
        if runner:
            command["runner"] = [str(part) for part in runner]
        await self._send(command)

        def settled(c: "AgentClient"):
            if digest in c._register_errors:
                code, message = c._register_errors.pop(digest)
                failure = AgentError(
                    f"agent@{c.address}: register {digest[:12]} failed "
                    f"({code}): {message}"
                )
                if code == "digest_mismatch":
                    failure.fault_label = "rpc_digest_mismatch"  # type: ignore[attr-defined]
                    failure.fault_transient = False  # type: ignore[attr-defined]
                raise failure
            return digest in c._registered

        await self._wait(settled, timeout)

    async def invoke(
        self,
        task_id: str,
        digest: str,
        spec: dict | None = None,
        args_b64: str | None = None,
        args_bytes: bytes | None = None,
        args_path: str = "",
        args_digest: str = "",
        path: str = "",
        result_path: str = "",
        result_max_inline: int | None = None,
        timeout: float = 30.0,
    ) -> int:
        """Invoke a registered function by digest; returns the worker pid.

        Args travel inline below the executor's size threshold — as raw
        bytes in a binary frame when the channel negotiated frames
        (``args_bytes``), else base64-in-JSON (``args_b64``, derived from
        ``args_bytes`` automatically) — or by CAS path + digest when
        oversized.  On a frame-negotiated pool channel, inline invokes
        additionally micro-batch: every invoke enqueued in the same event-
        loop turn (window configurable via COVALENT_TPU_RPC_BATCH_WINDOW_MS)
        for the same digest ships as ONE ``multi_invoke`` frame, acked by
        one ``multi_started``, with results fanning back out by op id —
        the shape the fleet scheduler's digest-affinity placement produces.
        ``path`` (the function's CAS artifact) rides along so a restarted
        runtime can self-heal a lost registration, digest-verified.  The
        same size policy applies on the way back: given ``result_path`` +
        ``result_max_inline``, a result pickle over the threshold is
        staged to that remote path (announced by sha256 digest) instead of
        inlined onto the channel in one write.  The ``started`` ack bounds
        this call; the result streams back separately
        (:meth:`wait_result`).
        """
        command: dict = {"cmd": "invoke", "id": task_id, "digest": digest}
        if path:
            command["path"] = path
        if spec:
            command["spec"] = dict(spec)
        framed = (
            self.frames_active and args_bytes is not None and not args_path
        )
        if not framed:
            if args_b64 is None and args_bytes is not None:
                args_b64 = base64.b64encode(args_bytes).decode("ascii")
            if args_b64 is not None:
                command["args"] = args_b64
            elif args_path:
                command["args_path"] = args_path
                if args_digest:
                    command["args_digest"] = args_digest
        if result_path and result_max_inline is not None:
            command["result_path"] = result_path
            command["result_max_inline"] = int(result_max_inline)
        submit_span = Span(
            "agent.invoke", {"address": self.address, "task_id": task_id}
        )
        submit_span.__enter__()
        try:
            if framed and self.mode == "pool":
                self._enqueue_invoke(digest, command, args_bytes or b"")
            elif framed:
                # Native runtime: frames yes, batching no (it forks one
                # runner per invocation — there is nothing to fan back).
                header = dict(command)
                header["_body"] = "args_bytes"
                await self._send_frame(
                    frames.VERB_INVOKE, header, args_bytes or b""
                )
            else:
                await self._send(command)

            def ready(c: "AgentClient"):
                rejection = c._pop_rejection(task_id, "invoke")
                if rejection is not None:
                    raise rejection
                return c._started.get(task_id)

            pid = await self._wait(ready, timeout)
            self._started.pop(task_id, None)
            return pid
        except AgentError as err:
            submit_span.record_error(err)
            raise
        finally:
            submit_span.end()

    # -- invoke micro-batching -----------------------------------------------

    def _enqueue_invoke(
        self, digest: str, command: dict, body: bytes
    ) -> None:
        """Queue one framed invoke; the flusher coalesces per digest."""
        self._pending_invokes.setdefault(digest, []).append((command, body))
        total = sum(len(v) for v in self._pending_invokes.values())
        if not self._flush_scheduled:
            self._flush_scheduled = True
            self._spawn_flush(immediate=False)
        elif total >= _BATCH_MAX_OPS and not self._flush_now:
            # A full batch flushes NOW — skipping any configured window —
            # instead of waiting it out; the windowed flusher will find
            # an empty queue.  One immediate flusher at a time: further
            # over-max enqueues ride the one already scheduled.
            self._flush_now = True
            self._spawn_flush(immediate=True)

    def _spawn_flush(self, immediate: bool) -> None:
        task = asyncio.ensure_future(self._flush_invokes(immediate))
        self._flush_tasks.add(task)
        task.add_done_callback(self._flush_tasks.discard)

    async def _flush_invokes(self, immediate: bool = False) -> None:
        """Ship every queued invoke: one frame per digest group.

        With the default zero window only invokes enqueued in the same
        event-loop turn coalesce — a lone invoke pays no added latency.
        A send failure files a rejection for every op in the group so the
        waiters fail fast instead of sitting out their timeouts.
        """
        if not immediate and _BATCH_WINDOW_S > 0:
            await asyncio.sleep(_BATCH_WINDOW_S)
        else:
            await asyncio.sleep(0)
        pending, self._pending_invokes = self._pending_invokes, {}
        self._flush_scheduled = False
        self._flush_now = False
        for digest, entries in pending.items():
            try:
                await self._send_invoke_group(digest, entries)
            except (AgentError, TransportError, ValueError) as err:
                async with self._cond:
                    for command, _body in entries:
                        tid = str(command.get("id") or "")
                        self._errors[tid] = (
                            f"batched invoke send failed: {err}"
                        )
                    self._cond.notify_all()

    async def _send_invoke_group(self, digest: str, entries: list) -> None:
        if len(entries) == 1:
            command, body = entries[0]
            header = dict(command)
            header["_body"] = "args_bytes"
            await self._send_frame(frames.VERB_INVOKE, header, body)
            return
        ops, bodies = [], []
        fn_path = ""
        for command, body in entries:
            fn_path = fn_path or str(command.get("path") or "")
            ops.append({
                k: v for k, v in command.items()
                if k not in ("cmd", "digest", "path")
            })
            bodies.append(body)
        header: dict = {
            "cmd": "multi_invoke", "digest": digest, "ops": ops,
            "args_lens": [len(b) for b in bodies], "_body": "args_bytes",
        }
        if fn_path:
            header["path"] = fn_path
        await self._send_frame(
            frames.VERB_MULTI_INVOKE, header, b"".join(bodies)
        )

    async def wait_result(
        self, task_id: str, timeout: float | None = None
    ) -> dict:
        """Block until the invocation's pushed ``result`` event."""
        event = await self._wait(lambda c: c._results.get(task_id), timeout)
        self._results.pop(task_id, None)
        return event

    # -- serving sessions ----------------------------------------------------

    async def serve_open(
        self,
        sid: str,
        digest: str,
        path: str,
        options: dict | None = None,
        spec: dict | None = None,
        runner: "list[str] | None" = None,
        timeout: float = 120.0,
    ) -> dict:
        """Open a resident serving session; returns the ``serve_opened``
        event (``slots``, worker ``pid``).

        Ships a cloudpickled model-factory by CAS digest: the worker
        verifies ``path``'s sha256 against ``digest`` BEFORE unpickling,
        calls the factory ONCE (model load + compile — hence the generous
        timeout), and serves request commands for the session's lifetime.
        A refused open raises :class:`AgentError`; permanent refusals
        (digest mismatch, a factory rejecting its model shape) carry the
        duck-typed ``fault_label`` so the resilience layer never burns
        gang retries re-opening them.  ``runner`` (native agent only)
        names the argv forked to host the session
        (``[python, harness, --serve-child]``).
        """
        command: dict = {
            "cmd": "serve_open", "id": sid, "digest": digest, "path": path,
        }
        if options:
            command["options"] = dict(options)
        if spec:
            command["spec"] = dict(spec)
        if runner:
            command["runner"] = [str(part) for part in runner]
        await self._send(command)

        def settled(c: "AgentClient"):
            if sid in c._serve_errors:
                event = c._serve_errors.pop(sid)
                failure = AgentError(
                    f"agent@{c.address}: serve_open {sid} failed "
                    f"({event.get('code')}): {event.get('message')}"
                )
                if event.get("permanent"):
                    failure.fault_label = str(  # type: ignore[attr-defined]
                        event.get("label")
                        or f"serve_{event.get('code') or 'error'}"
                    )
                    failure.fault_transient = False  # type: ignore[attr-defined]
                raise failure
            return c._serve_opened.pop(sid, None)

        return await self._wait(settled, timeout)

    async def serve_request(
        self,
        sid: str,
        rid: str,
        prompt,
        params: dict | None = None,
        deadline_s: float = 0.0,
        tenant: str = "",
        kv_bytes: bytes | None = None,
        kv_digest: str = "",
        kv_path: str = "",
        trace: dict | None = None,
    ) -> None:
        """Submit one request to an open session (fire-and-stream).

        The response streams back over the telemetry side-band as
        ``serve.token`` records routed to the session's
        :meth:`watch_serve` sink; backpressure and unknown sessions
        arrive as ``serve.reject`` records the same way.

        A disaggregated request attaches its prefilled KV bundle:
        ``kv_bytes`` rides a raw binary frame body on a negotiated
        channel (the gang-local fast path), ``kv_path`` references a
        CAS-staged copy (the cross-pool road); either way ``kv_digest``
        is verified worker-side before the engine unpickles anything,
        and any mismatch silently degrades to a full prefill.

        ``trace`` (a :func:`~.obs.trace.context_of` carrier) rides the
        command header so the worker's per-request spans — queue wait,
        admission, decode — join the dispatcher's trace instead of
        starting orphan ones.
        """
        command: dict = {
            "cmd": "serve_request", "id": sid, "rid": rid, "prompt": prompt,
        }
        if params:
            command["params"] = dict(params)
        if deadline_s:
            command["deadline_s"] = float(deadline_s)
        if tenant:
            command["tenant"] = str(tenant)
        if trace:
            command["trace"] = dict(trace)
        if kv_digest:
            command["kv_digest"] = kv_digest
        if kv_path:
            command["kv_path"] = kv_path
        if self.frames_active:
            # Header-only frame (or body-carrying for an inline KV
            # bundle): at serving request rates even the line framing +
            # re-parse tax is worth skipping.
            if kv_bytes is not None and not kv_path:
                command["_body"] = "kv_bytes"
                await self._send_frame(
                    frames.VERB_SERVE, command, kv_bytes
                )
                return
            await self._send_frame(frames.VERB_SERVE, command)
            return
        if kv_bytes is not None and not kv_path:
            command["kv"] = base64.b64encode(kv_bytes).decode("ascii")
        await self._send(command)

    async def serve_prefill(
        self,
        sid: str,
        rid: str,
        prompt,
        params: dict | None = None,
        timeout: float = 60.0,
        trace: dict | None = None,
    ) -> dict:
        """Run a prefill-only pass on an open session; returns the
        ``serve_kv`` event with the bundle under ``data_bytes``.

        The worker's engine packages the prompt's prefilled cache lane
        (plus cursor/rng/sampling state) as a serializable KV bundle and
        streams it back as a raw frame body (base64 on a JSONL channel).
        A worker-side refusal (unknown session, shed, an engine without
        the surface) raises :class:`AgentError` — the disaggregated
        front degrades to a full prefill on the decode replica.

        ``trace`` propagates the requesting stream's trace context so
        the prefill tier's worker span lands in the SAME trace as the
        decode tier's — the cross-tier handoff is one waterfall.
        """
        command: dict = {
            "cmd": "serve_prefill", "id": sid, "rid": rid, "prompt": prompt,
        }
        if params:
            command["params"] = dict(params)
        if trace:
            command["trace"] = dict(trace)
        if self.frames_active:
            await self._send_frame(frames.VERB_SERVE, command)
        else:
            await self._send(command)
        key = f"{sid}/{rid}"

        def settled(c: "AgentClient"):
            return c._serve_kv.pop(key, None)

        event = await self._wait(settled, timeout)
        if event.get("code"):
            raise AgentError(
                f"agent@{self.address}: serve_prefill {rid} failed "
                f"({event.get('code')}): {event.get('message')}"
            )
        if "data_bytes" not in event and event.get("data"):
            try:
                event["data_bytes"] = base64.b64decode(event["data"])
            except (TypeError, ValueError) as err:
                raise AgentError(
                    f"agent@{self.address}: serve_prefill {rid} returned "
                    f"an undecodable bundle: {err}"
                ) from err
        return event

    async def serve_close(self, sid: str, timeout: float = 30.0) -> dict:
        """Close a session; returns the ``serve_closed`` event (``served``
        request count) after the worker drains admitted lanes."""
        await self._send({"cmd": "serve_close", "id": sid})

        def settled(c: "AgentClient"):
            if sid in c._serve_errors:
                event = c._serve_errors.pop(sid)
                failure = AgentError(
                    f"agent@{c.address}: serve_close {sid} failed "
                    f"({event.get('code')}): {event.get('message')}"
                )
                if event.get("permanent"):
                    # Same duck-tag propagation as serve_open: closing a
                    # session that does not exist is deterministic — the
                    # resilience layer must not burn retries on it.
                    failure.fault_label = str(  # type: ignore[attr-defined]
                        event.get("label")
                        or f"serve_{event.get('code') or 'error'}"
                    )
                    failure.fault_transient = False  # type: ignore[attr-defined]
                raise failure
            return c._serve_closed.pop(sid, None)

        return await self._wait(settled, timeout)

    # -- crash recovery (epoch fence, inventories, stream resume) ------------

    async def declare_epoch(self, epoch: int, timeout: float = 15.0) -> dict:
        """Declare this dispatcher's journal epoch on the channel.

        The worker records the highest epoch it has ever seen and refuses
        mutating commands from channels that declared a lower one — the
        split-brain fence.  Raises when THIS channel is the stale one.
        """
        self._epoch_ack = None
        self._errors.pop("", None)
        self._error_codes.pop("", None)
        await self._send({"cmd": "epoch", "epoch": int(epoch)})

        def settled(c: "AgentClient"):
            if c._epoch_ack is not None:
                return c._epoch_ack
            if c._error_codes.get("") == "stale_epoch":
                message = c._errors.pop("", "stale epoch")
                c._error_codes.pop("", None)
                raise AgentError(
                    f"agent@{c.address}: {message}"
                )
            return None

        return await self._wait(settled, timeout)

    async def serve_inventory(self, timeout: float = 30.0) -> dict:
        """Ask the worker which serving sessions survive in-process.

        Returns the ``serve_inventory`` event: per-session sid, factory
        digest, running rids with emitted-token counts, and the finished
        ring — everything the recovery path needs to re-adopt streams.
        """
        self._inventories.pop("serve", None)
        await self._send({"cmd": "serve_inventory"})
        return await self._wait(
            lambda c: c._inventories.pop("serve", None), timeout
        )

    async def task_inventory(self, timeout: float = 30.0) -> dict:
        """Ask the worker which forked task children are still running."""
        self._inventories.pop("task", None)
        await self._send({"cmd": "task_inventory"})
        return await self._wait(
            lambda c: c._inventories.pop("task", None), timeout
        )

    async def serve_resume(
        self, sid: str, rid: str, start: int, timeout: float = 30.0
    ) -> dict:
        """Resume one stream from token ``start`` after re-adoption.

        The worker re-emits ``history[start:]`` on the side-band (under
        the same lock as live chunks, so no gap is possible) and answers
        ``serve_resumed`` with what it knows about the rid: streaming,
        done, pending, or unknown.
        """
        key = f"{sid}/{rid}"
        self._serve_resumed.pop(key, None)
        await self._send({
            "cmd": "serve_resume", "id": sid, "rid": rid, "from": int(start),
        })
        return await self._wait(
            lambda c: c._serve_resumed.pop(key, None), timeout
        )

    async def serve_attach(
        self,
        sid: str,
        adapter: str,
        digest: str,
        path: str,
        timeout: float = 60.0,
    ) -> dict:
        """Splice a LoRA adapter bundle into a *running* session.

        ``path`` names a CAS-staged bundle on the worker host and
        ``digest`` its sha256 — the worker verifies bytes before the
        engine touches them, so a torn stage refuses instead of serving
        garbage.  Returns the ``serve_attached`` ack (content ``digest``
        plus ``attach_s``).  Refusals raise :class:`AgentError`, carrying
        the same permanence duck-tags as serve_open: an engine without an
        adapter bank or a digest mismatch is deterministic and must not
        burn gang retries.
        """
        return await self._serve_attach_rpc(
            {
                "cmd": "serve_attach", "id": sid, "adapter": str(adapter),
                "digest": str(digest), "path": str(path),
            },
            timeout,
        )

    async def serve_detach(
        self, sid: str, adapter: str, timeout: float = 30.0
    ) -> dict:
        """Remove a named adapter from a running session (its decode slot
        frees once in-flight requests pinned to it drain)."""
        return await self._serve_attach_rpc(
            {"cmd": "serve_detach", "id": sid, "adapter": str(adapter)},
            timeout,
        )

    async def _serve_attach_rpc(self, command: dict, timeout: float) -> dict:
        name = str(command["cmd"])
        sid, adapter = str(command["id"]), str(command["adapter"])
        key = f"{name}ed:{sid}/{adapter}"
        self._serve_attached.pop(key, None)
        await self._send(command)

        def settled(c: "AgentClient"):
            return c._serve_attached.pop(key, None)

        event = await self._wait(settled, timeout)
        if event.get("code"):
            failure = AgentError(
                f"agent@{self.address}: {name} {adapter!r} on {sid} failed "
                f"({event.get('code')}): {event.get('message')}"
            )
            if event.get("permanent"):
                failure.fault_label = str(  # type: ignore[attr-defined]
                    event.get("label")
                    or f"serve_{event.get('code') or 'error'}"
                )
                failure.fault_transient = False  # type: ignore[attr-defined]
            raise failure
        return event

    async def serve_cancel(self, sid: str, rid: str) -> None:
        """Cancel one in-flight request on a session (fire-and-forget).

        The hedging path calls this for the LOSING arm the moment the
        winner's first token lands: the worker frees the decode lane and
        finalizes the stream with ``error="cancelled"``.  No ack to wait
        on — the cancel races completion by design, and either terminal
        record settles the same waiter.
        """
        await self._send({"cmd": "serve_cancel", "id": sid, "rid": rid})

    # -- resident-mode profiling ---------------------------------------------

    async def profile_start(
        self,
        profile_id: str,
        trace_dir: str,
        sid: str = "",
        timeout: float = 60.0,
    ) -> dict:
        """Start a ``jax.profiler`` trace inside the resident runtime.

        The pool server runs the trace in its own process (where RPC
        invocations and pool-mode serving sessions execute); the native
        C++ agent forwards the command into a live ``--serve-child``
        session runner (``sid`` pins which one; otherwise the agent picks
        any).  Exactly one trace runs per runtime — a second start is
        refused ``busy``.  Returns the ``profile_started`` event.
        """
        command: dict = {
            "cmd": "profile_start", "id": profile_id, "dir": trace_dir,
        }
        if sid:
            command["sid"] = sid
        await self._send(command)
        return await self._wait(
            self._profile_settled(profile_id, self._profile_started), timeout
        )

    async def profile_stop(
        self,
        profile_id: str,
        artifact_dir: str = "",
        sid: str = "",
        timeout: float = 120.0,
        discard: bool = False,
    ) -> dict:
        """Stop the active trace; returns the ``profile_stopped`` event.

        The worker packages the trace directory into one content-addressed
        ``<sha256>.profile.tgz`` under ``artifact_dir`` (the dispatcher
        points this at the CAS dir) and announces ``path``/``digest``/
        ``bytes`` — the caller fetches and digest-verifies before trusting
        the artifact.  The generous timeout covers tarring a large trace.
        ``discard=True`` (a compensating stop for an abandoned capture)
        skips packaging entirely: the worker deletes the raw trace dir.
        """
        command: dict = {"cmd": "profile_stop", "id": profile_id}
        if artifact_dir:
            command["artifact_dir"] = artifact_dir
        if sid:
            command["sid"] = sid
        if discard:
            command["discard"] = True
        await self._send(command)
        return await self._wait(
            self._profile_settled(profile_id, self._profile_stopped), timeout
        )

    async def profile_wait_stopped(
        self, profile_id: str, timeout: float = 120.0
    ) -> dict:
        """Wait out an in-flight stop's ``profile_stopped`` WITHOUT
        re-sending the command — the worker packages the trace on a
        thread, and a resend during packaging is refused ("already
        stopping"), abandoning the artifact it is about to announce."""
        return await self._wait(
            self._profile_settled(profile_id, self._profile_stopped), timeout
        )

    def _profile_settled(self, profile_id: str, table: dict):
        def settled(c: "AgentClient"):
            if profile_id in c._profile_errors:
                event = c._profile_errors.pop(profile_id)
                raise AgentError(
                    f"agent@{c.address}: profile {profile_id} failed "
                    f"({event.get('code')}): {event.get('message')}"
                )
            return table.pop(profile_id, None)

        return settled

    def watch_serve(self, sid: str, sink) -> None:
        """Route session ``sid``'s side-band records to ``sink(sid, data)``
        (instead of :attr:`on_telemetry`).  Register BEFORE the first
        request so no token can slip past."""
        self._serve_sinks[sid] = sink

    def unwatch_serve(self, sid: str) -> None:
        """Drop a closed session's sink and retained per-sid state."""
        self._serve_sinks.pop(sid, None)
        self._telemetry_seq.pop(sid, None)
        self._serve_opened.pop(sid, None)
        self._serve_errors.pop(sid, None)
        self._serve_closed.pop(sid, None)
        for key in [
            k for k in self._serve_kv if k.startswith(f"{sid}/")
        ]:
            del self._serve_kv[key]
        for key in [
            k for k in self._serve_attached
            if k.partition(":")[2].startswith(f"{sid}/")
        ]:
            del self._serve_attached[key]

    async def wait_dead(self) -> None:
        """Block until this channel dies, then raise :class:`AgentError`.

        The serving tier's supervisor awaits this to notice a dropped
        channel (or dead resident worker) the moment the reader does,
        triggering its reconnect instead of waiting on a stuck stream.
        """
        await self._wait(lambda c: None, None)

    def forget(self, task_id: str) -> None:
        """Drop any retained state for a finished/abandoned task.

        Called by the executor when an operation leaves its books — on
        EVERY exit path (success, kill, channel death, retry teardown):
        a straggler's unconsumed exit event, an unclaimed RPC result, the
        telemetry seq high-water mark, and any stored rejection must not
        accumulate for the channel's lifetime.
        """
        self._started.pop(task_id, None)
        self._exits.pop(task_id, None)
        self._errors.pop(task_id, None)
        self._error_codes.pop(task_id, None)
        self._results.pop(task_id, None)
        if task_id not in self._serve_sinks:
            # Serving sessions outlive electron operations on the same
            # channel: an electron's forget() must never reset a live
            # session's seq high-water mark (token dedup depends on it).
            self._telemetry_seq.pop(task_id, None)

    async def kill(self, task_id: str, sig: int = 15) -> None:
        await self._send({"cmd": "kill", "id": task_id, "sig": sig})

    async def _send(self, command: dict) -> None:
        if self._dead is not None:
            raise AgentError(f"agent@{self.address} channel died: {self._dead}")
        _AGENT_RPCS.labels(cmd=str(command.get("cmd", "?"))).inc()
        line = json.dumps(command)
        AGENT_FRAMES_TOTAL.labels(
            verb=str(command.get("cmd", "?")), encoding="jsonl"
        ).inc()
        AGENT_WIRE_BYTES_TOTAL.labels(
            direction="up", encoding="jsonl"
        ).inc(len(line) + 1)
        try:
            await self._process.write_line(line)
        except TransportError as err:
            raise AgentError(f"agent@{self.address}: send failed: {err}") from err

    async def _send_frame(
        self, verb: int, header: dict, body: bytes = b""
    ) -> None:
        """One binary frame down the channel (negotiated path only)."""
        if self._dead is not None:
            raise AgentError(f"agent@{self.address} channel died: {self._dead}")
        _AGENT_RPCS.labels(cmd=str(header.get("cmd", "?"))).inc()
        payload = frames.encode_frame(
            verb, header, body, codec=self._frame_codec
        )
        AGENT_FRAMES_TOTAL.labels(
            verb=frames.VERB_NAMES.get(verb, str(verb)), encoding="binary"
        ).inc()
        AGENT_WIRE_BYTES_TOTAL.labels(
            direction="up", encoding="binary"
        ).inc(len(payload))
        try:
            await self._process.write_bytes(payload)
        except TransportError as err:
            raise AgentError(f"agent@{self.address}: send failed: {err}") from err
