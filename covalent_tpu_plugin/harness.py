"""Remote execution harness — runs ON each TPU-VM worker.

TPU-native counterpart of the reference's ``covalent_ssh_plugin/exec.py``
template.  Two structural changes:

* The reference ``.format()``-instantiates its script per task
  (``ssh.py:160-171``), which forbids literal braces anywhere in the file
  (``exec.py`` header comment).  This harness is instead a *static* module
  copied verbatim to the worker and invoked as
  ``python harness.py <task_spec.json>`` — all per-task parameters travel in
  a small JSON spec, so one upload is reusable and the brace constraint
  disappears.
* Before touching the pickled function it wires up the multi-host data
  plane: ``jax.distributed.initialize(coordinator_address, num_processes,
  process_id)`` (SURVEY §2.4), then after the task it materialises device
  arrays to host memory and lets only process 0 write the result pickle.

The file protocol is otherwise the reference's: read ``(fn, args, kwargs)``
(``exec.py:29-30``), chdir into the task workdir (``exec.py:33-35``), run the
function catching any exception (``exec.py:37-40``), always write the
``(result, exception)`` pair (``exec.py:45-46``) — written atomically via a
temp file + rename so the dispatcher's status probe never sees a torn file.

MUST remain standalone: stdlib + cloudpickle (+ jax when present) only, since
it runs on workers where this package is not installed.
"""

from __future__ import annotations

import json
import os
import re
import struct
import sys
import threading
import time
import zlib

#: Fallback for ``_process_start`` (monotonic clock).
_T_LOADED = time.monotonic()


#: Per-process worker-event sequence + write-failure accounting.  The seq
#: lets the dispatcher dedup re-delivered lines (the telemetry side-band
#: re-tails from offset 0 after a reconnect) and is shared by EVERY worker
#: record — lifecycle events, streamed heartbeats, and the heartbeat
#: snapshot file all draw from one locked counter, so the dispatcher's
#: seq-based dedup compares a single monotonic domain whichever road a
#: record arrives by.  The failure counter backs the swallow-and-count
#: contract — an unwritable/ENOSPC events path must never take down the
#: task it was observing, but the first failure leaves one line on stderr
#: so the silence is diagnosable from the task log.
_worker_event_seq = 0
_worker_event_lock = threading.Lock()
_worker_event_failures = 0


def _build_worker_event(spec: dict, type: str, **fields) -> dict:
    """One worker record: ts/pid/seq envelope + trace context + fields.

    The single assembly point for every worker-side record (events and
    heartbeat snapshots alike), so the schema cannot drift between sinks
    and the seq counter stays atomic under the heartbeat thread.
    """
    global _worker_event_seq
    with _worker_event_lock:
        _worker_event_seq += 1
        seq = _worker_event_seq
    trace = spec.get("trace") or {}
    event = {
        "ts": round(time.time(), 6),
        "pid": os.getpid(),
        "seq": seq,
        "type": type,
        "operation_id": spec.get("operation_id"),
    }
    if trace.get("trace_id"):
        event["trace_id"] = trace.get("trace_id")
        event["parent_id"] = trace.get("span_id")
        if trace.get("attempt") is not None:
            event["attempt"] = trace.get("attempt")
    event.update(fields)
    return event


def _append_event_line(event: dict, paths: list) -> None:
    """Swallow-and-count JSONL append of one event to every sink path."""
    global _worker_event_failures
    try:
        line = json.dumps(event, default=repr) + "\n"
    except (TypeError, ValueError):
        return
    for path in paths:
        try:
            with open(path, "a", encoding="utf-8") as f:
                f.write(line)
        except OSError as err:
            _worker_event_failures += 1
            if _worker_event_failures == 1:
                print(
                    f"worker events unwritable ({path}: {err}); "
                    "further failures swallowed",
                    file=sys.stderr,
                )


def _worker_event_paths(spec: dict) -> list:
    """Every sink one worker event lands in (deduped, order-stable).

    ``events_file`` is the dispatcher's own stream (shared filesystem);
    ``telemetry_file`` is the per-task side-band the resident agent tails
    back over its channel.  Heartbeats go to the telemetry file only (see
    ``_start_heartbeat``); lifecycle events go to both.
    """
    paths = []
    for key in ("events_file", "telemetry_file"):
        path = spec.get(key)
        if path and path not in paths:
            paths.append(path)
    env_path = os.environ.get("COVALENT_TPU_EVENTS_PATH")
    if not paths and env_path:
        paths.append(env_path)
    return paths


def _emit_worker_event(spec: dict, type: str, _paths=None, **fields) -> None:
    """Append one structured JSONL event from the worker side.

    Mirrors the dispatcher's ``obs.events`` line format (ts/pid/type) but
    stays stdlib-only — this file runs on workers where the plugin is not
    installed.  Sink paths come from the spec (``events_file`` /
    ``telemetry_file``, set by the stager) or the worker's own
    ``COVALENT_TPU_EVENTS_PATH``; no path means no-op.  Trace context from
    the spec (``trace``: trace/parent span ids + attempt) is stamped on
    every event so worker-side records join the dispatch trace.

    Never raises: write failures are swallowed and counted, with a single
    stderr note on the first one — an ENOSPC events disk must not fail the
    electron it was observing.
    """
    paths = _worker_event_paths(spec) if _paths is None else _paths
    if not paths:
        return
    _append_event_line(_build_worker_event(spec, type, **fields), paths)


# --------------------------------------------------------------------------
# The worker's half of the trace.  One recorder for every runtime: a
# launch-mode task keeps its records in a list and writes them once, in
# the result file's trailer; an RPC invocation sends its list in one
# record, a serving session each span as it ends, over the telemetry
# side-band.  The dispatcher re-emits them with these ids kept
# (``obs.trace.record_remote_span``).
# --------------------------------------------------------------------------


class _Annotated:
    """``with _Annotated(name):`` — a ``jax.profiler.TraceAnnotation`` where
    jax is already imported in this process, a no-op where it is not: a
    profiler capture's host plane then shows what the program did, on the
    device's clock.  jax is never imported for it, and with no profiler
    session open entering one costs a flag test."""

    __slots__ = ("_annotation",)

    def __init__(self, name: str) -> None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        self._annotation = (
            None if profiler is None else profiler.TraceAnnotation(name)
        )

    def __enter__(self) -> "_Annotated":
        if self._annotation is not None:
            self._annotation.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        return False


def _emit_span(
    sink, name: str, trace, t0: float, t1: float | None = None,
    status: str = "OK", **attrs,
) -> None:
    """Hand ``sink`` one span record of the worker's own work.

    ``trace`` is a ``context_of`` carrier (the task spec's ``trace``, or
    the per-request one off a ``serve_request``/``serve_prefill`` header);
    without one (an old dispatcher, a malformed carrier) the span is
    dropped — a worker must never mint orphan traces the store can't
    finalize.  ``t0``/``t1`` are monotonic stamps; the wall-clock
    ``start_ts`` is reconstructed here so the two clock domains never mix
    on the wire.
    """
    if not isinstance(trace, dict) or not trace.get("trace_id"):
        return
    t1 = time.monotonic() if t1 is None else t1
    parent = trace.get("span_id")
    fields = {
        "name": name,
        "trace_id": str(trace["trace_id"]),
        "parent_id": str(parent) if parent else None,
        "span_id": os.urandom(8).hex(),
        "start_ts": round(time.time() - (time.monotonic() - t0), 6),
        "duration_s": round(max(0.0, t1 - t0), 6),
        "status": status,
    }
    if attrs:
        fields["attributes"] = attrs
    sink(fields)


class _WorkerSpan(_Annotated):
    """One timed segment of a task: monotonic stamps, the annotation beside
    it, one record through :func:`_emit_span` on exit — ``ERROR`` when the
    block raised (the exception propagates)."""

    __slots__ = ("_sink", "_name", "_trace", "_t0")

    def __init__(self, sink, name: str, trace) -> None:
        super().__init__(name)
        self._sink, self._name, self._trace = sink, name, trace

    def __enter__(self) -> "_WorkerSpan":
        self._t0 = time.monotonic()
        super().__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        super().__exit__(exc_type, exc, tb)
        _emit_span(
            self._sink, self._name, self._trace, self._t0,
            status="OK" if exc is None else "ERROR",
        )
        return False


def _process_start() -> float:
    """When this process started, as a ``time.monotonic()`` stamp: the
    kernel's own record of it (a forked task's is its fork), or this
    module's load where ``/proc`` cannot say."""
    try:
        with open("/proc/self/stat", encoding="utf-8") as f:
            ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - ticks / os.sysconf(
            "SC_CLK_TCK")
        return time.monotonic() - max(0.0, age)
    except (OSError, ValueError, IndexError, AttributeError):
        return _T_LOADED


def _obs_call(module: str, function: str, *args, **kwargs):
    """``function`` of one of the program's own accounts (``obs.jitstats``:
    its compiles; ``obs.modelstats``: what its train steps counted about
    their model; ``obs.stepstats``: how the host drove those steps), where
    the program loaded the module; a plain-Python task never did, and
    nothing is imported to ask.  None where it is not loaded or failed."""
    account = sys.modules.get(f"covalent_tpu_plugin.obs.{module}")
    if account is None:
        return None
    try:
        return getattr(account, function)(*args, **kwargs)
    except Exception:  # noqa: BLE001 - observability never fails the task
        return None


def _obs_totals(module: str) -> dict | None:
    """``totals()`` of one of the program's own accounts, where it has
    something to say."""
    return _obs_call(module, "totals") or None


def _trace_trailer(spans: list) -> bytes:
    """What follows the ``(result, exception)`` pickle in a launch-mode
    result file: one JSON line with the worker's spans, its compile
    counters and what its train steps recorded.  ``pickle.load`` stops at
    the pickle's end, so every reader of the pair is unaffected;
    ``utils.serialize.load_result_and_trailer`` reads both."""
    trailer: dict = {"spans": spans}
    for key, module in (("jit", "jitstats"), ("model", "modelstats"),
                        ("steps", "stepstats")):
        totals = _obs_totals(module)
        if totals is not None:
            trailer[key] = totals
    try:
        return b"\n" + json.dumps(trailer, default=repr).encode() + b"\n"
    except (TypeError, ValueError):
        return b""


def live_backend() -> str:
    """Platform of THIS process's initialised XLA backend, or ``""``.

    Never the thing that initialises a backend, and never an importer: it
    reads ``sys.modules`` only.  Heartbeat threads ask while the task's own
    thread may be halfway through ``import jax``, and a second thread
    entering that import graph from the side makes Python hand one of them
    a partially initialised module.
    """
    jax = sys.modules.get("jax")
    bridge = sys.modules.get("jax._src.xla_bridge")
    initialised = getattr(bridge, "backends_are_initialized", None)
    if jax is None or initialised is None or not initialised():
        return ""
    return jax.default_backend()


def _heartbeat_payload(
    metrics_file: str, late_s: float | None = None, stacks_file: str = "",
) -> dict:
    """One heartbeat's body: process vitals + user-published progress.

    Everything best-effort and stdlib-only.  The user function publishes
    progress (step counter, examples/s, tokens/s, ...) by writing a small
    JSON object to ``$COVALENT_TPU_WORKER_METRICS_PATH``; the beat folds it
    in verbatim.  jax device-memory stats are read ONLY when the task
    already imported jax AND a backend is live — the heartbeat thread must
    never be the thing that triggers (or races) backend initialization.
    ``late_s``: how far past its due time the previous beat finished.
    Where the program's train steps are recorded (``obs.stepstats``),
    ``steps`` says how many were steady and how long the one in progress
    has run, and stall dumps go to ``stacks_file``.
    """
    payload: dict = {}
    if late_s is not None:
        payload["late_s"] = late_s
    try:
        import resource

        usage = resource.getrusage(resource.RUSAGE_SELF)
        # ru_maxrss is KiB on Linux, bytes on macOS.
        scale = 1 if sys.platform == "darwin" else 1024
        payload["rss_bytes"] = int(usage.ru_maxrss) * scale
    except Exception:  # noqa: BLE001 - vitals are best-effort
        pass
    steps = _obs_call("stepstats", "beat", stacks_path=stacks_file or None)
    if steps:
        payload["steps"] = steps
    if metrics_file:
        try:
            with open(metrics_file, encoding="utf-8") as f:
                user = json.load(f)
            if isinstance(user, dict):
                step = user.pop("step", None)
                if isinstance(step, (int, float)):
                    payload["step"] = step
                if user:
                    payload["metrics"] = user
        except (OSError, ValueError):
            pass
    serve = _serve_occupancy()
    if serve:
        # Resident serving sessions (if any) fold their slot occupancy into
        # every beat, so a serving worker's liveness stream doubles as its
        # load report on the dispatcher side.
        payload["serve"] = serve
    if live_backend():
        try:
            import jax

            stats = jax.local_devices()[0].memory_stats() or {}
            mem = {
                k: stats[k]
                for k in ("bytes_in_use", "peak_bytes_in_use")
                if k in stats
            }
            if mem:
                payload["device_mem"] = mem
        except Exception:  # noqa: BLE001 - absent on CPU backends
            pass
    return payload


def _start_heartbeat(spec: dict):
    """Launch the heartbeat thread; returns a stop Event (or None).

    Cadence comes from the spec's ``heartbeat_s`` (0/absent disables).
    Each beat does two things:

    * emits a ``worker.heartbeat`` event into the *telemetry* side-band
      file (never the shared lifecycle stream — beats are high-volume
      plumbing, not dispatch history), which the resident agent tails
      back to the dispatcher in near-real-time;
    * atomically refreshes a tiny snapshot file (``<pid_file>.hb``) that
      the dispatcher's status probe reads piggybacked on its existing
      round trip, so the poll path gets liveness for free.

    The first beat fires immediately so even sub-second electrons leave
    one, and the dispatcher's stall detector has a baseline to age.  Beats
    are due on a fixed cadence of the monotonic clock; each measures how
    far past its due time it finished, its own work (device memory stats,
    the two writes) included, carries that in the next beat (``late_s``)
    and hands it to the train step record (``obs.stepstats``), where a
    stall's stacks go to ``<pid_file>.stacks``.
    """
    try:
        interval = float(spec.get("heartbeat_s") or 0)
    except (TypeError, ValueError):
        interval = 0.0
    if interval <= 0:
        return None
    # Resolve every side-band path to absolute BEFORE the task chdirs into
    # its workdir: the beat thread runs concurrently with the chdir'd
    # function, and a relative remote_cache would otherwise scatter
    # snapshots across working directories.
    pid_file = spec.get("pid_file")
    hb_file = os.path.abspath(f"{pid_file}.hb") if pid_file else None
    metrics_file = (
        os.path.abspath(f"{pid_file}.metrics") if pid_file else ""
    )
    stacks_file = os.path.abspath(f"{pid_file}.stacks") if pid_file else ""
    if metrics_file:
        # The user function's progress-publishing hook.
        os.environ["COVALENT_TPU_WORKER_METRICS_PATH"] = metrics_file
    telemetry_paths = [
        os.path.abspath(p) for p in (spec.get("telemetry_file"),) if p
    ]
    stop = threading.Event()

    def beat_loop() -> None:
        hb_seq = 0
        due = time.monotonic()
        late = None
        while True:
            hb_seq += 1
            # ONE event, one seq, two sinks: the streamed telemetry line
            # and the probe-read snapshot must be the same record so the
            # dispatcher's seq dedup works across delivery roads (e.g. an
            # agent-channel death downgrading to the polling path).
            event = _build_worker_event(
                spec, "worker.heartbeat",
                hb_seq=hb_seq, interval_s=interval,
                **_heartbeat_payload(metrics_file, late, stacks_file),
            )
            if telemetry_paths:
                _append_event_line(event, telemetry_paths)
            if hb_file:
                try:
                    tmp = f"{hb_file}.tmp.{os.getpid()}"
                    with open(tmp, "w", encoding="utf-8") as f:
                        f.write(json.dumps(event, default=repr))
                    os.replace(tmp, hb_file)
                except (OSError, TypeError, ValueError):
                    pass  # liveness reporting must never fail the task
            now = time.monotonic()
            late = round(max(0.0, now - due), 6)
            _obs_call("stepstats", "beat", late_s=late)
            # A late beat does not make the next ones hurry to catch up.
            due = max(due + interval, now)
            if stop.wait(due - now):
                return

    thread = threading.Thread(
        target=beat_loop, name="covalent-tpu-heartbeat", daemon=True
    )
    thread.start()
    return stop


# --------------------------------------------------------------------------
# Cooperative checkpointing (elastic gangs).
#
# A training electron registers a snapshot hook via
# ``covalent_tpu_plugin.utils.checkpoint.register_snapshot``; this harness
# (stdlib-only — the package is looked up through sys.modules, never
# imported) calls it on the configured interval and on SIGTERM (the spot
# preemption notice), publishing each snapshot as a sha256-named bundle in
# the worker's remote CAS plus an atomically-replaced per-lineage manifest.
# A kill mid-save can never tear the "latest": bundles publish tmp+replace
# and the manifest only ever references fully-written files, so the
# dispatcher's resume discovery (which digest-verifies every candidate)
# either finds a complete checkpoint or falls back to the previous one.
# --------------------------------------------------------------------------

def _sanitize_lineage(lineage: str) -> str:
    import re

    return re.sub(r"[^A-Za-z0-9._-]", "_", str(lineage))


def _ckpt_manifest_path(directory: str, lineage: str) -> str:
    return os.path.join(directory, f"ckpt_{_sanitize_lineage(lineage)}.json")


def _write_checkpoint_bundle(
    directory: str, lineage: str, step: int, tree, keep_n: int
) -> tuple:
    """Publish one checkpoint bundle atomically; returns (path, digest, n).

    Bundle = pickled ``{"v", "lineage", "step", "tree", "meta"}`` named by
    the sha256 of its bytes (a CAS artifact: the dispatcher re-stages it to
    replacement workers through the ordinary content-addressed upload
    path).  The manifest keeps a newest-first ``history`` of the last
    ``keep_n`` complete steps; bundles that fall off it are unlinked, so
    checkpoint output is bounded however long the task runs.
    """
    import hashlib

    try:
        import cloudpickle as pickler
    except ImportError:
        import pickle as pickler
    payload = pickler.dumps({
        "v": 1,
        "lineage": lineage,
        "step": int(step),
        "tree": _to_host(tree),
        "meta": {"saved_at": time.time(), "pid": os.getpid()},
    })
    digest = hashlib.sha256(payload).hexdigest()
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, f"{digest}.ckpt")
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(payload)
    os.replace(tmp, path)

    manifest_path = _ckpt_manifest_path(directory, lineage)
    # The manifest update is a read-modify-write: two process-0 writers
    # CAN coexist on a shared filesystem (a straggling old gang inside
    # its preemption grace window and the resumed replacement), and the
    # loser of an unlocked race would silently drop the other's newest
    # entry — both costing recompute on the next resume and leaking its
    # bundle past the keep_n GC forever.  flock serializes them; hosts
    # without fcntl (or filesystems without lock support) degrade to the
    # unlocked behavior.
    lock_fd = None
    try:
        import fcntl

        lock_fd = os.open(
            f"{manifest_path}.lock", os.O_CREAT | os.O_RDWR, 0o644
        )
        fcntl.flock(lock_fd, fcntl.LOCK_EX)
    except (ImportError, OSError):
        if lock_fd is not None:
            os.close(lock_fd)
        lock_fd = None
    try:
        return _publish_manifest(
            manifest_path, lineage, path, digest, payload, step, keep_n
        )
    finally:
        if lock_fd is not None:
            os.close(lock_fd)  # closing releases the flock


def _publish_manifest(
    manifest_path: str, lineage: str, path: str, digest: str,
    payload: bytes, step: int, keep_n: int,
) -> tuple:
    """Manifest read-modify-write + GC (under the caller's flock)."""
    history = []
    try:
        with open(manifest_path, encoding="utf-8") as f:
            manifest = json.load(f)
        if isinstance(manifest, dict) and isinstance(
            manifest.get("history"), list
        ):
            history = [
                h for h in manifest["history"]
                if isinstance(h, dict) and h.get("step") != int(step)
            ]
    except (OSError, ValueError):
        pass  # missing or torn manifest: rebuild from this save
    history.insert(
        0, {"step": int(step), "digest": digest, "file": path,
            "bytes": len(payload)},
    )
    # Highest step first, not insertion order: a straggling old gang and
    # a resumed replacement can interleave saves on a shared filesystem,
    # and resume discovery must always see the furthest-trained state at
    # the head.
    history.sort(
        key=lambda h: h.get("step", -1)
        if isinstance(h.get("step"), int) else -1,
        reverse=True,
    )
    keep_n = max(1, int(keep_n or 1))
    dropped, history = history[keep_n:], history[:keep_n]
    tmp_manifest = f"{manifest_path}.tmp.{os.getpid()}"
    with open(tmp_manifest, "w", encoding="utf-8") as f:
        json.dump(
            {"lineage": lineage, "updated": time.time(),
             "history": history},
            f,
        )
    os.replace(tmp_manifest, manifest_path)
    live = {h["digest"] for h in history}
    for old in dropped:
        if old.get("digest") in live:
            continue
        try:
            os.unlink(old.get("file") or "")
        except OSError:
            pass
    return path, digest, len(payload)


def _start_checkpointer(spec: dict):
    """Interval checkpointer for one task; returns ``(stop, save_now)``.

    ``save_now(trigger)`` takes one snapshot synchronously (used by both
    the interval thread and the SIGTERM handler; a shared lock + step
    high-water mark make concurrent calls safe and idempotent).  Only
    process 0 checkpoints — the snapshot hook's train state is replicated
    across the gang (the same single-writer contract as the result file).
    """
    cfg = spec.get("checkpoint") or {}
    try:
        interval = float(cfg.get("interval_s") or 0)
    except (TypeError, ValueError):
        interval = 0.0
    distributed = spec.get("distributed") or {}
    process_id = int(distributed.get("process_id") or 0)
    if interval <= 0 or not cfg.get("dir") or process_id != 0:
        return None, None
    directory = os.path.abspath(str(cfg["dir"]))
    lineage = str(cfg.get("lineage") or spec.get("operation_id") or "task")
    keep_n = int(cfg.get("keep_n") or 3)
    state = {"last_step": None, "failures": 0}
    lock = threading.Lock()

    def save_now(trigger: str):
        module = sys.modules.get("covalent_tpu_plugin.utils.checkpoint")
        take = getattr(module, "take_snapshot", None)
        if take is None:
            return None  # electron never registered a hook
        try:
            snap = take()
        except Exception as err:  # noqa: BLE001 - user hook
            state["failures"] += 1
            if state["failures"] == 1:
                print(f"snapshot hook failed: {err!r}", file=sys.stderr)
            _emit_worker_event(
                spec, "worker.checkpoint_error", lineage=lineage,
                trigger=trigger, error=repr(err),
            )
            return None
        if snap is None:
            return None
        tree, step = snap
        step = int(step)
        if step < 0:
            return None
        with lock:
            last = state["last_step"]
            if last is not None and step <= last:
                return None  # nothing new since the previous save
            path, digest, nbytes = _write_checkpoint_bundle(
                directory, lineage, step, tree, keep_n
            )
            state["last_step"] = step
        _emit_worker_event(
            spec, "worker.checkpoint_saved", lineage=lineage, step=step,
            digest=digest, path=path, bytes=nbytes, trigger=trigger,
        )
        return step

    stop = threading.Event()

    def loop() -> None:
        while not stop.wait(interval):
            try:
                save_now("interval")
            except Exception as err:  # noqa: BLE001 - never kill the task
                state["failures"] += 1
                if state["failures"] == 1:
                    print(f"checkpoint save failed: {err!r}", file=sys.stderr)

    threading.Thread(
        target=loop, name="covalent-tpu-checkpointer", daemon=True
    ).start()
    return stop, save_now


def _install_preempt_handler(spec: dict, save_now) -> None:
    """SIGTERM = the spot preemption notice: final snapshot, then die.

    The handler emits ``worker.preempt_notice`` (streamed up the telemetry
    side-band so the dispatcher can label the coming death), takes one
    last cooperative checkpoint inside the grace window, restores the
    default disposition and re-raises SIGTERM so the process exits with
    the true signal status the dispatcher's pollers expect.
    """
    import signal

    if threading.current_thread() is not threading.main_thread():
        return  # signal API is main-thread-only (RPC invocations skip)

    def _on_term(signum, frame):
        _emit_worker_event(spec, "worker.preempt_notice", signal="SIGTERM")
        try:
            if save_now is not None:
                save_now("preempt")
        except Exception:  # noqa: BLE001 - dying anyway; save is best-effort
            pass
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
        os.kill(os.getpid(), signal.SIGTERM)

    try:
        signal.signal(signal.SIGTERM, _on_term)
    except (ValueError, OSError):  # pragma: no cover - exotic platforms
        pass


def install_pip_deps(pip_deps: list) -> None:
    """Install an electron's pip dependencies; raise RuntimeError on failure.

    Shared contract between this worker harness and the in-process
    LocalExecutor (reference ct.DepsPip, svm_workflow.py:6,19).  The
    command is overridable via ``COVALENT_TPU_PIP_CMD`` for sandboxed test
    environments.
    """
    import shlex
    import subprocess

    pip_cmd = shlex.split(
        os.environ.get("COVALENT_TPU_PIP_CMD", "")
    ) or [sys.executable, "-m", "pip", "install"]
    proc = subprocess.run(
        pip_cmd + list(pip_deps), capture_output=True, text=True
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"pip dependency install failed "
            f"({' '.join(pip_deps)}): {proc.stderr.strip()}"
        )


def _fallback_result(result_file: str, error: BaseException) -> None:
    """Best-effort ``(None, error)`` write with stdlib pickle, mirroring the
    reference's cloudpickle-ImportError path (``exec.py:16-24``)."""
    import pickle

    tmp = result_file + ".tmp"
    with open(tmp, "wb") as f:
        pickle.dump((None, error), f)
    os.replace(tmp, result_file)


def _to_host(tree):
    """Materialise jax arrays onto the host before pickling."""
    # If the task never imported jax there can be no device arrays in the
    # result — skip the (multi-second) jax import entirely.
    if "jax" not in sys.modules:
        return tree
    try:
        import jax
    except Exception:
        return tree
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()
    return jax.tree_util.tree_map(
        lambda x: jax.device_get(x) if hasattr(x, "devices") else x, tree
    )


def _apply_spec_env(spec: dict) -> None:
    """Apply the task's env contract to THIS process.

    os.environ entries, a sys.path mirror for PYTHONPATH, the jax platform
    pin, and this file's own directory taken out of the file names jax
    records (``JAX_HLO_SOURCE_FILE_CANONICALIZATION_REGEX``, where the task's
    env does not set it).  Shared by the per-task harness (``run_task``) and RPC
    invocations executing inside the resident server — one server serves
    one executor, so ``task_env`` is constant across its invocations and
    the process-wide mutation is idempotent by construction.

    Every other ``JAX_*`` variable (``JAX_COMPILATION_CACHE_DIR`` above
    all) counts only if it lands here before this process first imports
    jax, which reads them once: true for a forked task and for the first
    session or invocation of a server that did not preload jax.
    """
    env = spec.get("env") or {}
    for key, value in env.items():
        os.environ[key] = str(value)
    # jax writes the Python traceback of every operation into what it lowers,
    # a Mosaic kernel's payload among it, and that keys the persistent compile
    # cache.  A frame of this file is in reach of those tracebacks, and its
    # staged copy lies in a directory the executor picked for this run: taken
    # out of the file names, the same program finds its cache entry again.
    os.environ.setdefault(
        "JAX_HLO_SOURCE_FILE_CANONICALIZATION_REGEX",
        re.escape(os.path.dirname(os.path.abspath(__file__)) + os.sep))
    if "PYTHONPATH" in env:
        # The interpreter already started; os.environ alone no longer affects
        # import resolution.  Mirror the entries into sys.path so task_env
        # PYTHONPATH means what users expect.
        for entry in reversed(str(env["PYTHONPATH"]).split(os.pathsep)):
            if entry and entry not in sys.path:
                sys.path.insert(0, entry)
    # jax reads JAX_PLATFORMS once, at import: a resident interpreter that
    # preloaded jax (or already ran an invocation) no longer sees the env
    # write above, so the spec's platform is pinned through jax.config —
    # explicit even when "" (auto-select).  The pin only takes before the
    # first backend use; a process already initialised under another
    # platform list cannot honour it, and that is the task's error, never
    # a task that quietly runs somewhere else.
    if "JAX_PLATFORMS" in env:
        platforms = str(env["JAX_PLATFORMS"])
        import jax

        if live_backend() and (jax.config.jax_platforms or "") != platforms:
            raise RuntimeError(
                f"task_env pins JAX_PLATFORMS={platforms!r} but pid "
                f"{os.getpid()} already initialised its backends under "
                f"{jax.config.jax_platforms!r}"
            )
        jax.config.update("jax_platforms", platforms)


def run_task(spec: dict) -> int:
    """Execute one staged task described by ``spec``.  Returns the exit code.

    The task's own waterfall goes home in the result file's trailer: five
    spans on this process's monotonic clock, under the dispatcher's
    ``executor.run`` (``spec["trace"]``).  ``worker.boot``: process start
    to the first read of the staged function (interpreter, env contract,
    pip deps, imports); ``worker.load``: digest check, the distributed
    bootstrap where there is one, unpickle; ``worker.execute``: the user's
    function and only it (``ERROR`` when it raised); ``worker.to_host``;
    ``worker.store``: the result pickle's write (the rename that publishes
    it follows: the trailer has to be inside the file it publishes).
    """
    result_file = spec["result_file"]
    trace = spec.get("trace")
    spans: list = []

    pid_file = spec.get("pid_file")
    if pid_file:
        # First thing, before any failure mode: the dispatcher's orphan
        # cleanup kills by this pid when a launch channel dies mid-submit
        # (a pool fork keeps the server's cmdline, so pkill can't find it).
        # Atomic write: a reader must never observe an empty pid file.
        tmp_pid = f"{pid_file}.tmp.{os.getpid()}"
        with open(tmp_pid, "w") as f:
            f.write(str(os.getpid()))
        os.replace(tmp_pid, pid_file)

    distributed = spec.get("distributed")
    process_id = int(distributed["process_id"]) if distributed else 0
    try:
        _apply_spec_env(spec)
    except Exception as env_error:  # noqa: BLE001 - transported to dispatcher
        if process_id == 0:
            _fallback_result(result_file, env_error)
        return 1

    # Event sinks resolve to absolute BEFORE the task chdirs into its
    # workdir: mid-task emissions (checkpoint saves, the SIGTERM
    # preemption notice) race the chdir'd function, and a relative
    # events path would scatter them across working directories.
    for sink_key in ("events_file", "telemetry_file"):
        if spec.get(sink_key):
            spec[sink_key] = os.path.abspath(spec[sink_key])

    _emit_worker_event(spec, "worker.task_started", process_id=process_id)
    # Liveness starts before any blocking stage (pip install, distributed
    # barrier, the task itself): a worker hung anywhere keeps beating —
    # and one that goes silent is genuinely wedged.
    heartbeat_stop = _start_heartbeat(spec)

    # Elastic gangs: interval checkpointer (the SIGTERM preemption handler
    # is installed LATER, after the distributed bootstrap — jax's
    # distributed runtime registers its own signal handlers during
    # initialize and would silently replace ours), and the resume
    # contract — a retry attempt shipping a verified checkpoint exposes
    # it to the electron via the COVALENT_TPU_RESUME_* env trio.
    checkpoint_stop, checkpoint_now = _start_checkpointer(spec)
    resume = spec.get("resume") or {}
    if resume.get("file"):
        # Absolute before the task chdirs into its workdir: the electron
        # reads this env var *after* the chdir.
        os.environ["COVALENT_TPU_RESUME_CHECKPOINT"] = os.path.abspath(
            str(resume["file"])
        )
        os.environ["COVALENT_TPU_RESUME_STEP"] = str(resume.get("step", ""))
        os.environ["COVALENT_TPU_RESUME_DIGEST"] = str(
            resume.get("digest", "")
        )
        _emit_worker_event(
            spec, "worker.resume_available", process_id=process_id,
            step=resume.get("step"), digest=resume.get("digest"),
        )
    else:
        for stale in (
            "COVALENT_TPU_RESUME_CHECKPOINT",
            "COVALENT_TPU_RESUME_STEP",
            "COVALENT_TPU_RESUME_DIGEST",
        ):
            os.environ.pop(stale, None)

    pip_deps = spec.get("pip_deps") or []
    if pip_deps:
        # Install BEFORE loading the function pickle — unpickling may import
        # the dependency (reference ct.DepsPip, svm_workflow.py:6,19).  A
        # non-zero process that fails here exits 1 *before* the distributed
        # barrier; the dispatcher's poller watches every worker's liveness
        # and fails the task fast instead of letting process 0 hang in
        # jax.distributed.initialize.
        try:
            install_pip_deps(pip_deps)
        except RuntimeError as pip_error:
            _emit_worker_event(
                spec, "worker.task_finished", process_id=process_id,
                ok=False, error=repr(pip_error),
            )
            if process_id == 0:
                _fallback_result(result_file, pip_error)
            return 1

    try:
        import cloudpickle as pickle
    except ImportError as import_error:
        if process_id == 0:
            _fallback_result(result_file, import_error)
        return 1

    t_load = time.monotonic()
    _emit_span(spans.append, "worker.boot", trace, _process_start(), t_load)
    expected_digest = spec.get("function_digest")
    if expected_digest:
        # The function file is a content-addressed (CAS) artifact: verify
        # its bytes against the digest the dispatcher staged before
        # unpickling, so a torn upload or stale cache entry fails loud
        # instead of executing the wrong payload.  Runs BEFORE the
        # distributed barrier so a bad artifact on any worker fails fast
        # with correct blame instead of hanging process 0 in initialize.
        import hashlib

        sha = hashlib.sha256()
        with open(spec["function_file"], "rb") as f:
            for chunk in iter(lambda: f.read(1 << 20), b""):
                sha.update(chunk)
        if sha.hexdigest() != expected_digest:
            digest_error = RuntimeError(
                f"staged function {spec['function_file']} does not match "
                f"its content digest (torn or stale CAS artifact)"
            )
            _emit_worker_event(
                spec, "worker.task_finished", process_id=process_id,
                ok=False, error=repr(digest_error),
            )
            if process_id == 0:
                _fallback_result(result_file, digest_error)
            return 1

    if distributed:
        # Data-plane bootstrap: after this, in-electron jax code sees every
        # chip in the slice and XLA collectives ride ICI/DCN (SURVEY §2.4).
        import jax

        jax.distributed.initialize(
            coordinator_address=distributed["coordinator_address"],
            num_processes=int(distributed["num_processes"]),
            process_id=process_id,
        )

    with _Annotated("worker.load"):
        with open(spec["function_file"], "rb") as f:
            fn, args, kwargs = pickle.load(f)
    _emit_span(spans.append, "worker.load", trace, t_load)

    # The SIGTERM preemption contract (notice event + final cooperative
    # snapshot + die with the signal) — installed after EVERY import that
    # can register its own signal handling (jax.distributed.initialize
    # above does), so the spot notice always reaches this handler.
    if spec.get("checkpoint"):
        _install_preempt_handler(spec, checkpoint_now)

    # Optional device-level tracing (SURVEY §5: the reference captures no
    # timings at all; this surfaces the XLA/TPU view of the electron).  The
    # trace lands in the task workdir/cache so the dispatcher can scp it.
    profile_dir = spec.get("profile_dir")
    profiling = False
    if profile_dir:
        try:
            import jax

            jax.profiler.start_trace(profile_dir)
            profiling = True
        except Exception as profile_error:  # pragma: no cover - best effort
            print(f"profiler unavailable: {profile_error}", file=sys.stderr)

    workdir = spec.get("workdir")
    current_dir = os.getcwd()
    result, exception = None, None
    try:
        if workdir:
            os.makedirs(workdir, exist_ok=True)
            os.chdir(workdir)
        with _WorkerSpan(spans.append, "worker.execute", trace):
            result = fn(*args, **kwargs)
        with _WorkerSpan(spans.append, "worker.to_host", trace):
            result = _to_host(result)
    except Exception as task_error:  # noqa: BLE001 - transported to dispatcher
        exception = task_error
    finally:
        os.chdir(current_dir)
        if profiling:
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception:  # pragma: no cover
                pass

    # Replicated outputs: one writer suffices (process 0); the others emit a
    # done-marker the control plane can watch for all-workers-finished.
    if process_id == 0:
        tmp = result_file + ".tmp"
        with _WorkerSpan(spans.append, "worker.store", trace):
            with open(tmp, "wb") as f:
                pickle.dump((result, exception), f)
        with open(tmp, "ab") as f:
            f.write(_trace_trailer(spans))
        os.replace(tmp, result_file)
    else:
        done = f"{result_file}.done.{process_id}"
        with open(done, "w") as f:
            f.write("done\n")

    if heartbeat_stop is not None:
        heartbeat_stop.set()
    if checkpoint_stop is not None:
        checkpoint_stop.set()
    _emit_worker_event(
        spec, "worker.task_finished", process_id=process_id,
        ok=exception is None,
        **({"error": repr(exception)} if exception is not None else {}),
    )
    return 0


# --------------------------------------------------------------------------
# Pool (forkserver) mode: `python harness.py --serve`
#
# One resident interpreter per worker, heavy imports preloaded ONCE, then a
# fork per task — the per-electron cost collapses from interpreter startup +
# imports (seconds) to a fork (milliseconds).  Speaks the same newline-JSON
# protocol as the native C++ agent (native/agent.cc) so the dispatcher
# drives both through one client, with `spec` instead of `argv`:
#
#   -> {"cmd":"run","id":"op","spec":"/path/spec.json","log":"/path/log"}
#   <- {"event":"started","id":"op","pid":123}
#   <- {"event":"exit","id":"op","code":0,"signal":0}
#
# Telemetry side-band: the dispatcher asks the server to tail a task's
# worker-local JSONL file (heartbeats + worker events) back over the same
# channel, turning post-mortem log files into a near-real-time stream:
#
#   -> {"cmd":"watch","id":"op","path":"/path/telemetry.jsonl"}
#   <- {"event":"watching","id":"op"}
#   <- {"event":"telemetry","id":"op","data":{...}}        (per line, pushed)
#   -> {"cmd":"unwatch","id":"op"}
#   <- {"event":"unwatched","id":"op"}
#
# A watch always starts from offset 0, so events buffered in the file while
# a channel was down are flushed on the reconnecting client's re-watch; the
# dispatcher dedups by each event's `seq`.
#
# Fork-safety: the server preloads modules (cloudpickle, jax, ...) and its own
# loop never initialises an XLA backend — for a forked task, backend init
# happens in the child (import before fork, use after).  RPC invocations and
# pool-mode serving sessions DO run inside this process, though, and the
# first one that touches jax makes the server the holder of its backend: on
# a TPU the chip belongs to that one process, and on any platform a fork of
# the now multi-threaded runtime deadlocks at its first computation.  From
# then on `run` is refused (`backend_held`, permanent) instead of forking a
# child that can only hang — see _spawn_task.  Children setsid into their own
# sessions, so they survive a pool/channel death exactly like the other
# launch paths, and the dispatcher can fall back to pid polling.
# --------------------------------------------------------------------------


#: Serializes protocol writes: the serve loop, RPC invocation threads, and
#: their heartbeat threads all share one stdout channel, and an interleaved
#: write would corrupt the line protocol.
_EMIT_LOCK = threading.Lock()


def _emit(obj: dict) -> None:
    with _EMIT_LOCK:
        try:
            sys.stdout.write(json.dumps(obj) + "\n")
            sys.stdout.flush()
        except OSError:
            # Dead channel (dispatcher gone, SIGPIPE ignored): swallowing
            # keeps session/RPC threads alive so the serve loop's orphan
            # path can hold their state for re-adoption instead of dying
            # on the first post-crash write.
            pass


# --------------------------------------------------------------------------
# Binary frame protocol (negotiated; JSONL stays the fallback).
#
# Hot-path payloads — RPC args/results, streamed serve tokens — used to pay
# pickle -> base64 -> JSON-line on every message (~33% inflation plus a
# JSON parse of the bulky string on both ends).  After negotiation the
# channel interleaves length-prefixed binary frames with JSON lines:
#
#   magic(2)=C5 F7  version(1)  verb(1)  flags(1)  hlen(4 BE)  blen(4 BE)
#   header: UTF-8 JSON object (the command/event, minus its bulky field)
#   body:   raw bytes, re-attached under the field named by header["_body"]
#
# Negotiation rides the ready banner (same one-round-trip shape as the
# COVALENT_TPU_CODECS= pre-flight probe): this server advertises
# `"frames": 1` in `ready`, the client answers `{"cmd":"frames",...}`, the
# ack flips both directions over.  No banner / no answer / the
# COVALENT_TPU_AGENT_FRAMES=0 kill switch all leave the channel on JSONL
# with byte-equal results.  This block mirrors transport/frames.py (and
# native/agent.cc) — it must stay stdlib-only because this file runs
# standalone on workers; the cross-implementation tests in
# tests/test_frames.py keep the three byte-compatible.
# --------------------------------------------------------------------------

_FRAME_MAGIC = b"\xc5\xf7"
_FRAME_VERSION = 1
_FRAME_HEADER = struct.Struct(">2sBBBII")
_FRAME_MAX_HEADER = 16 * 1024 * 1024
_FRAME_MAX_BODY = 512 * 1024 * 1024
_FRAME_MIN_COMPRESS = 512
_FRAME_FLAG_ZLIB = 0x01

_VERB_CMD = 0
_VERB_INVOKE = 1
_VERB_RESULT = 2
_VERB_TELEMETRY = 3
_VERB_MULTI_INVOKE = 4
_VERB_SERVE = 5

#: Outbound frame state, flipped by the negotiated `frames` command.
_FRAMES = {"out": False, "codec": ""}


def _frames_enabled() -> bool:
    """Kill switch: COVALENT_TPU_AGENT_FRAMES=0/off forces JSONL-only."""
    return os.environ.get(
        "COVALENT_TPU_AGENT_FRAMES", ""
    ).strip().lower() not in ("0", "off", "false", "no")


def _emit_frame(verb: int, header: dict, body: bytes = b"") -> None:
    """One binary frame on stdout (atomic under the emit lock).

    The body is zlib-compressed when the negotiated codec allows and the
    payload is big enough to win — same skip-if-incompressible heuristic
    the file-staging codec applies.
    """
    flags = 0
    if (
        body
        and _FRAMES["codec"] == "zlib"
        and len(body) >= _FRAME_MIN_COMPRESS
    ):
        packed = zlib.compress(body, 6)
        if len(packed) < len(body) * 0.9:
            body, flags = packed, _FRAME_FLAG_ZLIB
    head = json.dumps(header, separators=(",", ":")).encode()
    with _EMIT_LOCK:
        try:
            sys.stdout.flush()  # any pending text shares the one byte stream
            out = sys.stdout.buffer
            out.write(_FRAME_HEADER.pack(
                _FRAME_MAGIC, _FRAME_VERSION, verb, flags, len(head),
                len(body)
            ))
            out.write(head)
            if body:
                out.write(body)
            out.flush()
        except OSError:
            # Dead channel: same contract as _emit — stay alive for the
            # orphan/re-adoption path (a torn frame on a dead pipe is
            # unobservable; the adopted channel restarts on JSONL).
            pass


def _handle_frames_cmd(command: dict) -> None:
    """Negotiation verb: ack and flip the outbound side to frames.

    A disabled runtime (kill switch) answers ``version: 0`` so a capable
    client settles immediately on the JSONL fallback instead of waiting
    out a timeout.
    """
    if not _frames_enabled():
        _emit({"event": "frames", "version": 0})
        return
    codec = "zlib" if str(command.get("codec") or "") == "zlib" else ""
    _emit({"event": "frames", "version": _FRAME_VERSION, "codec": codec})
    _FRAMES["out"] = True
    _FRAMES["codec"] = codec


def _frame_resync(buffer: bytearray) -> None:
    """Drop garbage through the next newline (or all of it).

    After a bad magic/version/length the stream position is untrusted;
    valid traffic is self-delimiting frames or newline-terminated JSON,
    so the next newline is the only honest resync point.
    """
    nl = buffer.find(b"\n", 1)
    if nl < 0:
        buffer.clear()
    else:
        del buffer[:nl + 1]


def _extract_commands(buffer: bytearray) -> list:
    """Every complete inbound message in ``buffer`` (frames + JSON lines).

    Mutates the buffer in place; incomplete trailing frames/lines stay
    buffered for the next read.  Malformed input — bad magic or version,
    oversized lengths, non-JSON frame headers, torn compressed bodies —
    is answered with a clean ``error`` event and resynced past, NEVER
    allowed to hang the loop or kill the resident runtime (a channel
    death mid-frame simply leaves the partial frame buffered until the
    reader sees EOF).  Torn bodies carry ``permanent: true``: re-sending
    identical corrupt bytes can never succeed.
    """
    commands: list = []
    while buffer:
        if buffer[0] == _FRAME_MAGIC[0]:
            if len(buffer) < _FRAME_HEADER.size:
                break  # header still in flight
            magic, version, _verb, flags, hlen, blen = _FRAME_HEADER.unpack(
                bytes(buffer[:_FRAME_HEADER.size])
            )
            if magic != _FRAME_MAGIC or version != _FRAME_VERSION:
                _emit({
                    "event": "error", "code": "bad_frame",
                    "message": (
                        f"bad frame magic/version ({magic!r} v{version})"
                    ),
                })
                _frame_resync(buffer)
                continue
            if hlen > _FRAME_MAX_HEADER or blen > _FRAME_MAX_BODY:
                _emit({
                    "event": "error", "code": "bad_frame",
                    "message": (
                        f"oversized frame (header {hlen}B, body {blen}B)"
                    ),
                })
                _frame_resync(buffer)
                continue
            total = _FRAME_HEADER.size + hlen + blen
            if len(buffer) < total:
                break  # body still in flight
            header = bytes(buffer[_FRAME_HEADER.size:_FRAME_HEADER.size + hlen])
            body = bytes(buffer[_FRAME_HEADER.size + hlen:total])
            del buffer[:total]
            try:
                command = json.loads(header.decode("utf-8"))
                if not isinstance(command, dict):
                    raise ValueError("frame header is not an object")
            except (ValueError, UnicodeDecodeError) as err:
                # Frame consumed whole (lengths were valid): the stream
                # stays in sync, only this message is refused.
                _emit({"event": "error", "code": "bad_frame",
                       "message": f"frame header is not JSON: {err}"})
                continue
            if flags & _FRAME_FLAG_ZLIB:
                try:
                    body = zlib.decompress(body)
                except zlib.error as err:
                    ids = [str(command.get("id") or "")]
                    if command.get("cmd") == "multi_invoke":
                        # A batched frame's op ids live in ops: the
                        # permanent refusal must reach EVERY waiting op,
                        # not evaporate as one id-less log line.
                        ids = [
                            str(op.get("id") or "")
                            for op in (command.get("ops") or [])
                            if isinstance(op, dict)
                        ] or ids
                    for tid in ids:
                        _emit({
                            "event": "error", "id": tid,
                            "code": "bad_frame", "permanent": True,
                            "message": (
                                "frame body failed decompression "
                                f"(torn payload): {err}"
                            ),
                        })
                    continue
            key = command.pop("_body", None)
            if key:
                command[str(key)] = body
            commands.append(command)
        else:
            nl = buffer.find(b"\n")
            if nl < 0:
                break  # line still in flight
            raw = bytes(buffer[:nl])
            del buffer[:nl + 1]
            line = raw.decode(errors="replace").strip()
            if not line:
                continue
            try:
                command = json.loads(line)
            except ValueError:
                _emit({"event": "error", "message": "malformed command"})
                continue
            if isinstance(command, dict):
                commands.append(command)
            else:
                _emit({"event": "error", "message": "malformed command"})
    return commands


class _TelemetryBatcher:
    """Micro-batch coalescing for side-band telemetry frames.

    At 1000+ tokens/s per session the per-record line write + flush + JSON
    parse became its own hot path.  Intermediate ``serve.token`` chunks
    buffer up to a few ms (COVALENT_TPU_SERVE_COALESCE_MS, default 2) or N
    records (COVALENT_TPU_SERVE_COALESCE_MAX, default 32) and ship as ONE
    ``telemetry_batch`` frame whose body is the JSON array of records.
    Everything latency-sensitive — done markers, rejects, stats,
    heartbeats, lifecycle events — flushes the pending buffer and itself
    immediately, so per-id ordering is preserved and stream-final latency
    is untouched.  Each record keeps its own envelope (seq, cumulative
    ``idx``), so the dispatcher's dedup and the serving tier's
    exactly-once replay splice see exactly the records they always did.
    With frames off every record ships as its own JSON line — the
    pre-frame protocol, byte for byte.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._pending: dict = {}  # id -> [records]
        self._oldest: dict = {}   # id -> monotonic stamp of first record
        try:
            self.window_s = max(0.0, float(os.environ.get(
                "COVALENT_TPU_SERVE_COALESCE_MS", "2"
            )) / 1000.0)
        except ValueError:
            self.window_s = 0.002
        try:
            self.max_records = max(1, int(os.environ.get(
                "COVALENT_TPU_SERVE_COALESCE_MAX", "32"
            )))
        except ValueError:
            self.max_records = 32

    def reset(self) -> None:
        """Forked children must not inherit buffers or a held lock."""
        self._lock = threading.Lock()
        self._pending = {}
        self._oldest = {}

    def emit(self, task_id: str, data: dict) -> None:
        if not _FRAMES["out"] or self.window_s <= 0:
            _emit({"event": "telemetry", "id": task_id, "data": data})
            return
        urgent = data.get("type") != "serve.token" or data.get("done")
        with self._lock:
            self._pending.setdefault(task_id, []).append(data)
            self._oldest.setdefault(task_id, time.monotonic())
            full = len(self._pending[task_id]) >= self.max_records
        if urgent or full:
            self.flush()

    def flush(self) -> None:
        with self._lock:
            pending, self._pending = self._pending, {}
            self._oldest = {}
        for task_id, records in pending.items():
            emit_telemetry_batch(task_id, records)

    def flush_aged(self) -> None:
        """Ship buffers older than the window (called by the owning loops)."""
        if not self._pending:
            return
        now = time.monotonic()
        groups = []
        with self._lock:
            for task_id, t0 in list(self._oldest.items()):
                if now - t0 >= self.window_s:
                    records = self._pending.pop(task_id, None)
                    self._oldest.pop(task_id, None)
                    if records:
                        groups.append((task_id, records))
        for task_id, records in groups:
            emit_telemetry_batch(task_id, records)


def emit_telemetry_batch(task_id: str, records: list) -> None:
    """One coalesced telemetry frame (or per-record lines when frames off)."""
    if not _FRAMES["out"]:
        for data in records:
            _emit({"event": "telemetry", "id": task_id, "data": data})
        return
    try:
        body = json.dumps(records, default=repr).encode()
    except (TypeError, ValueError):
        return
    _emit_frame(
        _VERB_TELEMETRY,
        {"event": "telemetry_batch", "id": task_id,
         "count": len(records), "_body": "records"},
        body,
    )


_BATCHER = _TelemetryBatcher()


def _spawn_task(command: dict, children: dict) -> None:
    task_id = command.get("id")
    spec_path = command.get("spec")
    if not task_id or not spec_path:
        _emit({"event": "error", "id": task_id or "",
               "message": "run requires id and spec"})
        return
    held = live_backend()
    if held:
        _emit({"event": "error", "id": task_id, "code": "backend_held",
               "permanent": True,
               "message": (
                   f"resident runtime pid {os.getpid()} holds an "
                   f"initialised {held} backend (an RPC invocation or a "
                   "serving session ran in it): a forked task would "
                   "deadlock on the inherited runtime, and an accelerator "
                   "belongs to one process — launch this task from an "
                   "executor with its own runtime"
               )})
        return
    sys.stdout.flush()
    import signal as _signal

    # A kill can follow the launch before the child has run at all.  A
    # TERM delivered then finds the server's handler still in place, and
    # CPython forgets every signal tripped before its after-fork hook has
    # run: the task would live on.  Blocked across the fork, the signal
    # waits for the child to take the default disposition below.
    _signal.pthread_sigmask(_signal.SIG_BLOCK, {_signal.SIGTERM})
    try:
        pid = os.fork()
    except OSError:
        _signal.pthread_sigmask(_signal.SIG_UNBLOCK, {_signal.SIGTERM})
        raise
    if pid == 0:
        rc = 1
        try:
            # Fork-safety: an RPC invocation/heartbeat thread may hold the
            # event or emit lock at fork time, and the child inherits the
            # locked state with no thread to ever release it — fresh locks
            # make the child's own event writes deadlock-free.
            global _worker_event_lock, _EMIT_LOCK
            _worker_event_lock = threading.Lock()
            _EMIT_LOCK = threading.Lock()
            # The child's stdout is about to become the task log, not the
            # protocol channel: frame mode and any half-filled telemetry
            # batch belong to the server process alone.
            _FRAMES["out"] = False
            _FRAMES["codec"] = ""
            _BATCHER.reset()
            # The child is a task runner, not a session host: an inherited
            # copy of the server's live sessions would make its heartbeats
            # report a frozen fork-time serve occupancy forever.
            _SERVE_SESSIONS.clear()
            # Same for an in-flight profiler capture: the trace belongs to
            # the server process; a child must neither think one is active
            # nor inherit a lock held at fork time.
            global _PROFILE_LOCK
            _PROFILE_LOCK = threading.Lock()
            _PROFILE_ACTIVE.clear()
            _signal.set_wakeup_fd(-1)
            _signal.signal(_signal.SIGCHLD, _signal.SIG_DFL)
            # The serve-preempt notice handler belongs to the server; a
            # task child's own preemption contract (checkpoint + die) is
            # installed by run_task when the spec configures it.
            _signal.signal(_signal.SIGTERM, _signal.SIG_DFL)
            _signal.pthread_sigmask(_signal.SIG_UNBLOCK, {_signal.SIGTERM})
            os.setsid()
            log_fd = os.open(
                command.get("log") or os.devnull,
                os.O_WRONLY | os.O_CREAT | os.O_APPEND,
                0o644,
            )
            devnull = os.open(os.devnull, os.O_RDONLY)
            os.dup2(devnull, 0)
            os.dup2(log_fd, 1)
            os.dup2(log_fd, 2)
            with open(spec_path) as f:
                spec = json.load(f)
            rc = run_task(spec)
        except BaseException:  # noqa: BLE001 - child must never return
            import traceback

            traceback.print_exc()
        finally:
            os._exit(rc)
    _signal.pthread_sigmask(_signal.SIG_UNBLOCK, {_signal.SIGTERM})
    children[pid] = task_id
    _emit({"event": "started", "id": task_id, "pid": pid})


# --------------------------------------------------------------------------
# RPC execute-by-digest: the resident executor loop.
#
# Launch mode (above) pays a fork + interpreter state per electron and
# stages args/results through remote disk.  RPC mode keeps the *work* in
# the resident interpreter too: the dispatcher ships the cloudpickled
# function ONCE per connection into the CAS, registers it by digest, and
# thereafter invokes by digest with args inline on the channel — results
# stream back base64-pickled over the same channel.  No per-electron
# process, no pid file, no poll loop, no result file:
#
#   -> {"cmd":"register_fn","digest":"<sha256>","path":"/cas/<sha256>.pkl"}
#   <- {"event":"registered","digest":"<sha256>"}
#   <- {"event":"register_error","digest":"...","code":"digest_mismatch"|
#       "missing"|"load_failed","message":"..."}           (on failure)
#   -> {"cmd":"invoke","id":"<op>","digest":"<sha256>","spec":{...},
#       "args":"<b64 cloudpickle (args, kwargs)>"}            (inline)
#       ... or "args_path"/"args_digest" for oversized args staged in the
#       CAS (digest verified before unpickling, like the function itself)
#   <- {"event":"started","id":"<op>","pid":<server pid>,"rpc":true}
#   <- {"event":"telemetry","id":"<op>","data":{...}}   (task events +
#       heartbeats, same schema/trace contract as launch-mode workers)
#   <- {"event":"result","id":"<op>","ok":true,"data":"<b64 pickle of
#       (result, exception)>"}
#
# Registration digest-verifies the CAS artifact BEFORE unpickling (the
# same torn-payload guard run_task applies) and unpickles once; each
# invocation runs on a daemon thread so the command loop stays live and
# concurrent invocations share the warm imports.  A crash that takes the
# resident process down surfaces to the dispatcher as a channel death —
# classified transient, gang retried, function re-registered.
# --------------------------------------------------------------------------


def _load_fn_payload(path: str, digest: str):
    """``(code, fn_or_error)``: digest-verified CAS bytes -> callable."""
    try:
        with open(path, "rb") as f:
            data = f.read()
    except OSError as err:
        return "missing", err
    import hashlib

    if hashlib.sha256(data).hexdigest() != digest:
        return "digest_mismatch", RuntimeError(
            f"registered function {path} does not match its content digest "
            "(torn or stale CAS artifact)"
        )
    try:
        import cloudpickle

        return "", cloudpickle.loads(data)
    except BaseException as err:  # noqa: BLE001 - arbitrary user payloads
        return "load_failed", err


def _rpc_register(command: dict, registry: dict) -> None:
    digest = command.get("digest")
    path = command.get("path")
    if not digest or not path:
        _emit({"event": "error", "message": "register_fn requires digest and path"})
        return
    if digest in registry:  # idempotent: re-register is a no-op ack
        _emit({"event": "registered", "digest": digest})
        return
    code, loaded = _load_fn_payload(path, digest)
    if code:
        _emit({
            "event": "register_error", "digest": digest,
            "code": code, "message": repr(loaded),
        })
        return
    registry[digest] = loaded
    _emit({"event": "registered", "digest": digest})


def _decode_rpc_args(command: dict) -> tuple:
    """``(args, kwargs)`` from the invoke command (inline b64 or CAS path).

    CAS-staged args are digest-verified before unpickling — oversized
    payloads keep the same torn-artifact guard inline ones get for free
    (the channel delivered the exact bytes the dispatcher encoded).
    """
    import base64

    import cloudpickle

    raw = command.get("args_bytes")
    b64 = command.get("args")
    if raw is not None:
        # Binary-frame road: the channel delivered the exact pickle bytes,
        # no base64 leg to pay or verify.
        data = raw
    elif b64 is not None:
        data = base64.b64decode(b64)
    else:
        path = command.get("args_path")
        if not path:
            return (), {}
        with open(path, "rb") as f:
            data = f.read()
        expected = command.get("args_digest")
        if expected:
            import hashlib

            if hashlib.sha256(data).hexdigest() != expected:
                raise RuntimeError(
                    f"staged RPC args {path} do not match their content "
                    "digest (torn or stale CAS artifact)"
                )
    args, kwargs = cloudpickle.loads(data)
    return tuple(args), dict(kwargs)


def _pickle_rpc_result(result, exception) -> bytes:
    """The ``(result, exception)`` pickle — byte-identical layout to the
    result file launch mode writes."""
    try:
        import cloudpickle as pick
    except ImportError:
        import pickle as pick
    try:
        return pick.dumps((result, exception))
    except BaseException as err:  # noqa: BLE001 - unpicklable user results
        import pickle

        return pickle.dumps(
            (None, RuntimeError(f"RPC result not picklable: {err!r}"))
        )


def _emit_rpc_result(task_id: str, data: bytes, ok: bool, command: dict) -> None:
    """Stream one invocation's result pickle ``data``, inline or staged by
    size.

    The dispatcher's ``rpc_inline_args_max`` policy applies symmetrically:
    a result pickle at or below ``result_max_inline`` rides the channel
    base64-inline; a larger one is written (atomically) to the
    command-provided ``result_path`` and announced by path + sha256 digest
    — a multi-MB pickle must not be base64-inlined onto the channel in
    one write, for the same reason oversized args take the CAS road in.
    No ``result_path`` (or no threshold) preserves the inline-always
    contract.  A staging failure degrades to inline rather than losing
    the result.
    """
    import base64

    result_path = command.get("result_path")
    try:
        max_inline = int(command.get("result_max_inline"))
    except (TypeError, ValueError):
        max_inline = -1
    if result_path and 0 <= max_inline < len(data):
        import hashlib

        try:
            tmp = f"{result_path}.tmp.{os.getpid()}"
            with open(tmp, "wb") as f:
                f.write(data)
            os.replace(tmp, result_path)
        except OSError:
            pass  # fall through to the inline road below
        else:
            _emit({
                "event": "result", "id": task_id,
                "ok": ok,
                "data_path": result_path,
                "data_digest": hashlib.sha256(data).hexdigest(),
                "bytes": len(data),
            })
            return
    if _FRAMES["out"]:
        # Negotiated binary road: the raw pickle rides the frame body —
        # no base64 inflation, no giant JSON string to escape and parse.
        _emit_frame(
            _VERB_RESULT,
            {"event": "result", "id": task_id,
             "ok": ok, "_body": "data_bytes"},
            data,
        )
        return
    _emit({
        "event": "result", "id": task_id,
        "ok": ok,
        "data": base64.b64encode(data).decode("ascii"),
    })


def _emit_rpc_event(spec: dict, task_id: str, type: str, **fields) -> None:
    """One worker-side record pushed straight over the channel.

    Same envelope (`_build_worker_event`: ts/pid/seq/trace) as launch-mode
    workers write to their telemetry files — the dispatcher's backhaul
    handler can't tell the transports apart, which is the point.  The
    ``rpc`` marker tells the dispatcher these events did NOT also land in
    a shared-filesystem sink, so they re-emit even on the local transport.
    """
    _BATCHER.emit(task_id, _build_worker_event(spec, type, rpc=True, **fields))


def _start_rpc_heartbeat(spec: dict, task_id: str):
    """Channel-streamed heartbeats for one invocation (no snapshot files)."""
    try:
        interval = float(spec.get("heartbeat_s") or 0)
    except (TypeError, ValueError):
        interval = 0.0
    if interval <= 0:
        return None
    stop = threading.Event()

    def beat_loop() -> None:
        hb_seq = 0
        while True:
            hb_seq += 1
            _emit_rpc_event(
                spec, task_id, "worker.heartbeat",
                hb_seq=hb_seq, interval_s=interval,
                **_heartbeat_payload(""),
            )
            if stop.wait(interval):
                return

    threading.Thread(
        target=beat_loop, name="covalent-tpu-rpc-heartbeat", daemon=True
    ).start()
    return stop


def _run_rpc_task(command: dict, fn) -> None:
    """Execute one registered function in-process and stream the result.

    The launch-mode contract, minus the process: task_started /
    heartbeats / task_finished events (trace-stamped from the spec), user
    exceptions transported — never raised — and device arrays materialised
    to host before pickling.  The same five ``worker.*`` spans as
    ``run_task`` go home in one ``worker.trace`` record over the
    side-band, ahead of the result (the dispatcher closes the electron's
    trace on it): ``worker.boot`` is the env contract, ``worker.load`` the
    arguments' decode (the function was loaded at registration),
    ``worker.store`` the result's pickling; with them, this runtime's
    running compile totals.
    """
    t_boot = time.monotonic()
    task_id = command.get("id") or ""
    spec = dict(command.get("spec") or {})
    spec.setdefault("operation_id", task_id)
    trace = spec.get("trace")
    spans: list = []
    result, exception = None, None
    try:
        # Same env contract as a launch-mode harness child (os.environ +
        # PYTHONPATH sys.path mirror + jax platform pin): task_env must
        # mean the same thing whichever runtime executes the function.
        _apply_spec_env(spec)
        t_load = time.monotonic()
        _emit_span(spans.append, "worker.boot", trace, t_boot, t_load)
        args, kwargs = _decode_rpc_args(command)
        _emit_span(spans.append, "worker.load", trace, t_load)
    except BaseException as err:  # noqa: BLE001 - bad env/torn args fail the task
        args, kwargs, exception = (), {}, err
    _emit_rpc_event(spec, task_id, "worker.task_started", process_id=0)
    heartbeat_stop = _start_rpc_heartbeat(spec, task_id)
    try:
        if exception is None:
            try:
                with _WorkerSpan(spans.append, "worker.execute", trace):
                    result = fn(*args, **kwargs)
                with _WorkerSpan(spans.append, "worker.to_host", trace):
                    result = _to_host(result)
            except Exception as task_error:  # noqa: BLE001 - transported
                exception = task_error
    finally:
        if heartbeat_stop is not None:
            heartbeat_stop.set()
    with _WorkerSpan(spans.append, "worker.store", trace):
        data = _pickle_rpc_result(result, exception)
    jit = _obs_totals("jitstats")
    _emit_rpc_event(
        spec, task_id, "worker.trace", spans=spans,
        **({"jit": jit} if jit is not None else {}),
    )
    _emit_rpc_result(task_id, data, exception is None, command)
    _emit_rpc_event(
        spec, task_id, "worker.task_finished", process_id=0,
        ok=exception is None,
        **({"error": repr(exception)} if exception is not None else {}),
    )


def _rpc_invoke(command: dict, registry: dict, sync: bool = False) -> None:
    task_id = command.get("id")
    digest = command.get("digest")
    if not task_id or not digest:
        _emit({"event": "error", "id": task_id or "",
               "message": "invoke requires id and digest"})
        return
    fn = registry.get(digest)
    if fn is None and command.get("path"):
        # Self-heal a lost registration (agent restarted between the
        # dispatcher's register and invoke) and serve the --rpc-child
        # one-shot mode: load from the CAS path, digest verified.
        code, loaded = _load_fn_payload(command["path"], digest)
        if not code:
            registry[digest] = fn = loaded
    if fn is None:
        _emit({"event": "error", "id": task_id, "code": "unregistered",
               "message": f"no registered function for digest {digest[:12]}"})
        return
    _emit({"event": "started", "id": task_id, "pid": os.getpid(),
           "rpc": True})
    if sync:
        _run_rpc_task(command, fn)
        return
    threading.Thread(
        target=_run_rpc_task, args=(command, fn),
        name=f"covalent-tpu-rpc-{task_id}", daemon=True,
    ).start()


def _rpc_multi_invoke(command: dict, registry: dict) -> None:
    """Batched invoke: N queued electrons for one digest in ONE frame.

    The frame header carries the per-op command dicts (id, spec,
    result_path, ...) plus ``args_lens``; the body is the concatenation of
    each op's args pickle, split back out here by length.  One
    ``multi_started`` acks every op at once; results fan back out by op id
    through the exact same per-invocation path a lone ``invoke`` takes —
    each op gets its own thread, heartbeats, and result event.  A body
    whose lengths don't reconcile is torn content (``permanent``): the
    dispatcher must not burn retries re-sending identical corrupt bytes.
    """
    digest = command.get("digest")
    ops = [op for op in (command.get("ops") or []) if isinstance(op, dict)]
    lens = command.get("args_lens") or []
    body = command.get("args_bytes") or b""
    ids = [str(op.get("id") or "") for op in ops]
    if not digest or not ops or len(lens) != len(ops):
        for tid in ids or [""]:
            _emit({"event": "error", "id": tid, "code": "bad_request",
                   "message": "multi_invoke requires digest, ops and "
                              "args_lens"})
        return
    try:
        lens = [int(n) for n in lens]
        lens_ok = all(n >= 0 for n in lens) and sum(lens) == len(body)
    except (TypeError, ValueError):
        lens_ok = False
    if not lens_ok:
        for tid in ids:
            _emit({"event": "error", "id": tid, "code": "bad_frame",
                   "permanent": True,
                   "message": "multi_invoke args_lens do not match the "
                              "frame body (torn payload)"})
        return
    fn = registry.get(digest)
    if fn is None and command.get("path"):
        code, loaded = _load_fn_payload(command["path"], digest)
        if not code:
            registry[digest] = fn = loaded
    if fn is None:
        for tid in ids:
            _emit({"event": "error", "id": tid, "code": "unregistered",
                   "message": f"no registered function for digest "
                              f"{str(digest)[:12]}"})
        return
    _emit({"event": "multi_started", "ids": ids, "pid": os.getpid(),
           "rpc": True})
    offset = 0
    for op, n in zip(ops, lens):
        op = dict(op)
        op["args_bytes"] = body[offset:offset + n]
        offset += n
        threading.Thread(
            target=_run_rpc_task, args=(op, fn),
            name=f"covalent-tpu-rpc-{op.get('id')}", daemon=True,
        ).start()


def rpc_child() -> int:
    """``harness.py --rpc-child``: one invocation, command on stdin.

    The native C++ agent's invoke support: it forks this runner per
    invocation, pipes the invoke command (which carries the CAS ``path``)
    to stdin, and streams the started/telemetry/result events from stdout
    back over its channel.  Slower than the resident pool loop (one
    interpreter start per call) but keeps the protocol — and the
    no-disk-for-args/results property — uniform across both runtimes.
    """
    buffer = bytearray()
    saw_bytes = False
    while True:
        for command in _extract_commands(buffer):
            if command.get("cmd") == "frames":
                # The native agent pre-announces the client's negotiated
                # frame mode so this runner's result events ride frames.
                _handle_frames_cmd(command)
                continue
            _rpc_invoke(command, {}, sync=True)
            return 0
        data = sys.stdin.buffer.read1(65536)
        if not data:
            break
        saw_bytes = True
        buffer.extend(data)
    if saw_bytes:
        _emit({"event": "error", "message": "malformed invoke command"})
        return 1
    print("usage: harness.py --rpc-child  (invoke command on stdin)",
          file=sys.stderr)
    return 2


# --------------------------------------------------------------------------
# Resident-mode profiling: drive jax.profiler inside the resident runtime.
#
# Launch-mode profiling wraps one harness process per task; the warm
# resident runtimes (RPC invocations, serving sessions) used to be
# unprofilable — setting profile_dir forced launch mode.  These verbs
# capture the resident process itself:
#
#   -> {"cmd":"profile_start","id":"<pid>","dir":"/path/trace_dir"}
#   <- {"event":"profile_started","id":"<pid>","pid":123}
#   <- {"event":"profile_error","id":"<pid>","code":"busy"|"unavailable"|
#       "bad_request"|"not_running"|"stop_failed"|"package_failed",
#       "message":"..."}                                     (on failure)
#   -> {"cmd":"profile_stop","id":"<pid>","artifact_dir":"/cache/cas"}
#   <- {"event":"profile_stopped","id":"<pid>",
#       "path":"/cache/cas/<sha256>.profile.tgz",
#       "digest":"<sha256>","bytes":N}
#
# `profile_stop` packages the trace directory into ONE tar.gz artifact
# named by its own sha256 under `artifact_dir` (the dispatcher points this
# at the CAS dir, so the artifact is content-addressed like every other
# staged payload) and announces path + digest; the dispatcher fetches and
# digest-verifies before trusting the bytes.  jax.profiler is
# process-wide, so exactly one trace runs at a time — a second start is
# refused `busy` rather than corrupting the active capture.  The pool
# server handles the verbs directly (RPC invocations and pool-mode
# serving sessions execute in its process); `--serve-child` handles them
# too so the native agent can forward a capture into the session child
# that actually holds the model.
# --------------------------------------------------------------------------


_PROFILE_LOCK = threading.Lock()
#: {"id", "dir"} while a trace is active (jax.profiler is process-wide).
_PROFILE_ACTIVE: dict = {}


def _profile_start(command: dict) -> None:
    profile_id = str(command.get("id") or "")
    trace_dir = command.get("dir")
    if not profile_id or not trace_dir:
        _emit({"event": "profile_error", "id": profile_id,
               "code": "bad_request",
               "message": "profile_start requires id and dir"})
        return
    sid = str(command.get("sid") or "")
    if sid and sid not in _SERVE_SESSIONS:
        # A sid-pinned capture must land on the runtime hosting that
        # session; tracing whichever process got the command first would
        # return a digest-valid artifact of the WRONG runtime.  Refuse so
        # the dispatcher's target loop moves on to the right worker.
        _emit({"event": "profile_error", "id": profile_id,
               "code": "unknown_session",
               "message": f"no live serving session {sid!r} here"})
        return
    with _PROFILE_LOCK:
        if _PROFILE_ACTIVE:
            _emit({"event": "profile_error", "id": profile_id,
                   "code": "busy",
                   "message": (
                       f"trace {_PROFILE_ACTIVE.get('id')!r} already "
                       "active (the profiler is process-wide)"
                   )})
            return
        try:
            import jax

            os.makedirs(trace_dir, exist_ok=True)
            # Python's call tracer off: on a busy worker it is most of the
            # trace and made ``stop_trace`` outlast the dispatcher's wait.
            # The host's account is the program's own annotations
            # (``serve.loop.*``, ``serve.engine.*``, ``worker.*``); the
            # device planes and TraceMe events stay.
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        except Exception as err:  # noqa: BLE001 - any profiler failure
            _emit({"event": "profile_error", "id": profile_id,
                   "code": "unavailable", "message": repr(err)})
            return
        _PROFILE_ACTIVE.update({"id": profile_id, "dir": trace_dir})
    _emit({"event": "profile_started", "id": profile_id, "pid": os.getpid()})


def _package_trace(trace_dir: str, artifact_dir: str) -> tuple:
    """``(path, digest, bytes)``: one content-addressed trace artifact.

    Digest is computed over the exact tar bytes shipped, then the file is
    renamed to ``<digest>.profile.tgz`` — the same publish-by-content
    contract as every CAS artifact, so the dispatcher's fetch can verify
    end to end.  The raw trace directory is consumed (removed) so repeat
    captures never accrete worker disk.
    """
    import hashlib
    import shutil
    import tarfile

    os.makedirs(artifact_dir, exist_ok=True)
    tmp = os.path.join(
        artifact_dir, f".profile.tmp.{os.getpid()}.{time.time_ns()}.tgz"
    )
    with tarfile.open(tmp, "w:gz") as tar:
        tar.add(trace_dir, arcname=".")
    sha = hashlib.sha256()
    with open(tmp, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            sha.update(chunk)
    digest = sha.hexdigest()
    final = os.path.join(artifact_dir, f"{digest}.profile.tgz")
    os.replace(tmp, final)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return final, digest, os.path.getsize(final)


def _profile_stop(command: dict) -> None:
    """Validate + hand off; the heavy work runs on a daemon thread.

    Stopping the profiler and tarring/hashing a trace (routinely tens to
    hundreds of MB) must not run inline in the pool server's command
    loop: a capture on a busy server would otherwise freeze ping /
    serve_request / invoke admission for the whole packaging time — long
    enough for the dispatcher's stall detector to tear down the very
    runtime being profiled.
    """
    profile_id = str(command.get("id") or "")
    with _PROFILE_LOCK:
        active = dict(_PROFILE_ACTIVE)
        if not active or (profile_id and active.get("id") != profile_id):
            _emit({"event": "profile_error", "id": profile_id,
                   "code": "not_running",
                   "message": f"no active trace for {profile_id!r}"})
            return
        if _PROFILE_ACTIVE.get("stopping"):
            _emit({"event": "profile_error", "id": profile_id,
                   "code": "not_running",
                   "message": f"trace {profile_id!r} is already stopping"})
            return
        _PROFILE_ACTIVE["stopping"] = True
    artifact_dir = command.get("artifact_dir") or os.path.dirname(
        str(active["dir"]).rstrip("/")
    )
    threading.Thread(
        target=_profile_finish,
        args=(profile_id, str(active["dir"]), artifact_dir,
              bool(command.get("discard"))),
        daemon=True,
        name="profile-stop",
    ).start()


def _profile_finish(
    profile_id: str, trace_dir: str, artifact_dir: str, discard: bool = False
) -> None:
    try:
        import jax

        jax.profiler.stop_trace()
    except Exception as err:  # noqa: BLE001
        # The trace may still be running: KEEP the active record (minus
        # the stopping mark) so the caller can retry the stop — clearing
        # it here would wedge profiling on this runtime forever (every
        # later start would hit jax's own trace-in-progress error).
        with _PROFILE_LOCK:
            _PROFILE_ACTIVE.pop("stopping", None)
        _emit({"event": "profile_error", "id": profile_id,
               "code": "stop_failed", "message": repr(err)})
        return
    with _PROFILE_LOCK:
        _PROFILE_ACTIVE.clear()
    if discard:
        # A compensating stop for an abandoned capture (cancelled
        # mid-sleep, lost start ack): no caller will ever fetch the
        # artifact, so skip the tar+hash entirely and reclaim the disk.
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)
        _emit({"event": "profile_stopped", "id": profile_id,
               "discarded": True})
        return
    try:
        path, digest, size = _package_trace(trace_dir, artifact_dir)
    except Exception as err:  # noqa: BLE001 - tar/disk failures
        _emit({"event": "profile_error", "id": profile_id,
               "code": "package_failed", "message": repr(err)})
        return
    _emit({"event": "profile_stopped", "id": profile_id,
           "path": path, "digest": digest, "bytes": size})


# --------------------------------------------------------------------------
# Serving sessions: resident model server with in-worker continuous batching.
#
# RPC mode (above) made single *calls* cheap; a serving session makes whole
# REQUEST STREAMS cheap: `serve_open` loads a cloudpickled model-factory
# from the CAS ONCE (digest verified, like register_fn), builds its engine
# — params loaded, decode/prefill programs compiled — and then serves
# request-level commands for the session's whole lifetime.  Tokens stream
# back incrementally over the EXISTING telemetry side-band (same envelope,
# seq counter, and dedup contract as heartbeats), so time-to-first-token is
# real, not end-of-batch:
#
#   -> {"cmd":"serve_open","id":"<sid>","digest":"<sha256>",
#       "path":"/cas/<sha256>.pkl","options":{"queue_max":64,
#       "default_deadline_s":0,"stats_interval_s":1.0},"spec":{...}}
#   <- {"event":"serve_opened","id":"<sid>","slots":4,"pid":123}
#   <- {"event":"serve_error","id":"<sid>","code":"digest_mismatch"|
#       "missing"|"load_failed"|"factory_failed","message":"...",
#       "permanent":true|false,"label":"..."}              (on failure)
#   -> {"cmd":"serve_request","id":"<sid>","rid":"<rid>","prompt":[...],
#       "params":{...},"deadline_s":5.0,"tenant":"a"}
#   <- {"event":"telemetry","id":"<sid>","data":{"type":"serve.token",
#       "rid":"<rid>","idx":N,"tokens":[...],"done":false,...}}  (pushed)
#   <- {"event":"telemetry","id":"<sid>","data":{"type":"serve.reject",
#       "rid":"<rid>","code":"serve_admission_shed"|"unknown_session"|
#       "deadline"|"engine_error","message":"..."}}       (backpressure)
#   <- {"event":"telemetry","id":"<sid>","data":{"type":"serve.stats",
#       "slots":4,"busy":2,"queued":7,"served":123,"tokens_per_s":...}}
#   -> {"cmd":"serve_close","id":"<sid>"}
#   <- {"event":"serve_closed","id":"<sid>","served":123}
#
# The factory returns an ENGINE the session thread drives through a small
# duck-typed surface (no imports required on this side):
#
#   engine.slots          int, concurrent request lanes (default 1)
#   engine.admit(rid, prompt, params)   occupy a free lane (host-side)
#   engine.step() -> [{"rid", "tokens": [...], "done": bool, ...}, ...]
#                         advance every busy lane one chunk
#   engine.cancel(rid)    optional: free a lane early (deadline)
#   engine.close()        optional: teardown at serve_close
#
# Inside the worker an admission queue feeds the engine's slot loop, so
# concurrent requests share one static-shape batch (continuous batching —
# models/serve.py's ContinuousEngine implements this surface for LMs).
# Backpressure is a bounded queue: a request arriving on a full queue is
# rejected immediately with code `serve_admission_shed`, which the
# dispatcher classifies PERMANENT via the duck-typed fault-label hook
# (retrying amplifies the very overload that shed the work).  Per-request
# deadlines are enforced both in the queue and mid-generation.
#
# `serve.token` events carry `idx` — the request's cumulative token count
# BEFORE the chunk — so a dispatcher replaying a deterministic request on a
# fresh session after a mid-stream death can splice the streams with no
# duplicate or lost tokens.
# --------------------------------------------------------------------------


#: Dispatcher epoch fence (split-brain guard).  ``value`` is the highest
#: epoch this worker has EVER seen (epoch command, or the adopt handshake);
#: ``channel`` is the epoch the CURRENT channel declared.  A channel whose
#: declared epoch is below the high-water mark belongs to a dispatcher that
#: crashed and was succeeded — its mutating commands are refused with
#: ``stale_epoch`` so a zombie controller can never double-dispatch work a
#: newer incarnation already owns.  Both start at 0: a dispatcher that
#: never declares an epoch (journaling off, old client) is unfenced.
_EPOCH = {"value": 0, "channel": 0}

#: Commands that mutate worker state and must be epoch-fenced.  Reads
#: (ping, inventories, watch) stay open to any dispatcher — a stale one
#: can look, not touch.
_FENCED_CMDS = frozenset((
    "run", "register_fn", "invoke", "multi_invoke", "serve_open",
    "serve_request", "serve_prefill", "serve_close", "serve_resume",
    "serve_cancel", "serve_attach", "serve_detach", "kill",
))


def _epoch_ok() -> bool:
    return _EPOCH["channel"] >= _EPOCH["value"]


def _handle_epoch_cmd(command: dict) -> None:
    try:
        declared = int(command.get("epoch") or 0)
    except (TypeError, ValueError):
        declared = 0
    _EPOCH["channel"] = declared
    if declared >= _EPOCH["value"]:
        _EPOCH["value"] = declared
        _emit({"event": "epoch_ok", "epoch": declared})
    else:
        _emit({
            "event": "error", "id": "", "code": "stale_epoch",
            "message": (
                f"dispatcher epoch {declared} is stale "
                f"(worker has seen {_EPOCH['value']})"
            ),
        })


def _refuse_stale(name: str, command: dict) -> None:
    """Answer one fenced command from a stale dispatcher.

    The refusal rides whatever event shape that command's waiter settles
    on, so the stale dispatcher fails fast instead of timing out."""
    message = (
        f"stale dispatcher epoch {_EPOCH['channel']} "
        f"(worker fenced at {_EPOCH['value']})"
    )
    sid = str(command.get("id") or "")
    if name == "serve_request":
        _emit({
            "event": "telemetry", "id": sid,
            "data": _build_worker_event(
                {}, "serve.reject", rpc=True,
                rid=str(command.get("rid") or ""),
                code="stale_epoch", message=message,
            ),
        })
    elif name == "serve_prefill":
        _emit({"event": "serve_kv", "id": sid,
               "rid": str(command.get("rid") or ""),
               "code": "stale_epoch", "message": message})
    elif name in ("serve_open", "serve_close"):
        _emit({"event": "serve_error", "id": sid, "code": "stale_epoch",
               "message": message, "permanent": True})
    elif name == "serve_resume":
        _emit({"event": "serve_resumed", "id": sid,
               "rid": str(command.get("rid") or ""),
               "state": "refused", "code": "stale_epoch"})
    elif name in ("serve_attach", "serve_detach"):
        _emit({"event": name + "ed", "id": sid,
               "adapter": str(command.get("adapter") or ""),
               "code": "stale_epoch", "message": message,
               "permanent": True})
    else:
        _emit({"event": "error", "id": sid, "code": "stale_epoch",
               "message": message})


#: sid -> live _ServeSession; read by the heartbeat payload so a serving
#: worker's beats carry slot occupancy.
_SERVE_SESSIONS: dict = {}


def _gray_chaos_from_env() -> dict | None:
    """Worker-side gray-fault injection spec from ``COVALENT_TPU_CHAOS``.

    The transport-level ``ChaosTransport`` gates dispatcher-side ops, but
    a serving brownout has to live where the latency lives: in the decode
    loop.  This parses only the gray keys (``seed``, ``jitter``,
    ``p_slow``, ``slow_factor``) from the same spec — unknown keys are
    *ignored* here (they are the transport's business, validated there) —
    and returns a seeded plan dict, or None when no gray mode is set.
    """
    import random as random_mod

    spec = os.environ.get("COVALENT_TPU_CHAOS", "").strip()
    if not spec:
        return None
    vals = {"seed": 0.0, "jitter": 0.0, "p_slow": 0.0, "slow_factor": 10.0}
    for token in spec.split(","):
        key, sep, value = token.strip().partition("=")
        if sep and key.strip() in vals:
            try:
                vals[key.strip()] = float(value)
            except ValueError:
                pass
    if vals["jitter"] <= 0 and vals["p_slow"] <= 0:
        return None
    return {
        "rng": random_mod.Random(int(vals["seed"])),
        "jitter": vals["jitter"],
        "p_slow": vals["p_slow"],
        "slow_s": vals["slow_factor"] * max(vals["jitter"], 0.01),
    }


def _serve_occupancy() -> dict:
    """Aggregate slot occupancy across this process's live sessions."""
    sessions = list(_SERVE_SESSIONS.values())
    if not sessions:
        return {}
    return {
        "sessions": len(sessions),
        "slots": sum(s.slots for s in sessions),
        "busy": sum(len(s.running) for s in sessions),
        "queued": sum(s.queue.qsize() for s in sessions),
    }


class _ServeSession:
    """One resident serving session: engine + admission queue + loop thread.

    The command loop calls :meth:`submit` / :meth:`close` (cheap, non-
    blocking); everything slow — the factory call (model load + compile),
    admission, decode chunks — runs on the session's own daemon thread so
    the protocol stays live while the engine works.
    """

    def __init__(self, sid: str, command: dict) -> None:
        import queue as queue_mod

        self.sid = sid
        self.spec = dict(command.get("spec") or {})
        self.spec.setdefault("operation_id", sid)
        options = dict(command.get("options") or {})
        try:
            self.queue_max = max(1, int(options.get("queue_max", 64)))
        except (TypeError, ValueError):
            self.queue_max = 64
        try:
            self.default_deadline_s = float(
                options.get("default_deadline_s") or 0.0
            )
        except (TypeError, ValueError):
            self.default_deadline_s = 0.0
        try:
            self.stats_interval_s = float(
                options.get("stats_interval_s") or 1.0
            )
        except (TypeError, ValueError):
            self.stats_interval_s = 1.0
        self.digest = str(command.get("digest") or "")
        self.path = str(command.get("path") or "")
        self.queue: "queue_mod.Queue" = queue_mod.Queue()
        #: serve_prefill commands awaiting the session thread (the
        #: disaggregated tier's prefill-only work: no decode lane taken).
        self.prefill_queue: "queue_mod.Queue" = queue_mod.Queue()
        #: serve_attach/serve_detach commands awaiting the session thread
        #: (adapter splices mutate engine state, so they serialize with
        #: admission and decode on the one thread that owns the engine).
        self.attach_queue: "queue_mod.Queue" = queue_mod.Queue()
        self.attaches = 0
        #: rid -> {"deadline": abs_ts|None, "emitted": n, "t_admit": ts}
        self.running: dict = {}
        #: rid -> full emitted-token list for RUNNING lanes; the recovery
        #: path's `serve_resume` re-emits `history[from:]` so a restarted
        #: dispatcher can splice a surviving stream exactly-once from the
        #: client-held high-water mark.  Guarded by ``_history_lock``
        #: together with the emit, so a resume re-emission and a live
        #: chunk can never interleave with a gap between them.
        self.history: dict = {}
        #: rid -> {"tokens": [...], "error": str} for FINISHED requests
        #: (bounded FIFO): a stream that completed while the dispatcher
        #: was dead resumes to its full final answer instead of "unknown".
        self.finished: dict = {}
        self.finished_max = 256
        #: every rid ever accepted into the queue — distinguishes a
        #: queued-but-unadmitted request ("pending") from one this worker
        #: never saw ("unknown") at resume time.
        self.submitted: set = set()
        #: rids a ``serve_cancel`` asked to kill, drained on the session
        #: thread (running lane -> engine cancel + terminal record;
        #: queued-only -> skipped at admission).  The hedging loser-
        #: cancel path frees decode lanes through here.
        self.cancels: set = set()
        self._cancel_lock = threading.Lock()
        self._cancelled_pending: set = set()
        #: Worker-side gray chaos (seeded slow tail / jitter on decode
        #: steps), parsed from COVALENT_TPU_CHAOS after the task env is
        #: applied — how a test brownouts ONE replica of a set.
        self._gray = None
        self._history_lock = threading.Lock()
        self.slots = 1
        self.served = 0
        self.tokens_total = 0
        #: KV data plane accounting (disaggregated prefill/decode).
        self.kv_admits = 0
        self.kv_fallbacks = 0
        self.prefills = 0
        self._t_open = time.time()
        self._closed = threading.Event()
        self._engine = None
        self._thread = threading.Thread(
            target=self._loop, name=f"covalent-tpu-serve-{sid}", daemon=True
        )

    # -- command-loop surface (must never block) ---------------------------

    def start(self) -> None:
        self._thread.start()

    def submit(self, command: dict) -> None:
        """Admission control: bounded queue, immediate shed on overflow."""
        rid = str(command.get("rid") or "")
        if not rid:
            self._emit_reject("", "bad_request", "serve_request requires rid")
            return
        if self._closed.is_set():
            self._emit_reject(rid, "unknown_session", "session closed")
            return
        if self.queue.qsize() >= self.queue_max:
            self._emit_reject(
                rid, "serve_admission_shed",
                f"admission queue full ({self.queue_max})",
            )
            return
        command = dict(command)
        command["_enqueued"] = time.monotonic()
        self.submitted.add(rid)
        self.queue.put(command)

    def submit_prefill(self, command: dict) -> None:
        """Queue one prefill-only command (disaggregated tier).

        Same bounded-admission verdict as :meth:`submit`; refusals
        answer with a ``serve_kv`` error event so the dispatcher's
        prefill waiter fails fast (and degrades to a full prefill on the
        decode replica) instead of sitting out its timeout.
        """
        rid = str(command.get("rid") or "")
        if not rid:
            self._emit_kv("", code="bad_request",
                          message="serve_prefill requires rid")
            return
        if self._closed.is_set():
            self._emit_kv(rid, code="unknown_session",
                          message="session closed")
            return
        if self.prefill_queue.qsize() >= self.queue_max:
            self._emit_kv(
                rid, code="serve_admission_shed",
                message=f"prefill queue full ({self.queue_max})",
            )
            return
        self.prefill_queue.put(dict(command))
        # Wake an idle session loop NOW instead of on its 100ms tick: a
        # prefill replica is usually idle exactly when a prefill lands,
        # and the tick would tax every disaggregated request's TTFT.
        self.queue.put(None)

    def submit_attach(self, command: dict) -> None:
        """Queue one serve_attach/serve_detach for the session thread.

        Splices happen BETWEEN decode chunks on the engine's own thread
        — live lanes never observe a half-written bank — and the answer
        (``serve_attached``/``serve_detached``) is emitted from there so
        it cannot reorder against the splice itself.
        """
        name = str(command.get("adapter") or "")
        event = str(command.get("cmd") or "serve_attach") + "ed"
        if self._closed.is_set():
            _emit({"event": event, "id": self.sid, "adapter": name,
                   "code": "unknown_session", "message": "session closed",
                   "permanent": True})
            return
        self.attach_queue.put(dict(command))
        self.queue.put(None)  # wake an idle loop promptly

    def cancel_request(self, rid: str) -> None:
        """Ask the session thread to cancel one request (running or
        queued).  Cheap and non-blocking: the terminal ``serve.token``
        record (``error="cancelled"``) is emitted from the session
        thread so it serializes with live chunks under the history
        lock."""
        if not rid:
            return
        with self._cancel_lock:
            self.cancels.add(rid)
        self.queue.put(None)  # wake an idle loop promptly

    def close(self) -> None:
        self._closed.set()
        self.queue.put(None)  # wake the loop

    def join(self, timeout: float = 10.0) -> None:
        self._thread.join(timeout)

    # -- emission ----------------------------------------------------------

    def _emit_serve(self, type: str, **fields) -> None:
        """One session record over the telemetry side-band (seq-stamped).

        Routed through the coalescer: intermediate token chunks micro-
        batch into one frame per window, everything else flushes through
        immediately (and in order).
        """
        _BATCHER.emit(
            self.sid,
            _build_worker_event(self.spec, type, rpc=True, **fields),
        )

    def _emit_reject(self, rid: str, code: str, message: str) -> None:
        self._emit_serve(
            "serve.reject", rid=rid, code=code, message=message
        )

    def _send_span(self, fields: dict) -> None:
        """:func:`_emit_span`'s sink for this session: the record rides the
        telemetry side-band, and ``SessionSupervisor`` re-emits it with its
        ids kept, which is what puts the worker's queue/admission/decode
        time inside the request's own waterfall."""
        self._emit_serve("span", **fields)

    def _emit_kv(
        self, rid: str, data: bytes | None = None,
        code: str = "", message: str = "",
    ) -> None:
        """One ``serve_kv`` answer to a prefill command: the bundle bytes
        ride a raw binary frame body on a negotiated channel (the same
        road RPC result pickles take), base64-in-JSON otherwise; a
        failure ships the ``code``/``message`` pair with no body."""
        event = {"event": "serve_kv", "id": self.sid, "rid": rid}
        if code:
            event["code"] = code
            event["message"] = message
            _emit(event)
            return
        data = data or b""
        import hashlib as hashlib_mod

        event["digest"] = hashlib_mod.sha256(data).hexdigest()
        event["bytes"] = len(data)
        if _FRAMES["out"]:
            event["_body"] = "data_bytes"
            _emit_frame(_VERB_SERVE, event, data)
        else:
            import base64

            event["data"] = base64.b64encode(data).decode("ascii")
            _emit(event)

    def _pump_prefill(self) -> None:
        """Run queued prefill-only commands on the session thread (the
        engine is single-threaded state) and stream each KV bundle back."""
        import queue as queue_mod

        while True:
            try:
                command = self.prefill_queue.get_nowait()
            except queue_mod.Empty:
                return
            rid = str(command.get("rid") or "")
            prefill = getattr(self._engine, "prefill_only", None)
            if prefill is None:
                self._emit_kv(
                    rid, code="unsupported",
                    message="engine has no prefill_only surface",
                )
                continue
            trace = command.get("trace")
            t_prefill = time.monotonic()
            try:
                data = prefill(
                    command.get("prompt"),
                    dict(command.get("params") or {}),
                )
                if not isinstance(data, (bytes, bytearray)):
                    raise TypeError(
                        f"prefill_only returned {type(data).__name__}, "
                        "want bytes"
                    )
            except BaseException as err:  # noqa: BLE001 - engine refusals
                self._emit_kv(rid, code="prefill_failed", message=repr(err))
                continue
            self.prefills += 1
            _emit_span(
                self._send_span, "serve.worker.prefill", trace,
                t_prefill, rid=rid, kv_bytes=len(data),
            )
            self._emit_kv(rid, bytes(data))

    def _pump_attach(self) -> None:
        """Apply queued adapter splices on the session thread.

        ``serve_attach`` loads a sha256-verified CAS bundle (the model
        registry's wire form) and calls the engine's duck-typed
        ``attach_adapter(name, payload)``; ``serve_detach`` retires a
        name.  Failures answer with the same event carrying ``code`` /
        ``message`` and a duck-typed ``permanent`` flag (an
        ``AdapterUnsupported`` — bad geometry, full bank, reserved name
        — must refuse ONCE, not burn retries), mirroring the open path's
        fault classification.
        """
        import queue as queue_mod

        while True:
            try:
                command = self.attach_queue.get_nowait()
            except queue_mod.Empty:
                return
            verb = str(command.get("cmd") or "serve_attach")
            event = verb + "ed"
            name = str(command.get("adapter") or "")
            t_attach = time.monotonic()

            def _fail(code: str, err, permanent: bool = True,
                      label: str = "") -> None:
                _emit({"event": event, "id": self.sid, "adapter": name,
                       "code": code, "message": repr(err),
                       "permanent": bool(permanent),
                       **({"label": label} if label else {})})

            if verb == "serve_detach":
                detach = getattr(self._engine, "detach_adapter", None)
                if detach is None:
                    _fail("unsupported",
                          "engine has no detach_adapter surface")
                    continue
                try:
                    detach(name)
                except BaseException as err:  # noqa: BLE001 - refusals
                    _fail("unknown_adapter", err)
                    continue
                _emit({"event": event, "id": self.sid, "adapter": name})
                continue
            attach = getattr(self._engine, "attach_adapter", None)
            if attach is None:
                _fail("unsupported", "engine has no attach_adapter surface")
                continue
            code, payload = _load_fn_payload(
                str(command.get("path") or ""),
                str(command.get("digest") or ""),
            )
            if code:
                _fail(code, payload, permanent=(code == "digest_mismatch"))
                continue
            try:
                digest = attach(name, payload)
            except BaseException as err:  # noqa: BLE001 - engine refusals
                label = getattr(err, "fault_label", "") or ""
                permanent = bool(label) and not bool(
                    getattr(err, "fault_transient", False)
                )
                _fail("attach_failed", err, permanent=permanent,
                      label=label)
                continue
            self.attaches += 1
            _emit({
                "event": event, "id": self.sid, "adapter": name,
                "digest": str(digest or ""),
                "attach_s": round(time.monotonic() - t_attach, 6),
            })

    def _resolve_kv(self, command: dict):
        """``(kv_bytes | None, verified)`` for a KV-attached request.

        The bundle arrives as a raw frame body (``kv_bytes``), base64
        JSON (``kv``), or a CAS path staged by the dispatcher
        (``kv_path``); whichever road, its sha256 must match the
        announced ``kv_digest`` BEFORE the engine may unpickle it —
        exactly the register_fn contract.  Any resolution or digest
        failure returns ``(None, False)``: the caller degrades to a full
        prefill, never a user-visible error.
        """
        data = command.get("kv_bytes")
        if data is None and command.get("kv"):
            import base64

            try:
                data = base64.b64decode(command["kv"])
            except (TypeError, ValueError):
                return None, False
        if data is None and command.get("kv_path"):
            try:
                with open(command["kv_path"], "rb") as f:
                    data = f.read()
            except OSError:
                return None, False
        if data is None:
            return None, False
        import hashlib as hashlib_mod

        digest = str(command.get("kv_digest") or "")
        if not digest or hashlib_mod.sha256(
            data
        ).hexdigest() != digest:
            return None, False
        return bytes(data), True

    def _emit_stats(self) -> None:
        age = max(time.time() - self._t_open, 1e-9)
        extra: dict = {}
        # Engine-local counters (ContinuousEngine.stats: prefix-tree
        # hits/misses, prefill positions, KV traffic) become serving
        # metrics — without this they are invisible to /metrics,
        # /history, and the SLO plane.
        engine_stats = getattr(self._engine, "stats", None)
        if isinstance(engine_stats, dict):
            for key in (
                "prefix_hits", "prefix_misses", "prefill_positions",
                "prefix_evictions", "kv_exports",
                "spec_rounds", "spec_proposed", "spec_accepted",
                "spec_refusals", "mode_refusals",
            ):
                value = engine_stats.get(key)
                if isinstance(value, (int, float)):
                    extra[key] = value
            # Per-decode-mode token counters ride through verbatim (the
            # mode set is closed, so the key space is bounded), as do the
            # adapter bank's lifecycle + per-adapter counters (bounded by
            # COVALENT_TPU_SERVE_ADAPTERS_MAX; the dispatcher reaps the
            # per-name series when the session closes).
            for key, value in engine_stats.items():
                if (
                    key.startswith("mode_tokens_")
                    or key.startswith("adapter_")
                ) and isinstance(value, (int, float)):
                    extra[key] = value
            # The accept rate is computed HERE (not on the dispatcher)
            # so any engine exposing the two counters — the real one or
            # a CI stub — feeds the gauge the same way.
            proposed = engine_stats.get("spec_proposed")
            if isinstance(proposed, (int, float)) and proposed > 0:
                accepted = engine_stats.get("spec_accepted") or 0
                extra["spec_accept_rate"] = round(
                    float(accepted) / float(proposed), 4
                )
        if self.kv_admits or self.kv_fallbacks:
            extra["kv_admits"] = self.kv_admits
            extra["kv_fallbacks"] = self.kv_fallbacks
        if self.prefills:
            extra["prefills"] = self.prefills
        jit = _obs_totals("jitstats")
        if jit is not None:
            # Running totals of this runtime's compiles; the dispatcher
            # adds their growth into covalent_tpu_worker_jit_seconds_total.
            extra["jit"] = jit
        self._emit_serve(
            "serve.stats",
            slots=self.slots,
            busy=len(self.running),
            queued=self.queue.qsize(),
            served=self.served,
            tokens_total=self.tokens_total,
            tokens_per_s=round(self.tokens_total / age, 3),
            **extra,
        )

    # -- session thread ----------------------------------------------------

    def _open_engine(self) -> bool:
        """Apply the env contract, load + verify the factory payload, build
        the engine, ack open."""
        try:
            _apply_spec_env(self.spec)
        except Exception as err:  # noqa: BLE001 - answered as a refused open
            self._emit_open_error("env_failed", err, permanent=True)
            return False
        code, loaded = _load_fn_payload(self.path, self.digest)
        if code:
            self._emit_open_error(code, loaded, permanent=(
                code == "digest_mismatch"
            ))
            return False
        try:
            self._engine = loaded()
        except BaseException as err:  # noqa: BLE001 - arbitrary factories
            # Duck-typed permanence: a factory refusing its model shape
            # (e.g. rolling_cache) tags fault_label/fault_transient; the
            # dispatcher must NOT burn gang retries re-opening it.
            label = getattr(err, "fault_label", "") or ""
            permanent = bool(label) and not bool(
                getattr(err, "fault_transient", False)
            )
            self._emit_open_error(
                "factory_failed", err, permanent=permanent, label=label
            )
            return False
        try:
            self.slots = max(1, int(getattr(self._engine, "slots", 1)))
        except (TypeError, ValueError):
            self.slots = 1
        _emit({
            "event": "serve_opened", "id": self.sid,
            "slots": self.slots, "pid": os.getpid(),
        })
        return True

    def _emit_open_error(
        self, code: str, err, permanent: bool = False, label: str = ""
    ) -> None:
        # Mark terminal BEFORE the error leaves the process: the client
        # reopens the sid the moment this event lands, and _serve_open
        # must find a closed session it can wait out — not a live-looking
        # one it refuses as a duplicate.
        self._closed.set()
        _emit({
            "event": "serve_error", "id": self.sid, "code": code,
            "message": repr(err), "permanent": bool(permanent),
            **({"label": label} if label else {}),
        })

    def _admit_waiting(self) -> None:
        """Move queued requests onto free engine lanes (deadline-checked)."""
        import queue as queue_mod

        while len(self.running) < self.slots:
            try:
                command = self.queue.get_nowait()
            except queue_mod.Empty:
                return
            if command is None:
                continue
            rid = str(command.get("rid") or "")
            if rid in self._cancelled_pending:
                # Cancelled while queued: never admit; terminal record so
                # a resume finds "done" with the cancellation marker.
                self._cancelled_pending.discard(rid)
                with self._history_lock:
                    self._emit_serve(
                        "serve.token", rid=rid, idx=0, tokens=[],
                        done=True, error="cancelled",
                    )
                    self._finish_history(rid, "cancelled")
                continue
            deadline_s = command.get("deadline_s", self.default_deadline_s)
            try:
                deadline_s = float(deadline_s or 0.0)
            except (TypeError, ValueError):
                deadline_s = 0.0
            if deadline_s > 0 and (
                time.monotonic() - command["_enqueued"] >= deadline_s
            ):
                self._emit_reject(
                    rid, "deadline",
                    f"request spent its {deadline_s:.1f}s deadline queued",
                )
                continue
            prompt = command.get("prompt")
            params = dict(command.get("params") or {})
            trace = command.get("trace")
            t_admit_start = time.monotonic()
            _emit_span(
                self._send_span, "serve.worker.queue_wait", trace,
                command["_enqueued"], t_admit_start, rid=rid,
            )
            admitted = False
            if (
                command.get("kv_bytes") is not None
                or command.get("kv")
                or command.get("kv_path")
            ):
                # Disaggregated fast path: scatter the shipped KV bundle
                # straight into a lane (digest-verified first).  ANY
                # failure — torn transfer, mismatched digest, a bundle
                # from a different engine shape, an engine without the
                # surface — degrades to the full prefill below; the
                # caller's stream must never see the difference.
                kv_data, verified = self._resolve_kv(command)
                admit_kv = getattr(self._engine, "admit_from_kv", None)
                if verified and admit_kv is not None:
                    try:
                        admit_kv(rid, kv_data, params)
                        admitted = True
                        self.kv_admits += 1
                    except BaseException:  # noqa: BLE001 - fall back
                        admitted = False
                if not admitted:
                    self.kv_fallbacks += 1
            if not admitted:
                try:
                    self._engine.admit(rid, prompt, params)
                except BaseException as err:  # noqa: BLE001 - rejections
                    self._emit_reject(rid, "engine_error", repr(err))
                    continue
            t_admitted = time.monotonic()
            _emit_span(
                self._send_span, "serve.worker.admission", trace,
                t_admit_start, t_admitted, rid=rid, kv=admitted,
            )
            self.running[rid] = {
                "deadline": (
                    command["_enqueued"] + deadline_s
                    if deadline_s > 0 else None
                ),
                "emitted": 0,
                "t_admit": t_admitted,
                "trace": trace,
            }

    def _cancel_lane(self, rid: str) -> None:
        cancel = getattr(self._engine, "cancel", None)
        if cancel is not None:
            try:
                cancel(rid)
            except BaseException:  # noqa: BLE001 - best-effort free
                pass

    def _drain_cancels(self) -> None:
        """Apply queued ``serve_cancel`` requests on the session thread.

        A running lane is cancelled mid-stream: engine lane freed, one
        terminal ``serve.token`` (``done=True, error="cancelled"``)
        emitted under the history lock, history moved to the finished
        ring — exactly the deadline-reclaim shape, so a later resume
        answers ``done`` with the cancellation marker.  A rid still
        queued is remembered and skipped at admission.  An unknown rid
        is a no-op (cancels are fire-and-forget and race completion).
        """
        with self._cancel_lock:
            if not self.cancels:
                return
            rids = list(self.cancels)
            self.cancels.clear()
        for rid in rids:
            state = self.running.get(rid)
            if state is None:
                if rid in self.submitted and rid not in self.finished:
                    self._cancelled_pending.add(rid)
                continue
            self._cancel_lane(rid)
            _emit_span(
                self._send_span, "serve.worker.decode", state.get("trace"),
                state["t_admit"], rid=rid,
                tokens=state["emitted"], error="cancelled",
            )
            with self._history_lock:
                self._emit_serve(
                    "serve.token", rid=rid, idx=state["emitted"],
                    tokens=[], done=True, error="cancelled",
                )
                self.served += 1
                self.running.pop(rid, None)
                self._finish_history(rid, "cancelled")

    def _finish_history(self, rid: str, error: str = "") -> None:
        """Move one rid's history into the bounded finished ring.

        Caller holds ``_history_lock``.  The ring exists for the crash
        window: a stream that completes while no dispatcher is listening
        must still resume to its full final answer, but memory for dead
        streams cannot grow forever."""
        tokens = self.history.pop(rid, [])
        self.finished[rid] = {"tokens": tokens, "error": error}
        while len(self.finished) > self.finished_max:
            self.finished.pop(next(iter(self.finished)))

    def resume(self, rid: str, start: int) -> None:
        """Re-emit one stream's tokens from ``start`` (recovery path).

        Called on the command-loop thread by ``serve_resume`` after a
        dispatcher restart re-adopts this session.  The re-emission and
        any concurrent live chunk serialize on ``_history_lock``, so the
        wire sees ``history[start:]`` at some idx==start followed by
        chunks whose idx continues from the re-emitted end — the
        dispatcher's existing splice dedups any overlap and a gap is
        impossible.  The ``serve_resumed`` ack tells the dispatcher what
        this worker knows: ``streaming`` (live lane, tokens re-emitted),
        ``done`` (finished ring hit, full tail + done re-emitted),
        ``pending`` (queued, nothing emitted yet), ``unknown`` (never
        seen — the dispatcher re-sends the full request).
        """
        start = max(0, int(start or 0))
        with self._history_lock:
            if rid in self.running:
                tokens = list(self.history.get(rid, ())[start:])
                self._emit_serve(
                    "serve.token", rid=rid, idx=start, tokens=tokens,
                    done=False, resumed=True,
                )
                state, sent = "streaming", len(tokens)
            elif rid in self.finished:
                entry = self.finished[rid]
                tokens = list(entry["tokens"][start:])
                extra = (
                    {"error": entry["error"]} if entry.get("error") else {}
                )
                self._emit_serve(
                    "serve.token", rid=rid, idx=start, tokens=tokens,
                    done=True, resumed=True, **extra,
                )
                state, sent = "done", len(tokens)
            elif rid in self.submitted:
                state, sent = "pending", 0
            else:
                state, sent = "unknown", 0
        _emit({
            "event": "serve_resumed", "id": self.sid, "rid": rid,
            "state": state, "from": start, "sent": sent,
        })

    def inventory(self) -> dict:
        """This session's entry in the ``serve_inventory`` answer."""
        with self._history_lock:
            running = {
                rid: int(state.get("emitted") or 0)
                for rid, state in self.running.items()
            }
            finished = {
                rid: {"tokens": len(entry["tokens"]),
                      "error": entry.get("error") or ""}
                for rid, entry in self.finished.items()
            }
        entry = {
            "sid": self.sid,
            "digest": self.digest,
            "slots": self.slots,
            "served": self.served,
            "queued": self.queue.qsize(),
            "running": running,
            "finished": finished,
        }
        # Attached adapters (name -> content digest): the recovery path
        # compares this against the journaled registry records to decide
        # which re-attaches a re-adopted session still needs.
        digests = getattr(self._engine, "adapter_digests", None)
        if isinstance(digests, dict) and digests:
            entry["adapters"] = {
                str(k): str(v) for k, v in digests.items()
            }
        return entry

    def _pump_engine(self) -> None:
        """One decode chunk for every busy lane; stream fresh tokens.

        On speculative engines (``engine.spec_active``) the chunk's wall
        time is attributed per-request PROPORTIONALLY to each request's
        share of the chunk's fresh tokens, accumulated per lane and
        attached to the final token record as ``spec_verify_s`` — the
        dispatcher tiles it into the request's latency waterfall.  An
        attribution, not a measurement: lanes decode fused, so a
        per-request split of one wave is proportional by construction.
        """
        gray = self._gray
        if gray is not None:
            # Seeded gray latency: the engine still answers — just late.
            if gray["jitter"] > 0:
                time.sleep(gray["rng"].random() * gray["jitter"])
            if gray["p_slow"] > 0 and gray["rng"].random() < gray["p_slow"]:
                time.sleep(gray["slow_s"])
        spec = bool(getattr(self._engine, "spec_active", False))
        t_step = time.monotonic()
        try:
            with _Annotated("serve.loop.step"):
                events = self._engine.step() or []
        except BaseException as err:  # noqa: BLE001 - engine crash fails all
            for rid in list(self.running):
                self._emit_reject(rid, "engine_error", repr(err))
                self._cancel_lane(rid)
                self.running.pop(rid, None)
            return
        step_s = time.monotonic() - t_step
        with _Annotated("serve.loop.emit"):
            self._emit_chunk(events, step_s, spec)

    def _emit_chunk(self, events: list, step_s: float, spec: bool) -> None:
        """Stream one decode chunk's fresh tokens, then enforce deadlines."""
        chunk_tokens = sum(
            len(e.get("tokens") or ()) for e in events
        ) if spec else 0
        for event in events:
            rid = str(event.get("rid") or "")
            state = self.running.get(rid)
            if state is None:
                continue
            tokens = list(event.get("tokens") or ())
            done = bool(event.get("done"))
            extra = {
                k: v for k, v in event.items()
                if k not in ("rid", "tokens", "done")
            }
            if spec and chunk_tokens and tokens:
                state["spec_s"] = (
                    state.get("spec_s", 0.0)
                    + step_s * len(tokens) / chunk_tokens
                )
            if done:
                extra.setdefault(
                    "gen_s", round(time.monotonic() - state["t_admit"], 6)
                )
                if state.get("spec_s"):
                    extra.setdefault(
                        "spec_verify_s", round(state["spec_s"], 6)
                    )
                # Span BEFORE the final token record: the dispatcher
                # finalizes the trace on ``done``, and the side-band is
                # ordered — emitting after would strand the decode span
                # as a straggler.
                _emit_span(
                    self._send_span, "serve.worker.decode", state.get("trace"),
                    state["t_admit"], rid=rid,
                    tokens=state["emitted"] + len(tokens),
                )
            # History extend + emit are one atomic unit under the lock a
            # serve_resume re-emission also takes: either the resume
            # snapshot includes this chunk, or this chunk's idx lands at
            # (or past) the resume's end — never a gap between them.
            with self._history_lock:
                idx = state["emitted"]
                state["emitted"] += len(tokens)
                self.tokens_total += len(tokens)
                if tokens:
                    self.history.setdefault(rid, []).extend(tokens)
                self._emit_serve(
                    "serve.token", rid=rid, idx=idx, tokens=tokens,
                    done=done, **extra,
                )
                if done:
                    self.served += 1
                    self.running.pop(rid, None)
                    self._finish_history(rid, str(extra.get("error") or ""))
        # Mid-generation deadline enforcement: a lane past its budget is
        # cancelled and finalized with an error marker, freeing the slot.
        now = time.monotonic()
        for rid, state in list(self.running.items()):
            if state["deadline"] is not None and now >= state["deadline"]:
                self._cancel_lane(rid)
                _emit_span(
                    self._send_span, "serve.worker.decode", state.get("trace"),
                    state["t_admit"], rid=rid,
                    tokens=state["emitted"], error="deadline_exceeded",
                )
                with self._history_lock:
                    self._emit_serve(
                        "serve.token", rid=rid, idx=state["emitted"],
                        tokens=[], done=True, error="deadline_exceeded",
                    )
                    self.served += 1
                    self.running.pop(rid, None)
                    self._finish_history(rid, "deadline_exceeded")

    def _loop(self) -> None:
        if not self._open_engine():
            # Failed open: mark closed so late requests reject cleanly
            # instead of queueing into a thread that already exited.
            self._closed.set()
            _SERVE_SESSIONS.pop(self.sid, None)
            return
        self._gray = _gray_chaos_from_env()  # reads the env just applied
        last_stats = time.monotonic()
        try:
            while not (self._closed.is_set()
                       and not self.running
                       and self.queue.empty()):
                # One annotation per phase of a turn: a profiler capture's
                # host plane then says what the loop did in a device gap.
                with _Annotated("serve.loop.drain"):
                    self._drain_cancels()
                    self._pump_attach()
                    self._pump_prefill()
                with _Annotated("serve.loop.admit"):
                    self._admit_waiting()
                if self.running:
                    self._pump_engine()
                else:
                    # Idle: wait on the queue with a short tick so stats
                    # keep flowing and close() wakes promptly.  The head
                    # stays where it is: taken out and put back, it went
                    # behind whatever had arrived meanwhile.
                    with self.queue.not_empty:
                        if not self.queue.queue:
                            self.queue.not_empty.wait(0.1)
                # Age-out the coalescing buffer: a token batch must never
                # wait on MORE tokens to ship once its window expires.
                _BATCHER.flush_aged()
                if (
                    self.stats_interval_s > 0
                    and time.monotonic() - last_stats >= self.stats_interval_s
                ):
                    last_stats = time.monotonic()
                    self._emit_stats()
        finally:
            closer = getattr(self._engine, "close", None)
            if closer is not None:
                try:
                    closer()
                except BaseException:  # noqa: BLE001 - teardown best-effort
                    pass
            self._emit_stats()
            # The stats record is urgent (non-token) so the coalescer has
            # flushed every buffered token ahead of it; serve_closed must
            # still never overtake a straggler batch.
            _BATCHER.flush()
            _SERVE_SESSIONS.pop(self.sid, None)
            _emit({
                "event": "serve_closed", "id": self.sid,
                "served": self.served,
            })


def _serve_open(command: dict, sessions: dict) -> None:
    sid = str(command.get("id") or "")
    if not sid or not command.get("digest") or not command.get("path"):
        _emit({"event": "serve_error", "id": sid, "code": "bad_request",
               "message": "serve_open requires id, digest and path",
               "permanent": True})
        return
    existing = sessions.get(sid)
    if existing is not None:
        if existing._closed.is_set() and existing._thread.is_alive():
            # Terminating but not yet dead: a failed factory open (or a
            # drained close) emits its error BEFORE the thread's last
            # instructions run, and the client legitimately reopens the
            # moment that event lands — wait out the teardown rather
            # than racing it into a spurious permanent "duplicate".
            existing._thread.join(timeout=2.0)
        if existing._closed.is_set() and not existing._thread.is_alive():
            # A dead entry (failed factory open, or a drained close whose
            # serve_close never arrived): evict so the sid is re-openable
            # — the reconnect path retries the SAME sid on a live agent,
            # and a stale tombstone must not refuse it as a duplicate.
            sessions.pop(sid, None)
        else:
            _emit({"event": "serve_error", "id": sid, "code": "duplicate",
                   "message": f"session {sid} already open",
                   "permanent": True})
            return
    session = _ServeSession(sid, command)
    sessions[sid] = session
    _SERVE_SESSIONS[sid] = session
    session.start()


def _serve_request(command: dict, sessions: dict) -> None:
    sid = str(command.get("id") or "")
    session = sessions.get(sid)
    if session is None:
        # Streamed as a per-request reject so the caller's stream fails
        # fast; the envelope needs no session spec (there is none).
        _emit({
            "event": "telemetry", "id": sid,
            "data": _build_worker_event(
                {}, "serve.reject", rpc=True,
                rid=str(command.get("rid") or ""),
                code="unknown_session",
                message=f"no open session {sid!r}",
            ),
        })
        return
    session.submit(command)


def _serve_prefill(command: dict, sessions: dict) -> None:
    sid = str(command.get("id") or "")
    session = sessions.get(sid)
    if session is None:
        # A direct serve_kv error (not a streamed reject): the prefill
        # waiter settles on serve_kv events only.
        _emit({
            "event": "serve_kv", "id": sid,
            "rid": str(command.get("rid") or ""),
            "code": "unknown_session",
            "message": f"no open session {sid!r}",
        })
        return
    session.submit_prefill(command)


def _serve_attach(command: dict, sessions: dict) -> None:
    """Route one adapter splice (attach or detach) to its session."""
    sid = str(command.get("id") or "")
    event = str(command.get("cmd") or "serve_attach") + "ed"
    session = sessions.get(sid)
    if session is None:
        _emit({"event": event, "id": sid,
               "adapter": str(command.get("adapter") or ""),
               "code": "unknown_session",
               "message": f"no open session {sid!r}", "permanent": True})
        return
    session.submit_attach(command)


def _serve_close(command: dict, sessions: dict) -> None:
    sid = str(command.get("id") or "")
    session = sessions.pop(sid, None)
    if session is None:
        _emit({"event": "serve_error", "id": sid, "code": "unknown_session",
               "message": f"no open session {sid!r}", "permanent": True})
        return
    session.close()
    # The session thread emits serve_closed after its drain; nothing to
    # block on here — the command loop must stay live.


def _serve_cancel(command: dict, sessions: dict) -> None:
    """Fire-and-forget cancellation of one in-flight request.

    The hedging path uses this to free the losing replica's decode lane
    the moment the winner's first token lands.  No waiter: an unknown
    session or rid is a silent no-op (the cancel races completion by
    design), so the only answer is the stream's own terminal record.
    """
    sid = str(command.get("id") or "")
    session = sessions.get(sid)
    if session is not None:
        session.cancel_request(str(command.get("rid") or ""))


def _serve_resume(command: dict, sessions: dict) -> None:
    sid = str(command.get("id") or "")
    rid = str(command.get("rid") or "")
    session = sessions.get(sid)
    if session is None:
        _emit({"event": "serve_resumed", "id": sid, "rid": rid,
               "state": "unknown", "from": 0, "sent": 0})
        return
    try:
        start = int(command.get("from") or 0)
    except (TypeError, ValueError):
        start = 0
    session.resume(rid, start)


def _serve_inventory(sessions: dict) -> None:
    entries = []
    for session in list(sessions.values()):
        if session._closed.is_set():
            continue
        try:
            entries.append(session.inventory())
        except Exception:  # noqa: BLE001 - one bad session must not hide rest
            pass
    _emit({
        "event": "serve_inventory", "pid": os.getpid(),
        "epoch": _EPOCH["value"], "sessions": entries,
    })


def _task_inventory(children: dict) -> None:
    _emit({
        "event": "task_inventory", "pid": os.getpid(),
        "epoch": _EPOCH["value"],
        "tasks": [
            {"id": task_id, "pid": pid}
            for pid, task_id in children.items()
        ],
    })


# --------------------------------------------------------------------------
# Orphan self-defense + live re-adoption.
#
# A pool server's only channel is the stdin/stdout pipe of the process the
# dispatcher spawned — when the dispatcher dies, so does the channel, while
# the resident sessions (model weights, running decodes) live on.  Instead
# of tearing them down, a server with live sessions and a configured grace
# TTL (COVALENT_TPU_ORPHAN_TTL_S) goes into *orphan mode*: it silences its
# dead stdout, opens a unix rendezvous socket next to this file (the remote
# cache directory the dispatcher stages into), publishes its coordinates in
# `pool_orphan.json`, and keeps decoding — growing each stream's token
# history — until either a successor dispatcher adopts it (one `adopt`
# line, epoch-fenced, then the socket BECOMES fds 0/1 and a fresh ready
# banner starts the protocol over) or the TTL expires and it drains and
# exits rather than leaking model memory forever.
# --------------------------------------------------------------------------

ORPHAN_RENDEZVOUS = "pool_orphan.json"


def _orphan_dir() -> str:
    return os.path.dirname(os.path.abspath(__file__))


def _orphan_ttl_s() -> float:
    try:
        return float(os.environ.get("COVALENT_TPU_ORPHAN_TTL_S", "0") or 0)
    except (TypeError, ValueError):
        return 0.0


def _enter_orphan_mode(sel, serve_sessions: dict):
    """Switch a channel-dead pool server into adoption-wait; returns the
    orphan state dict, or None when orphan mode does not apply (no live
    sessions, no TTL, or the socket cannot be created)."""
    import selectors
    import socket

    ttl = _orphan_ttl_s()
    live = {
        sid: s for sid, s in serve_sessions.items()
        if not s._closed.is_set()
    }
    if ttl <= 0 or not live:
        return None
    base = _orphan_dir()
    sock_path = os.path.join(base, f"pool_orphan.{os.getpid()}.sock")
    try:
        os.unlink(sock_path)
    except OSError:
        pass
    try:
        listener = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        listener.bind(sock_path)
        listener.listen(2)
        listener.setblocking(False)
    except OSError as err:
        print(f"orphan socket failed: {err}", file=sys.stderr)
        return None
    meta = {
        "pid": os.getpid(), "sock": sock_path, "epoch": _EPOCH["value"],
        "sessions": sorted(live), "ttl_s": ttl, "t_orphaned": time.time(),
    }
    rendezvous = os.path.join(base, ORPHAN_RENDEZVOUS)
    tmp = f"{rendezvous}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump(meta, fh)
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, rendezvous)
    except OSError as err:
        print(f"orphan rendezvous failed: {err}", file=sys.stderr)
        listener.close()
        return None
    # Silence the dead pipe: every emitter (session threads included)
    # keeps running, but writes land in /dev/null instead of raising.
    devnull = os.open(os.devnull, os.O_WRONLY)
    with _EMIT_LOCK:
        try:
            sys.stdout.flush()
        except OSError:
            pass
        os.dup2(devnull, 1)
    os.close(devnull)
    try:
        _BATCHER.flush()
    except Exception:  # noqa: BLE001 - buffers now drain to /dev/null
        pass
    sel.register(listener, selectors.EVENT_READ, "orphan")
    return {
        "listener": listener, "sock_path": sock_path,
        "rendezvous": rendezvous, "deadline": time.monotonic() + ttl,
    }


def _orphan_cleanup(sel, orphan: dict) -> None:
    try:
        sel.unregister(orphan["listener"])
    except (KeyError, ValueError):
        pass
    try:
        orphan["listener"].close()
    except OSError:
        pass
    for path in (orphan["sock_path"], orphan["rendezvous"]):
        try:
            os.unlink(path)
        except OSError:
            pass


def _orphan_try_adopt(sel, orphan: dict, serve_sessions: dict) -> bool:
    """Accept one adoption attempt; True when the socket became the new
    channel (caller restarts the protocol), False to keep waiting."""
    try:
        conn, _ = orphan["listener"].accept()
    except OSError:
        return False
    try:
        conn.setblocking(True)
        conn.settimeout(10.0)
        data = b""
        while not data.endswith(b"\n") and len(data) < 65536:
            chunk = conn.recv(4096)
            if not chunk:
                break
            data += chunk
        try:
            adopt = json.loads(data.decode("utf-8", "replace"))
        except ValueError:
            adopt = {}
        epoch = 0
        try:
            epoch = int(adopt.get("epoch") or 0)
        except (TypeError, ValueError):
            pass
        if adopt.get("cmd") != "adopt" or epoch < _EPOCH["value"]:
            # Fence: a stale dispatcher (or garbage) does not get the
            # sessions — answer and keep waiting for the real successor.
            try:
                conn.sendall((json.dumps({
                    "event": "error", "code": "stale_epoch",
                    "message": (
                        f"adopt epoch {epoch} < fence {_EPOCH['value']}"
                    ),
                }) + "\n").encode())
            except OSError:
                pass
            conn.close()
            return False
        _EPOCH["value"] = epoch
        _EPOCH["channel"] = epoch
        conn.settimeout(None)
        fd = conn.fileno()
        with _EMIT_LOCK:
            try:
                sys.stdout.flush()
            except OSError:
                pass
            os.dup2(fd, 0)
            os.dup2(fd, 1)
            # The adopted channel starts over on JSONL; the successor
            # re-negotiates frames off the fresh banner like any client.
            _FRAMES["out"] = False
            _FRAMES["codec"] = ""
        conn.close()  # fds 0/1 hold the socket now
    except OSError:
        try:
            conn.close()
        except OSError:
            pass
        return False
    _orphan_cleanup(sel, orphan)
    banner = {
        "event": "ready", "pid": os.getpid(), "mode": "pool",
        "reattach": True, "epoch": epoch,
        "sessions": sorted(
            sid for sid, s in serve_sessions.items()
            if not s._closed.is_set()
        ),
    }
    if _frames_enabled():
        banner["frames"] = _FRAME_VERSION
        banner["codecs"] = ["zlib"]
    _emit(banner)
    return True


def attach_relay(sock_path: str) -> int:
    """``harness.py --attach <sock>``: bridge stdio onto an orphan socket.

    The successor dispatcher cannot dial a unix socket on a remote worker
    directly, but it CAN spawn processes there — so re-adoption rides the
    same road as a fresh pool server: spawn this relay via the transport,
    and the relay splices its stdin/stdout onto the orphan's socket.  The
    relay is a dumb pump — the adopt handshake, epoch fence, and banner
    all flow through it verbatim, keeping protocol logic in one place.
    """
    import select as select_mod
    import socket

    try:
        sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        sock.connect(sock_path)
    except OSError as err:
        sys.stdout.write(json.dumps({
            "event": "error", "code": "attach_failed",
            "message": f"connect {sock_path}: {err}",
        }) + "\n")
        sys.stdout.flush()
        return 3
    sock.setblocking(True)
    sfd = sock.fileno()

    def _write_all(fd: int, data: bytes) -> bool:
        while data:
            try:
                n = os.write(fd, data)
            except OSError:
                return False
            data = data[n:]
        return True

    try:
        while True:
            ready, _, _ = select_mod.select([0, sfd], [], [])
            if 0 in ready:
                data = os.read(0, 65536)
                if not data:
                    break  # dispatcher hung up: orphan re-enters wait
                try:
                    sock.sendall(data)
                except OSError:
                    break
            if sfd in ready:
                data = sock.recv(65536)
                if not data:
                    break  # worker side closed (refused or exited)
                if not _write_all(1, data):
                    break
    finally:
        try:
            sock.close()
        except OSError:
            pass
    return 0


def _announce_preemption(reason: str = "sigterm") -> None:
    """Emit ``serve.preempt`` on every live session's side-band."""
    for session in list(_SERVE_SESSIONS.values()):
        try:
            session._emit_serve("serve.preempt", reason=reason)
        except Exception:  # noqa: BLE001 - notice is best-effort
            pass
    try:
        _BATCHER.flush()
    except Exception:  # noqa: BLE001
        pass


def _install_serve_preempt_notice() -> None:
    """SIGTERM on a serving runtime = the spot preemption notice.

    Announce ``serve.preempt`` on every live session's side-band and KEEP
    SERVING: the dispatcher-side supervisor warm-hands the sessions off to
    a fresh gang during the grace window (draining in-flight streams via
    the exactly-once idx splice), and the preempter's hard kill — or the
    channel death — is what actually ends this process, not the notice.
    """
    import signal

    if threading.current_thread() is not threading.main_thread():
        return  # pragma: no cover - signal API is main-thread-only

    def _on_term(signum, frame):
        if not _SERVE_SESSIONS:
            # Nothing to hand off: keep the pre-notice contract and die
            # with the signal, so plain TERM-driven teardown of idle
            # runtimes is unchanged.
            signal.signal(signal.SIGTERM, signal.SIG_DFL)
            os.kill(os.getpid(), signal.SIGTERM)
            return
        # Never write the channel from the handler itself: the main thread
        # may hold _EMIT_LOCK at delivery time and the handler runs ON the
        # main thread (same-thread deadlock).  A helper thread serializes
        # through the lock normally.
        threading.Thread(
            target=_announce_preemption,
            name="covalent-tpu-preempt-notice", daemon=True,
        ).start()

    try:
        signal.signal(signal.SIGTERM, _on_term)
    except (ValueError, OSError):  # pragma: no cover - exotic platforms
        pass


def serve_child() -> int:
    """``harness.py --serve-child``: one serving session over stdin.

    The native C++ agent's session support: it forks this runner at
    ``serve_open`` with the pipe held open, forwards ``serve_request`` /
    ``serve_close`` lines to stdin, and streams every stdout event back
    over its channel verbatim — the protocol (and the engine contract)
    stays uniform across both runtimes.  EOF closes the session.
    """
    _install_serve_preempt_notice()
    sessions: dict = {}
    opened: list = []  # every session ever opened, for the final drain
    buffer = bytearray()
    closing = False
    while not closing:
        for command in _extract_commands(buffer):
            name = command.get("cmd")
            if name == "frames":
                _handle_frames_cmd(command)
            elif name == "serve_open":
                _serve_open(command, sessions)
                session = sessions.get(str(command.get("id") or ""))
                if session is not None and session not in opened:
                    opened.append(session)
            elif name == "serve_request":
                _serve_request(command, sessions)
            elif name == "serve_cancel":
                _serve_cancel(command, sessions)
            elif name == "serve_resume":
                _serve_resume(command, sessions)
            elif name == "serve_inventory":
                _serve_inventory(sessions)
            elif name == "serve_prefill":
                _serve_prefill(command, sessions)
            elif name in ("serve_attach", "serve_detach"):
                _serve_attach(command, sessions)
            elif name == "profile_start":
                _profile_start(command)
            elif name == "profile_stop":
                _profile_stop(command)
            elif name == "serve_close":
                _serve_close(command, sessions)
                closing = True
                break
            else:
                _emit({"event": "error", "message": f"unknown cmd: {name}"})
        if closing:
            break
        data = sys.stdin.buffer.read1(65536)
        if not data:
            break  # EOF closes the session, as before
        buffer.extend(data)
    for session in sessions.values():
        session.close()
    for session in opened:
        session.join()
    return 0


#: Per-pump read ceiling: one oversized telemetry burst must not wedge the
#: command loop behind a single giant read.
_WATCH_READ_LIMIT = 256 * 1024


def _pump_watchers(watchers: dict) -> None:
    """Forward new complete JSONL lines from every watched file.

    Each watcher tracks a byte offset; partial trailing lines wait in a
    buffer for the next pump.  Unparsable lines are dropped (the side-band
    forwards structured events only), and a missing file just means the
    task hasn't emitted yet.
    """
    for task_id, w in list(watchers.items()):
        try:
            size = os.path.getsize(w["path"])
        except OSError:
            continue
        if size < w["pos"]:
            w["pos"], w["buf"] = 0, ""  # truncated/rotated: start over
        if size == w["pos"]:
            continue
        try:
            with open(w["path"], "r", encoding="utf-8", errors="replace") as f:
                f.seek(w["pos"])
                chunk = f.read(_WATCH_READ_LIMIT)
                w["pos"] = f.tell()
        except OSError:
            continue
        w["buf"] += chunk
        records = []
        while "\n" in w["buf"]:
            line, w["buf"] = w["buf"].split("\n", 1)
            line = line.strip()
            if not line:
                continue
            try:
                data = json.loads(line)
            except ValueError:
                continue
            if isinstance(data, dict):
                records.append(data)
        if records:
            # One frame per pump per task (or per-record lines when frames
            # are off): a telemetry burst costs one write, not one per line.
            emit_telemetry_batch(task_id, records)


def _reap(children: dict, watchers: dict | None = None) -> None:
    while True:
        try:
            pid, status = os.waitpid(-1, os.WNOHANG)
        except ChildProcessError:
            return
        if pid <= 0:
            return
        task_id = children.pop(pid, None)
        if task_id is None:
            continue
        code = os.waitstatus_to_exitcode(status)
        if watchers is not None and task_id in watchers:
            # Auto-unwatch on exit (after one final pump so the tail of
            # the telemetry file is flushed): a long-lived server must not
            # keep stat()ing files of finished tasks forever.
            _pump_watchers({task_id: watchers[task_id]})
            del watchers[task_id]
        _emit({
            "event": "exit",
            "id": task_id,
            "code": code if code >= 0 else -1,
            "signal": -code if code < 0 else 0,
        })


def serve() -> int:
    """Forkserver main loop: poll stdin commands + a SIGCHLD wakeup pipe."""
    import selectors
    import signal

    for mod in filter(None, os.environ.get(
        "COVALENT_TPU_POOL_PRELOAD", "cloudpickle"
    ).split(",")):
        try:
            __import__(mod.strip())
        except Exception as preload_error:  # noqa: BLE001 - children retry
            print(f"preload {mod} failed: {preload_error}", file=sys.stderr)

    rpipe, wpipe = os.pipe()
    os.set_blocking(rpipe, False)
    os.set_blocking(wpipe, False)
    signal.set_wakeup_fd(wpipe)
    signal.signal(signal.SIGCHLD, lambda *_: None)
    signal.signal(signal.SIGPIPE, signal.SIG_IGN)
    # Preemption notice for resident serving sessions hosted in THIS
    # process (pool mode): announce, keep serving through the grace window.
    _install_serve_preempt_notice()

    sel = selectors.DefaultSelector()
    sel.register(0, selectors.EVENT_READ, "stdin")
    sel.register(rpipe, selectors.EVENT_READ, "sigchld")

    children: dict = {}
    #: task id -> {"path", "pos", "buf"} telemetry tails (watch cmd).
    watchers: dict = {}
    #: digest -> unpickled callable (register_fn cmd); dies with the
    #: process, which is exactly the lifetime the dispatcher's
    #: per-connection registered-set mirrors.
    rpc_registry: dict = {}
    #: sid -> _ServeSession (serve_open cmd); sessions die with the
    #: channel — a reconnecting dispatcher re-opens on a fresh server.
    serve_sessions: dict = {}
    buffer = bytearray()
    running = True
    stdin_open = True
    #: Non-None while waiting out the orphan grace TTL for re-adoption.
    orphan: dict | None = None
    banner: dict = {"event": "ready", "pid": os.getpid(), "mode": "pool"}
    if _frames_enabled():
        # Capability advertisement: the client answers with a `frames`
        # command (or stays silently on JSONL — old clients, kill switch).
        banner["frames"] = _FRAME_VERSION
        banner["codecs"] = ["zlib"]
    _emit(banner)

    while running and (stdin_open or children or orphan is not None):
        # With live watchers (or an orphan TTL ticking down) the select
        # wakes on a short tick; otherwise block freely.
        tick = 0.25 if (watchers or orphan is not None) else None
        for key, _ in sel.select(timeout=tick):
            if key.data == "sigchld":
                try:
                    while os.read(rpipe, 512):
                        pass
                except BlockingIOError:
                    pass
                _reap(children, watchers)
                continue
            if key.data == "orphan":
                if orphan is not None and _orphan_try_adopt(
                    sel, orphan, serve_sessions
                ):
                    # The orphan socket IS fds 0/1 now: restart the
                    # protocol on it (stale inbound bytes discarded).
                    orphan = None
                    stdin_open = True
                    buffer.clear()
                    sel.register(0, selectors.EVENT_READ, "stdin")
                continue
            data = os.read(0, 65536)
            if not data:
                # Channel dropped: children keep running in their own
                # sessions; serve until they are all reaped, then exit.
                # Serving sessions historically died with the channel —
                # but with an orphan grace TTL configured they are held
                # (still decoding, token history growing) for a successor
                # dispatcher to re-adopt; only when no TTL/no sessions
                # do they drain immediately as before.
                stdin_open = False
                sel.unregister(0)
                orphan = _enter_orphan_mode(sel, serve_sessions)
                if orphan is None:
                    for session in list(serve_sessions.values()):
                        session.close()
                    serve_sessions.clear()
                continue
            buffer.extend(data)
            for command in _extract_commands(buffer):
                name = command.get("cmd")
                if name == "ping":
                    _emit({"event": "pong"})
                elif name == "frames":
                    _handle_frames_cmd(command)
                elif name == "epoch":
                    _handle_epoch_cmd(command)
                elif name == "serve_inventory":
                    _serve_inventory(serve_sessions)
                elif name == "task_inventory":
                    _task_inventory(children)
                elif name in _FENCED_CMDS and not _epoch_ok():
                    _refuse_stale(name, command)
                elif name == "serve_resume":
                    _serve_resume(command, serve_sessions)
                elif name == "run":
                    _spawn_task(command, children)
                elif name == "register_fn":
                    _rpc_register(command, rpc_registry)
                elif name == "invoke":
                    _rpc_invoke(command, rpc_registry)
                elif name == "multi_invoke":
                    _rpc_multi_invoke(command, rpc_registry)
                elif name == "serve_open":
                    _serve_open(command, serve_sessions)
                elif name == "serve_request":
                    _serve_request(command, serve_sessions)
                elif name == "serve_cancel":
                    _serve_cancel(command, serve_sessions)
                elif name == "serve_prefill":
                    _serve_prefill(command, serve_sessions)
                elif name in ("serve_attach", "serve_detach"):
                    _serve_attach(command, serve_sessions)
                elif name == "serve_close":
                    _serve_close(command, serve_sessions)
                elif name == "profile_start":
                    _profile_start(command)
                elif name == "profile_stop":
                    _profile_stop(command)
                elif name == "kill":
                    target = command.get("id")
                    sig = int(command.get("sig", 15))
                    for pid, task_id in list(children.items()):
                        if task_id == target:
                            # Group AND direct pid: a kill racing the child's
                            # setsid() would otherwise no-op (same guard as
                            # native/agent.cc kill_task).
                            try:
                                os.killpg(pid, sig)
                            except ProcessLookupError:
                                pass
                            try:
                                os.kill(pid, sig)
                            except ProcessLookupError:
                                pass
                            _emit({"event": "killed", "id": target})
                            break
                    else:
                        _emit({"event": "error", "id": target or "",
                               "message": "unknown task id"})
                elif name == "watch":
                    task_id = command.get("id")
                    path = command.get("path")
                    if not task_id or not path:
                        _emit({"event": "error", "id": task_id or "",
                               "message": "watch requires id and path"})
                    else:
                        # Offset 0 on every (re-)watch: a reconnecting
                        # dispatcher gets the buffered backlog flushed.
                        watchers[task_id] = {"path": path, "pos": 0,
                                             "buf": ""}
                        _emit({"event": "watching", "id": task_id})
                elif name == "unwatch":
                    task_id = command.get("id")
                    watchers.pop(task_id, None)
                    _emit({"event": "unwatched", "id": task_id or ""})
                elif name == "shutdown":
                    _emit({"event": "bye"})
                    running = False
                else:
                    _emit({"event": "error",
                           "message": f"unknown cmd: {name}"})
        if orphan is not None and time.monotonic() >= orphan["deadline"]:
            # Grace TTL spent with no successor: drain and exit instead of
            # leaking model memory (and a TPU reservation) forever.
            _orphan_cleanup(sel, orphan)
            orphan = None
            for session in list(serve_sessions.values()):
                session.close()
            serve_sessions.clear()
        _pump_watchers(watchers)
        _reap(children, watchers)  # belt-and-braces against missed wakeups
    return 0


def main(argv: list[str]) -> int:
    if len(argv) == 2 and argv[1] == "--serve":
        return serve()
    if len(argv) >= 2 and argv[1] == "--rpc-child":
        return rpc_child()
    if len(argv) >= 2 and argv[1] == "--serve-child":
        return serve_child()
    if len(argv) >= 3 and argv[1] == "--attach":
        return attach_relay(argv[2])
    if len(argv) != 2:
        print(
            "usage: harness.py <task_spec.json> | --serve | --rpc-child"
            " | --serve-child | --attach <socket>",
            file=sys.stderr,
        )
        return 2
    # Become a session/process-group leader (pool-mode children already do
    # this in _spawn_task): the dispatcher's cancel and timeout-escalation
    # paths kill `-- -pid`, and only a group leader pid makes that reach
    # the user function's own subprocesses — no orphans on billed TPU time.
    try:
        os.setsid()
    except OSError:
        pass  # already a leader (or platform without sessions)
    with open(argv[1]) as f:
        spec = json.load(f)
    return run_task(spec)


if __name__ == "__main__":
    sys.exit(main(sys.argv))
