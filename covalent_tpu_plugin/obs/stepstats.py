"""The train step as the host drives it: a record of every call to the
jitted step, kept in this process, so that a step the host held up leaves
its cause behind.

``models/train.py``'s ``_TrainStep`` opens :func:`step` around each call.
A call is stamped on the wall clock (``time.time_ns()``, the clock the
profiler gives host events) and timed on ``perf_counter``:

``dispatch_s``
    the jitted call alone.  A loop that blocks on each step before it
    sends the next leaves the device idle that long between steps.
``stats_s``
    ``modelstats.defer``'s read of the previous step's counts.
``interval_s``
    from this call's start to the next call's.

Over each interval it counts what took the host: garbage-collection pauses
by generation (``gc.callbacks``), the calling thread's wait on the run
queue (``/proc/thread-self/schedstat``, left out where the file is absent),
major and minor page faults and involuntary context switches
(``getrusage(RUSAGE_THREAD)``, zeros where the platform has no per-thread
usage), and the worst lateness of a heartbeat the worker's harness reported
meanwhile (:func:`beat`).

Only steady intervals are summed: those in which jit did nothing
(``jitstats.activity`` did not move) and no profiler session started or
stopped, which leaves out the first call, whatever a caller compiles
between its steps and the profiler's writing of its trace.  Memory is
bounded: sums, the recent steady intervals and dispatch times (for
medians), the slowest few intervals with their counters, and at most
:data:`MAX_STALLS` stalls.

**A stall.**  Stacks are dumped only in a worker whose heartbeat named a
file for them (:func:`beat`); elsewhere the record keeps its counters and
never touches ``faulthandler``.  There, once :data:`ARM_AFTER` steady
intervals are known, each call is due to end twice the median of the
recent ones after it began, never under :data:`MIN_DUMP_S`.  A watch thread
looks every :data:`TICK_S`; finding the open interval past its due time, it
writes every thread's Python stack with ``faulthandler.dump_traceback``
while it holds the GIL, so that no thread's frames move or are freed under
the walk.  Where the interval stalls with the GIL held (a C++ call that
keeps it, a collection, the regular-expression engine), the watch cannot
run: for that the watch keeps CPython's own C watchdog
(``faulthandler.dump_traceback_later``) armed :data:`GRACE_S` past the due
time, which writes the stacks without the GIL, and cancels it whenever it
gets to run.  (Armed on its own at every call, the watchdog crashes the
process now and then: its walk without the GIL reads the frames of threads
that exit meanwhile.)  The interval closes at the next call and keeps the
first :data:`STACK_BYTES` of its dump as its ``stacks``.  An interval that
never closes, and one open when :func:`totals` is read, are no stalls.  The
step itself only takes its stamps and counters before its dispatch; the
accounting waits until the dispatch returned, while the device works.
Observability never fails the step: where the record's own work raises,
that call goes unrecorded and the step's result is returned as it is.

A worker sends :func:`totals` home with its result; the dispatcher adds
them into its registry with :func:`absorb_worker`
(``covalent_tpu_worker_step_*``, ``covalent_tpu_worker_heartbeat_late_
seconds``) and re-emits each stall as a ``worker.stall`` event under the
electron's trace.
"""

from __future__ import annotations

import atexit
import faulthandler
import gc
import heapq
import os
import statistics
import sys
import threading
import time
from collections import deque

from . import events as _events
from . import jitstats
from .metrics import REGISTRY, Registry
from .trace import _annotation

try:
    import resource
except ImportError:  # pragma: no cover - not a POSIX host
    resource = None

__all__ = [
    "step", "beat", "totals", "absorb_worker", "ARM_AFTER", "MIN_DUMP_S",
    "TICK_S", "GRACE_S", "MAX_STALLS", "STACK_BYTES", "WORKER_STEP_CALLS",
    "WORKER_STEP_SECONDS", "WORKER_STEP_GC_SECONDS",
    "WORKER_STEP_RUNQUEUE_SECONDS", "WORKER_STEP_HOST_EVENTS",
    "WORKER_STEP_DISPATCH_SECONDS", "WORKER_HEARTBEAT_LATE_SECONDS",
]

#: Steady intervals known before a call arms the stack dump.
ARM_AFTER = 8
#: Recent steady intervals whose median sets the dump's delay.
RECENT = 16
#: An interval is due two medians after it began, and never sooner than this.
MIN_DUMP_S = 1.0
#: How often the watch thread looks at the open interval.
TICK_S = 0.1
#: How long past the due time the C watchdog waits for the watch.
GRACE_S = 1.0
#: Stalls a process keeps, and the bytes of stacks each keeps.
MAX_STALLS = 8
STACK_BYTES = 8192
#: Slowest steady intervals kept with their counters.
SLOWEST = 4
#: Steady dispatch times the median is taken over (the latest ones).
DISPATCHES = 4096

WORKER_STEP_CALLS = "covalent_tpu_worker_step_calls_total"
WORKER_STEP_SECONDS = "covalent_tpu_worker_step_seconds_total"
WORKER_STEP_GC_SECONDS = "covalent_tpu_worker_step_gc_seconds_total"
WORKER_STEP_RUNQUEUE_SECONDS = (
    "covalent_tpu_worker_step_runqueue_seconds_total")
WORKER_STEP_HOST_EVENTS = "covalent_tpu_worker_step_host_events_total"
WORKER_STEP_DISPATCH_SECONDS = "covalent_tpu_worker_step_dispatch_seconds"
WORKER_HEARTBEAT_LATE_SECONDS = "covalent_tpu_worker_heartbeat_late_seconds"

_lock = threading.Lock()


class _GcClock:
    """Seconds of garbage collection by generation, and pauses, since the
    process first called a step (``gc.callbacks`` run with the GIL held,
    one collection at a time)."""

    def __init__(self) -> None:
        self.seconds = [0.0, 0.0, 0.0]
        self.pauses = 0
        self._t0 = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
            return
        generation = min(max(int(info.get("generation", 2)), 0), 2)
        self.seconds[generation] += time.perf_counter() - self._t0
        self.pauses += 1


_gc = _GcClock()


class _SchedStat(threading.local):
    """The calling thread's ``/proc/thread-self/schedstat``, kept open: its
    second field is the nanoseconds the thread waited on a run queue."""

    fd: int | None = None

    def wait_ns(self) -> int | None:
        if self.fd is None:
            try:
                self.fd = os.open("/proc/thread-self/schedstat", os.O_RDONLY)
            except OSError:
                self.fd = -1
        if self.fd < 0:
            return None
        try:
            return int(os.pread(self.fd, 128, 0).split()[1])
        except (OSError, ValueError, IndexError):
            return None


_sched = _SchedStat()


def _profiling() -> bool:
    """Whether a profiler session is open (jax is never imported to ask)."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    enabled = getattr(getattr(profiler, "TraceAnnotation", None),
                      "is_enabled", None)
    return bool(enabled()) if enabled is not None else False


def _counters() -> tuple:
    """What the host has done so far, as one tuple: GC seconds by
    generation and pauses, run-queue nanoseconds (None where not kept),
    major and minor faults, involuntary switches, jit's events, whether a
    profiler session is open."""
    faults = (0, 0, 0)
    who = getattr(resource, "RUSAGE_THREAD", None)
    if who is not None:
        usage = resource.getrusage(who)
        faults = (usage.ru_majflt, usage.ru_minflt, usage.ru_nivcsw)
    seconds = _gc.seconds
    return (seconds[0], seconds[1], seconds[2], _gc.pauses,
            _sched.wait_ns(), *faults, jitstats.activity(), _profiling())


class _Interval:
    """One call and the time until the next: the call's stamps and parts,
    the counters at its start, the worst heartbeat lateness meanwhile, when
    it is due to end (``perf_counter``; None where it is not watched), and
    where its dump began and ended in the stacks file."""

    __slots__ = ("wall_ns", "t0", "counters", "dispatch_s", "stats_s",
                 "late_s", "first", "due", "armed_at", "dumped_to",
                 "previous")

    def __init__(self, first: bool) -> None:
        self.wall_ns = time.time_ns()
        self.t0 = time.perf_counter()
        self.counters = _counters()
        self.dispatch_s = self.stats_s = 0.0
        self.late_s: float | None = None
        self.first = first
        self.due: float | None = None
        self.armed_at: int | None = None
        self.dumped_to = 0
        self.previous: _Interval | None = None


class _Record:
    """The process's account: the open interval, the sums over steady
    ones, the recent ones, the slowest, the stalls."""

    def __init__(self) -> None:
        self.open: _Interval | None = None
        self.calls = 0
        self.steady = 0
        self.sums = {"interval": 0.0, "dispatch": 0.0, "stats": 0.0}
        self.gc_seconds = [0.0, 0.0, 0.0]
        self.runqueue_s: float | None = None
        self.events = {"gc_pause": 0, "major_fault": 0, "minor_fault": 0,
                       "involuntary_switch": 0}
        self.late_s: float | None = None
        self.recent: deque = deque(maxlen=RECENT)
        self.dispatches: deque = deque(maxlen=DISPATCHES)
        self.dispatch_max = 0.0
        self.slowest: list = []
        self.stalls: list = []
        #: Where each maximum was seen: the interval's ``wall_ns``.
        self.worst_at_ns: dict = {}
        #: The file stall dumps go to, once the worker's heartbeat named it.
        self.stacks_file = None
        #: The due time the C watchdog is armed for, if it is.
        self.backstop: float | None = None


_record = _Record()


def _mark(interval: _Interval) -> None:
    """Note where what the interval's dump wrote, if one did, ends: what is
    written after the next call began is no part of it."""
    if interval.armed_at is not None:
        interval.dumped_to = os.fstat(_record.stacks_file.fileno()).st_size


def _stacks(interval: _Interval) -> str | None:
    """What the interval's dump wrote, if it fired."""
    if interval.armed_at is None or interval.dumped_to <= interval.armed_at:
        return None
    data = os.pread(_record.stacks_file.fileno(),
                    min(interval.dumped_to - interval.armed_at, STACK_BYTES),
                    interval.armed_at)
    return data.decode("utf-8", "replace")


def _close(interval: _Interval, t1: float, now: tuple) -> None:
    """Account one finished interval (the lock held)."""
    before = interval.counters
    stacks = _stacks(interval)
    steady = (not interval.first and now[8] == before[8]
              and now[9] == before[9])
    entry = {
        "wall_ns": interval.wall_ns,
        "interval_s": t1 - interval.t0,
        "dispatch_s": interval.dispatch_s,
        "stats_s": interval.stats_s,
        "gc_s": {str(g): now[g] - before[g] for g in range(3)},
        "gc_pauses": now[3] - before[3],
        "major_faults": now[5] - before[5],
        "minor_faults": now[6] - before[6],
        "involuntary_switches": now[7] - before[7],
        "steady": steady,
    }
    if now[4] is not None and before[4] is not None:
        entry["runqueue_s"] = (now[4] - before[4]) / 1e9
    if interval.late_s is not None:
        entry["beat_late_s"] = interval.late_s
    if stacks is not None and len(_record.stalls) < MAX_STALLS:
        _record.stalls.append(dict(entry, stacks=stacks))
    if not steady:
        return
    rec = _record
    rec.steady += 1
    rec.sums["interval"] += entry["interval_s"]
    rec.sums["dispatch"] += entry["dispatch_s"]
    rec.sums["stats"] += entry["stats_s"]
    for g in range(3):
        rec.gc_seconds[g] += entry["gc_s"][str(g)]
    if "runqueue_s" in entry:
        rec.runqueue_s = (rec.runqueue_s or 0.0) + entry["runqueue_s"]
    for key, field in (("gc_pause", "gc_pauses"),
                       ("major_fault", "major_faults"),
                       ("minor_fault", "minor_faults"),
                       ("involuntary_switch", "involuntary_switches")):
        rec.events[key] += entry[field]
    if interval.late_s is not None and interval.late_s > (rec.late_s or 0.0):
        rec.late_s = interval.late_s
        rec.worst_at_ns["heartbeat_late"] = interval.wall_ns
    rec.recent.append(entry["interval_s"])
    rec.dispatches.append(entry["dispatch_s"])
    if entry["dispatch_s"] >= rec.dispatch_max:
        rec.dispatch_max = entry["dispatch_s"]
        rec.worst_at_ns["dispatch"] = interval.wall_ns
    item = (entry["interval_s"], rec.steady, entry)
    if len(rec.slowest) < SLOWEST:
        heapq.heappush(rec.slowest, item)
    else:
        heapq.heappushpop(rec.slowest, item)


def _begin() -> _Interval:
    """Open this call's interval: the stamps and counters at the boundary,
    and where the last interval's dump ends.  What can wait for the
    dispatch waits (:func:`_settle`): the device is idle until it is
    done."""
    with _lock:
        if _record.calls == 0 and _gc not in gc.callbacks:
            gc.callbacks.append(_gc)
        interval = _Interval(first=_record.calls == 0)
        interval.previous = _record.open
        if interval.previous is not None:
            _mark(interval.previous)
        _record.open = interval
        _record.calls += 1
        return interval


def _settle(interval: _Interval) -> None:
    """After the dispatch: account the interval that ended at this call's
    start, and where the heartbeat named a file for dumps and enough steady
    intervals are known give this one its due time, for the watch."""
    with _lock:
        previous, interval.previous = interval.previous, None
        if previous is not None:
            _close(previous, interval.t0, interval.counters)
        if (_record.open is not interval or _record.stacks_file is None
                or _record.steady < ARM_AFTER
                or len(_record.stalls) >= MAX_STALLS):
            return
        interval.armed_at = os.fstat(_record.stacks_file.fileno()).st_size
        interval.due = interval.t0 + max(
            MIN_DUMP_S, 2.0 * statistics.median(_record.recent))
        _watch.ensure()


def _disarm() -> None:
    """Cancel the C watchdog, where it is armed (the lock held)."""
    if _record.backstop is not None:
        faulthandler.cancel_dump_traceback_later()
        _record.backstop = None


def _look() -> None:
    """One look of the watch at the open interval (the lock held, and the
    GIL: no thread's frames move while it dumps them)."""
    interval = _record.open
    due = None if interval is None else interval.due
    if due is None:
        _disarm()
        return
    now = time.perf_counter()
    if now < due:
        if _record.backstop != due:
            _disarm()
            faulthandler.dump_traceback_later(
                due - now + GRACE_S, file=_record.stacks_file.fileno())
            _record.backstop = due
        return
    _disarm()
    faulthandler.dump_traceback(file=_record.stacks_file.fileno(),
                                all_threads=True)
    interval.due = None


class _Watch:
    """The watch thread, started with the first due time in a process (a
    forked child starts its own), and the C watchdog cancelled before the
    interpreter exits or forks: neither may find it armed."""

    def __init__(self) -> None:
        self.pid: int | None = None

    def ensure(self) -> None:
        if self.pid == os.getpid():
            return
        self.pid = os.getpid()
        threading.Thread(target=self._run, name="covalent-tpu-step-watch",
                         daemon=True).start()

    def _run(self) -> None:
        pid = os.getpid()
        while True:
            time.sleep(TICK_S)
            if self.pid != pid:  # the interpreter exits
                return
            with _lock:
                try:
                    _look()
                except (OSError, ValueError):  # the stacks file went away
                    _record.open = None


_watch = _Watch()


def _quiet() -> None:
    with _lock:
        _disarm()


def _exit() -> None:
    _watch.pid = None
    _quiet()


def _child() -> None:
    """A forked child records its own steps, on its own thread's files,
    under a lock no other thread of the parent held when it forked."""
    global _record, _lock
    _lock = threading.Lock()
    _record = _Record()
    _sched.fd = None


atexit.register(_exit)
os.register_at_fork(before=_quiet, after_in_child=_child)


class _Part:
    """``with``: a ``TraceAnnotation`` of the part's name; its seconds
    added to the call's record on exit, where the call has one."""

    __slots__ = ("_interval", "_field", "_annotation", "_t0")

    def __init__(self, interval: _Interval | None, field: str,
                 name: str) -> None:
        self._interval, self._field = interval, field
        self._annotation = _annotation(name)

    def __enter__(self) -> "_Part":
        if self._annotation is not None:
            self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        seconds = time.perf_counter() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        if self._interval is not None:
            setattr(self._interval, self._field, seconds)
        return False


class _Step:
    """``with step() as call:`` around one call to a jitted step; the
    annotation ``train.step`` around it, ``call.dispatch()`` and
    ``call.stats()`` its two parts.  Where the record's own work raises,
    the call goes unrecorded and the step runs on."""

    __slots__ = ("_interval", "_annotation")

    def __enter__(self) -> "_Step":
        # The annotation first, so that on the host plane ``train.step``
        # holds the record's own work and starts with the wall stamp.
        self._annotation = _annotation("train.step")
        if self._annotation is not None:
            self._annotation.__enter__()
        try:
            self._interval = _begin()
        except Exception:  # noqa: BLE001 - observability never fails the step
            self._interval = None
        return self

    def __exit__(self, *exc) -> bool:
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        if self._interval is not None:
            try:
                _settle(self._interval)
            except Exception:  # noqa: BLE001 - nor does its accounting
                pass
        return False

    def dispatch(self) -> _Part:
        return _Part(self._interval, "dispatch_s", "train.step.dispatch")

    def stats(self) -> _Part:
        return _Part(self._interval, "stats_s", "train.step.stats")


def step() -> _Step:
    """A context manager around one call to a jitted train step."""
    return _Step()


def beat(late_s: float | None = None, stacks_path: str | None = None) -> dict:
    """The worker's heartbeat thread, once a beat: ``late_s`` is how far
    past its due time the last beat finished, charged to the interval open
    now; ``stacks_path`` the file stall dumps go to, opened the first time
    it is named (no dump is armed before, nor where it cannot be opened).
    Returns what the beat carries: ``steady_steps``, ``step_age_s`` (since
    the last call began) and ``late_max_s`` (the worst lateness in a steady
    interval so far); ``{}`` before the first call."""
    now = time.perf_counter()
    with _lock:
        if stacks_path and _record.stacks_file is None:
            try:
                _record.stacks_file = open(stacks_path, "ab+")  # noqa: SIM115
            except OSError:
                pass  # no dumps where the place cannot be written
        interval = _record.open
        if interval is None:
            return {}
        if late_s is not None:
            interval.late_s = max(interval.late_s or 0.0, float(late_s))
        out = {"steady_steps": _record.steady,
               "step_age_s": round(now - interval.t0, 6)}
        if _record.late_s is not None:
            out["late_max_s"] = round(_record.late_s, 6)
        return out


def totals() -> dict:
    """This process's record for the result's trailer: call counts, sums
    over steady intervals, the dispatch median, the slowest intervals, the
    stalls with their stacks, and the ``wall_ns`` of the interval each
    maximum came from (``worst_at_ns``); ``{}`` where no step was ever
    called.  The
    open interval is no longer watched: it never closes."""
    with _lock:
        rec = _record
        if rec.calls == 0:
            return {}
        if rec.open is not None:
            rec.open.due = rec.open.armed_at = None
        _disarm()
        out = {
            "calls": rec.calls,
            "steady": rec.steady,
            "seconds": dict(rec.sums),
            "gc_seconds": {str(g): s for g, s in enumerate(rec.gc_seconds)},
            "events": dict(rec.events),
            "slowest": [e for _, _, e in sorted(rec.slowest, reverse=True)],
            "stalls": list(rec.stalls),
            "worst_at_ns": dict(rec.worst_at_ns),
        }
        if rec.steady:
            out["dispatch_s"] = {"p50": statistics.median(rec.dispatches),
                                 "max": rec.dispatch_max}
        if rec.runqueue_s is not None:
            out["seconds"]["runqueue"] = rec.runqueue_s
        if rec.late_s is not None:
            out["heartbeat_late_s"] = {"max": rec.late_s}
        return out


def absorb_worker(worker_totals, trace: dict | None = None,
                  registry: Registry = REGISTRY) -> None:
    """Add one worker's final :func:`totals` into this process's
    ``covalent_tpu_worker_step_*`` series (the sums over steady intervals
    as counters, the dispatch median and the worst beat lateness as gauges
    of the worker that reported last), and emit each stall as a
    ``worker.stall`` event and the rest as one ``worker.steps`` event,
    under ``trace`` (``trace_id``, ``parent_id``).  Series are made only
    where a steady interval was seen.  Never raises: the record crossed a
    process boundary."""
    try:
        calls = int(worker_totals["calls"])
        steady = int(worker_totals["steady"])
        stalls = [s for s in worker_totals.get("stalls") or ()
                  if isinstance(s, dict)]
        seconds = {str(k): float(v)
                   for k, v in worker_totals["seconds"].items()}
        gc_seconds = {str(k): float(v)
                      for k, v in worker_totals["gc_seconds"].items()}
        events = {str(k): float(v)
                  for k, v in worker_totals["events"].items()}
        dispatch = {str(k): float(v) for k, v in
                    (worker_totals.get("dispatch_s") or {}).items()}
        late = {str(k): float(v) for k, v in
                (worker_totals.get("heartbeat_late_s") or {}).items()}
    except (TypeError, KeyError, ValueError, AttributeError):
        return
    if calls <= 0:
        return
    ids = {k: trace[k] for k in ("trace_id", "parent_id")
           if isinstance(trace, dict) and trace.get(k)}
    counted = registry.counter(
        WORKER_STEP_CALLS, "Train step calls workers made (all), those "
        "whose interval jit left alone (steady), and the intervals a stack "
        "dump fired in (stall), as workers reported them",
        label_names=("kind",))
    for kind, value in (("all", calls), ("steady", steady),
                        ("stall", len(stalls))):
        counted.labels(kind=kind).inc(value)
    if steady > 0:
        part = registry.counter(
            WORKER_STEP_SECONDS, "Seconds of steady train step intervals "
            "(interval), of their jitted calls (dispatch) and of reading "
            "the model's counts (stats), as workers reported them",
            label_names=("part",))
        for name in ("interval", "dispatch", "stats"):
            part.labels(part=name).inc(seconds.get(name, 0.0))
        collected = registry.counter(
            WORKER_STEP_GC_SECONDS, "Seconds of garbage collection inside "
            "steady train step intervals, by generation",
            label_names=("generation",))
        for generation, value in gc_seconds.items():
            collected.labels(generation=generation).inc(value)
        if "runqueue" in seconds:
            registry.counter(
                WORKER_STEP_RUNQUEUE_SECONDS, "Seconds the thread that "
                "called the train step waited on a run queue inside steady "
                "intervals", label_names=("thread",),
            ).labels(thread="step").inc(seconds["runqueue"])
        happened = registry.counter(
            WORKER_STEP_HOST_EVENTS, "GC pauses, major and minor page "
            "faults and involuntary context switches inside steady train "
            "step intervals", label_names=("kind",))
        for kind, value in events.items():
            happened.labels(kind=kind).inc(value)
        timed = registry.gauge(
            WORKER_STEP_DISPATCH_SECONDS, "The jitted train step call's "
            "seconds over steady calls (p50, max), of the worker that "
            "reported last", label_names=("stat",))
        for stat, value in dispatch.items():
            timed.labels(stat=stat).set(value)
        if late:
            beats = registry.gauge(
                WORKER_HEARTBEAT_LATE_SECONDS, "How far past its due time a "
                "heartbeat finished, the worst inside steady train step "
                "intervals, of the worker that reported last",
                label_names=("stat",))
            for stat, value in late.items():
                beats.labels(stat=stat).set(value)
    summary = {k: v for k, v in worker_totals.items() if k != "stalls"}
    _events.emit("worker.steps", **ids, **summary)
    for stall in stalls:
        _events.emit("worker.stall", **ids, **stall)
