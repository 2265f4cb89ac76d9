"""The train step as the host drives it (``obs.stepstats``): a record of
each call, the counters of each interval between calls, the threads'
stacks when an interval stalls, a heartbeat's lateness, and the road home:
the result's trailer, the dispatcher's series and ``worker.stall``."""

from __future__ import annotations

import gc
import json
import os
import sys
import time

import pytest

from covalent_tpu_plugin import TPUExecutor, harness
from covalent_tpu_plugin.obs import events as obs_events
from covalent_tpu_plugin.obs import stepstats
from covalent_tpu_plugin.obs.metrics import Registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def record(monkeypatch):
    """A record of this test's own; no dump left armed after it."""
    fresh = stepstats._Record()
    monkeypatch.setattr(stepstats, "_record", fresh)
    yield fresh
    stepstats.totals()  # stops watching, and the C watchdog
    if fresh.stacks_file is not None:
        fresh.stacks_file.close()


@pytest.fixture()
def events(tmp_path):
    path = tmp_path / "events.jsonl"
    obs_events.configure(str(path))
    yield lambda: [json.loads(line)
                   for line in path.read_text().splitlines()]
    obs_events.reset()


def call(pause_s: float = 0.0) -> None:
    """One call through the record, its dispatch taking ``pause_s``."""
    with stepstats.step() as step:
        with step.dispatch():
            time.sleep(pause_s)


def steady_calls(n: int, pause_s: float = 0.002) -> None:
    """``n`` calls: the first call's interval is not steady, ``n - 2``
    steady ones close after it, and the last call's stays open."""
    for _ in range(n):
        call(pause_s)
        time.sleep(pause_s)


def planted_stall(seconds: float) -> None:
    time.sleep(seconds)


def test_a_collection_between_two_calls_is_that_intervals_gc_pause():
    heap = [[i] for i in range(400_000)]
    steady_calls(3)
    call()
    gc.collect(2)
    call()
    del heap
    totals = stepstats.totals()
    slowest = totals["slowest"][0]
    assert slowest["gc_s"]["2"] > 0.5 * slowest["interval_s"]
    assert slowest["gc_pauses"] >= 1
    assert totals["gc_seconds"]["2"] >= slowest["gc_s"]["2"]
    assert totals["events"]["gc_pause"] >= 1
    # The pause was the host's, not the jitted call's.
    assert slowest["dispatch_s"] < 0.05


def test_a_planted_sleep_leaves_a_stall_whose_stacks_name_it(
        tmp_path, monkeypatch):
    monkeypatch.setattr(stepstats, "MIN_DUMP_S", 0.4)
    stacks = tmp_path / "pid_op.0.stacks"
    stepstats.beat(stacks_path=str(stacks))
    steady_calls(stepstats.ARM_AFTER + 2)
    call()
    planted_stall(1.0)
    call()
    (stall,) = [s for s in stepstats.totals()["stalls"]
                if "planted_stall" in s["stacks"]]
    assert stall["interval_s"] >= 1.0
    assert stall["steady"] is True
    assert 0 < len(stall["stacks"].encode()) <= stepstats.STACK_BYTES
    # The dump went to the file the worker's heartbeat named.
    assert "planted_stall" in stacks.read_text()
    # The stall is wall-stamped on the profiler's clock.
    assert abs(stall["wall_ns"] / 1e9 - time.time()) < 60


def test_the_first_call_and_a_call_that_compiled_are_not_steady():
    import jax
    import jax.numpy as jnp

    from covalent_tpu_plugin.models.train import _TrainStep

    step = _TrainStep(jax.jit(lambda s, b: (s + b, {"loss": jnp.sum(s)})))
    small, big = (jnp.zeros(4), jnp.ones(4)), (jnp.zeros(8), jnp.ones(8))
    for args in (small, small, small, big, big, big):
        step(*args)  # the fourth call compiles anew
    totals = stepstats.totals()
    assert totals["calls"] == 6
    # Five intervals closed: the first call's and the recompiling call's
    # are not steady; the sixth call's never closes.
    assert totals["steady"] == 3
    assert totals["dispatch_s"]["p50"] <= totals["dispatch_s"]["max"]


def test_an_interval_that_never_closes_is_no_stall(tmp_path, monkeypatch):
    monkeypatch.setattr(stepstats, "MIN_DUMP_S", 0.2)
    stepstats.beat(stacks_path=str(tmp_path / "pid_op.0.stacks"))
    steady_calls(stepstats.ARM_AFTER + 2)
    call()
    planted_stall(0.5)  # the dump fires, and no call comes after
    totals = stepstats.totals()
    assert totals["stalls"] == []
    assert totals["steady"] == stepstats.ARM_AFTER + 1
    # The trailer's writer cancelled the watchdog: nothing fires later.
    call()
    assert stepstats.totals()["stalls"] == []


def test_outside_a_worker_that_named_a_stacks_file_nothing_is_dumped(
        monkeypatch):
    armed = []
    monkeypatch.setattr(stepstats, "MIN_DUMP_S", 0.2)
    monkeypatch.setattr(stepstats.faulthandler, "dump_traceback_later",
                        lambda *a, **k: armed.append(a))
    monkeypatch.setattr(stepstats.faulthandler, "dump_traceback",
                        lambda *a, **k: armed.append(a))
    steady_calls(stepstats.ARM_AFTER + 2)
    call()
    planted_stall(0.5)
    call()
    totals = stepstats.totals()
    # The counters are kept; the process-wide watchdog is never touched.
    assert totals["steady"] == stepstats.ARM_AFTER + 2
    assert totals["stalls"] == [] and armed == []


def test_intervals_a_profiler_started_or_stopped_in_are_not_steady(tmp_path):
    import jax

    steady_calls(4)
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        steady_calls(3)
    finally:
        jax.profiler.stop_trace()
    steady_calls(3)
    totals = stepstats.totals()
    # Nine intervals closed: the first call's, the one the session opened
    # in and the one it closed in are not steady.
    assert totals["calls"] == 10
    assert totals["steady"] == 6


def test_a_step_runs_where_the_platform_keeps_no_per_thread_usage(
        monkeypatch):
    import resource

    monkeypatch.delattr(resource, "RUSAGE_THREAD")
    assert stepping(4) == 3 * 4
    totals = stepstats.totals()
    assert totals["calls"] == 4 and totals["steady"] == 2
    assert totals["events"]["major_fault"] == 0


@pytest.mark.parametrize("part", ["_begin", "_settle"])
def test_a_record_that_raises_leaves_the_steps_result_whole(
        monkeypatch, part):
    def broken(*_args):
        raise OSError("the record's own work failed")

    monkeypatch.setattr(stepstats, part, broken)
    assert stepping(3) == 2 * 4
    # No call was accounted (where ``_settle`` failed, each was opened).
    assert stepstats.totals().get("steady", 0) == 0


def test_a_held_back_heartbeat_is_late_inside_the_steady_span(
        tmp_path, monkeypatch):
    steady_calls(3)
    held = []
    payload = harness._heartbeat_payload

    def held_back(*args):
        if len(held) == 2:
            time.sleep(0.3)
        held.append(args)
        return payload(*args)

    monkeypatch.setattr(harness, "_heartbeat_payload", held_back)
    # The heartbeat publishes its metrics path here; restored after.
    monkeypatch.setenv("COVALENT_TPU_WORKER_METRICS_PATH", "")
    pid_file = tmp_path / "pid_op.0"
    stop = harness._start_heartbeat(
        {"heartbeat_s": 0.05, "pid_file": str(pid_file)})
    try:
        deadline = time.monotonic() + 10
        beat = {}
        while time.monotonic() < deadline:
            try:
                beat = json.loads((tmp_path / "pid_op.0.hb").read_text())
            except (OSError, ValueError):
                beat = {}
            if beat.get("late_s", 0) >= 0.25:
                break
            time.sleep(0.02)
    finally:
        stop.set()
    assert beat["late_s"] >= 0.25
    assert beat["steps"]["steady_steps"] == 1
    assert beat["steps"]["step_age_s"] >= 0.25
    assert "cpu_s" not in beat
    call()  # closes the interval the late beat fell in
    assert stepstats.totals()["heartbeat_late_s"]["max"] >= 0.25
    assert stepstats.beat()["late_max_s"] >= 0.25


def test_trailer_to_absorb_worker_to_series_and_stall_events(
        tmp_path, monkeypatch, events):
    monkeypatch.setattr(stepstats, "MIN_DUMP_S", 0.2)
    stepstats.beat(stacks_path=str(tmp_path / "pid_op.0.stacks"))
    steady_calls(stepstats.ARM_AFTER + 2)
    call()
    planted_stall(0.5)
    call()
    call()
    trailer = json.loads(harness._trace_trailer([]))
    registry = Registry()
    stepstats.absorb_worker(
        trailer["steps"], trace={"trace_id": "t" * 32, "parent_id": "e" * 16},
        registry=registry)
    series = registry.snapshot()["metrics"]

    def value(name, **labels):
        return sum(s["value"] for s in series[name]["series"]
                   if all(s["labels"].get(k) == v for k, v in labels.items()))

    assert value(stepstats.WORKER_STEP_CALLS, kind="all") == (
        stepstats.ARM_AFTER + 5)
    assert value(stepstats.WORKER_STEP_CALLS, kind="stall") >= 1
    assert value(stepstats.WORKER_STEP_CALLS, kind="steady") == (
        stepstats.ARM_AFTER + 3)
    assert value(stepstats.WORKER_STEP_SECONDS, part="interval") >= 0.5
    assert 0 < value(stepstats.WORKER_STEP_DISPATCH_SECONDS, stat="p50") < 0.05
    assert value(stepstats.WORKER_STEP_HOST_EVENTS, kind="major_fault") >= 0
    assert stepstats.WORKER_STEP_GC_SECONDS in series
    if os.path.exists("/proc/thread-self/schedstat"):
        assert value(stepstats.WORKER_STEP_RUNQUEUE_SECONDS,
                     thread="step") >= 0
    # No beat came: no lateness series.
    assert stepstats.WORKER_HEARTBEAT_LATE_SECONDS not in series
    (stall,) = [e for e in events() if e["type"] == "worker.stall"
                and "planted_stall" in e["stacks"]]
    assert stall["trace_id"] == "t" * 32 and stall["parent_id"] == "e" * 16
    (summary,) = [e for e in events() if e["type"] == "worker.steps"]
    assert summary["steady"] == stepstats.ARM_AFTER + 3
    assert "stalls" not in summary
    # The slowest dispatch is named by the interval it came from; no beat
    # came, so no lateness has one.
    assert set(summary["worst_at_ns"]) == {"dispatch"}
    assert abs(summary["worst_at_ns"]["dispatch"] / 1e9 - time.time()) < 60
    # A torn or foreign record adds nothing and never raises.
    for bad in (None, "x", {"calls": "many"}, {"calls": 0, "steady": 0,
                                               "seconds": {}, "gc_seconds": {},
                                               "events": {}}):
        other = Registry()
        stepstats.absorb_worker(bad, registry=other)
        assert other.snapshot()["metrics"] == {}


def stepping(n):
    import jax
    import jax.numpy as jnp

    from covalent_tpu_plugin.models.train import _TrainStep

    step = _TrainStep(jax.jit(lambda s, b: (s + b, {"loss": jnp.sum(s)})))
    state = jnp.zeros(4)
    for _ in range(n):
        state, metrics = step(state, jnp.ones(4))
    return float(metrics["loss"])


def plain(n):
    import sys as worker_sys

    return n + 1, "jax" in worker_sys.modules


def test_the_record_comes_home_from_a_stepping_worker_and_not_a_plain_one(
        tmp_path, run_async, events):
    ex = TPUExecutor(
        transport="local", cache_dir=str(tmp_path / "cache"),
        remote_cache=str(tmp_path / "remote"), python_path=sys.executable,
        poll_freq=0.2, use_agent=False, task_env={"PYTHONPATH": REPO})

    async def go():
        try:
            first = await ex.run(stepping, [12], {},
                                 {"dispatch_id": "st", "node_id": 0})
            second = await ex.run(plain, [1], {},
                                  {"dispatch_id": "st", "node_id": 1})
            return first, second
        finally:
            await ex.close()

    loss, (result, had_jax) = run_async(go())
    assert loss == 11 * 4 and result == 2
    assert had_jax is False
    spans = {e["trace_id"]: e for e in events()
             if e["type"] == "span" and e["name"] == "worker.execute"}
    (summary,) = [e for e in events() if e["type"] == "worker.steps"]
    assert summary["calls"] == 12 and summary["steady"] == 10
    # Under the stepping electron's worker.execute; the plain one sent none.
    execute = spans[summary["trace_id"]]
    assert summary["parent_id"] == execute["span_id"]
    assert len(spans) == 2


@pytest.mark.parametrize("beat,says", [
    ({"age_s": 16.6, "steps": {"steady_steps": 41, "step_age_s": 19.2,
                               "late_max_s": 0.004}},
     "its last beat: 41 steady train steps, the step in progress 19.2s "
     "old, worst beat lateness 0.004s"),
    ({"age_s": 16.6, "steps": {"steady_steps": 3, "step_age_s": 0.4}},
     "its last beat: 3 steady train steps, the step in progress 0.4s old, "
     "worst beat lateness ?s"),
    ({"age_s": 16.6, "rss_bytes": 1}, "its last beat names no train step"),
    ({}, "its last beat names no train step"),
])
def test_a_stall_error_quotes_the_last_beats_step(beat, says):
    from covalent_tpu_plugin.tpu import _steps_of_beat

    assert _steps_of_beat(beat) == says


def planted_regex_stall():
    import re

    # The regular expression engine holds the GIL: the heartbeat thread
    # stands still with the main thread, as behind a blocking C++ call.
    return re.match(r"(a+)+$", "a" * 40 + "b")


def stepping_then_stuck(n):
    import jax
    import jax.numpy as jnp

    from covalent_tpu_plugin.models.train import _TrainStep

    step = _TrainStep(jax.jit(lambda s, b: (s + b, {"loss": jnp.sum(s)})))
    state = jnp.zeros(4)
    for _ in range(n):
        state, _ = step(state, jnp.ones(4))
        time.sleep(0.02)
    planted_regex_stall()
    return "never"


def test_a_worker_killed_as_stalled_says_it_was_inside_a_step(
        tmp_path, run_async):
    ex = TPUExecutor(
        transport="local", cache_dir=str(tmp_path / "cache"),
        remote_cache=str(tmp_path / "remote"), python_path=sys.executable,
        poll_freq=0.2, use_agent=False, task_env={"PYTHONPATH": REPO},
        heartbeat_interval=0.25, stall_threshold=3.0, max_task_retries=0,
        run_local_on_dispatch_fail=False, task_timeout=120.0)

    async def go():
        try:
            return await ex.run(stepping_then_stuck, [60], {},
                                {"dispatch_id": "stuck", "node_id": 0})
        finally:
            await ex.close()

    with pytest.raises(RuntimeError) as failed:
        run_async(go())
    message = str(failed.value)
    assert "stalled on" in message
    assert "its last beat: " in message and "steady train steps" in message
    # The watchdog dumped the threads' stacks before the gang was killed.
    assert "planted_regex_stall" in message
