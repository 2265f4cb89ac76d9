"""Observability subsystem tests: registry semantics, Prometheus exposition,
span nesting/ids, JSONL event sink round-trip, and the integration contract —
a full ``TPUExecutor.run()`` over the local transport leaves the expected
ordered span set with consistent trace/parent ids (ISSUE 1 acceptance)."""

from __future__ import annotations

import json
import time

import pytest

from covalent_tpu_plugin.obs import dump_metrics
from covalent_tpu_plugin.obs import events as obs_events
from covalent_tpu_plugin.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    Registry,
)
from covalent_tpu_plugin.obs.trace import SPAN_HISTOGRAM, Span, current_span, span

from .helpers import make_local_executor


@pytest.fixture()
def events_file(tmp_path):
    """Point the process-wide event sink at a fresh JSONL file.

    Teardown is reset(), not configure(None): a process-wide
    COVALENT_TPU_EVENTS_PATH (CI's telemetry artifact) must resume
    collecting for the test files that run after this one.
    """
    path = tmp_path / "events.jsonl"
    obs_events.configure(str(path))
    yield path
    obs_events.reset()


def read_events(path) -> list[dict]:
    if not path.exists():
        return []
    return [json.loads(line) for line in path.read_text().splitlines()]


# --------------------------------------------------------------------- #
# Metrics registry semantics
# --------------------------------------------------------------------- #


def test_counter_semantics():
    reg = Registry()
    c = reg.counter("requests_total", "total requests")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError, match="only go up"):
        c.inc(-1)


def test_counter_labels_are_separate_series():
    reg = Registry()
    c = reg.counter("tasks_total", "", label_names=("outcome",))
    c.labels(outcome="ok").inc()
    c.labels(outcome="ok").inc()
    c.labels(outcome="err").inc()
    assert c.labels(outcome="ok").value == 2
    assert c.labels(outcome="err").value == 1
    with pytest.raises(ValueError, match="expected labels"):
        c.labels(wrong="x")
    with pytest.raises(ValueError, match="use .labels"):
        c.inc()


def test_gauge_semantics():
    reg = Registry()
    g = reg.gauge("active", "")
    g.set(5)
    g.inc()
    g.dec(2)
    assert g.value == 4


def test_histogram_buckets_and_quantiles():
    reg = Registry()
    h = reg.histogram("latency_seconds", "", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0):
        h.observe(v)
    assert h.count == 4
    assert h.sum == pytest.approx(6.05)
    child = h._default_child()
    # cumulative le counts: 0.1 -> 1, 1.0 -> 3, 10.0 -> 4, +Inf -> 4
    assert child.cumulative() == [1, 3, 4, 4]
    assert h.quantile(0.5) == 1.0  # upper-bound estimate of the median
    assert h.quantile(1.0) == 10.0


def test_registry_get_or_create_returns_same_metric():
    reg = Registry()
    a = reg.counter("x_total", "")
    b = reg.counter("x_total", "")
    assert a is b
    with pytest.raises(ValueError, match="different type"):
        reg.gauge("x_total", "")


def test_histogram_bucket_mismatch_rejected():
    reg = Registry()
    a = reg.histogram("h_seconds", "", buckets=(0.1, 1.0))
    assert reg.histogram("h_seconds", "", buckets=(1.0, 0.1)) is a  # order-free
    with pytest.raises(ValueError, match="different buckets"):
        reg.histogram("h_seconds", "", buckets=(0.5, 2.0))


def test_snapshot_shape():
    reg = Registry()
    reg.counter("c_total", "help c").inc(3)
    reg.histogram("h_seconds", "", buckets=(1.0,)).observe(0.5)
    snap = reg.snapshot()
    assert snap["metrics"]["c_total"]["kind"] == "counter"
    assert snap["metrics"]["c_total"]["series"][0]["value"] == 3
    hist = snap["metrics"]["h_seconds"]["series"][0]
    assert hist["count"] == 1
    assert hist["buckets"]["1"] == 1
    assert hist["buckets"]["+Inf"] == 1
    json.dumps(snap)  # JSON-serializable end to end


# --------------------------------------------------------------------- #
# Prometheus text exposition
# --------------------------------------------------------------------- #


def test_prometheus_text_counter_and_gauge():
    reg = Registry()
    reg.counter("jobs_total", "jobs", label_names=("state",)).labels(
        state="done"
    ).inc(2)
    reg.gauge("pool_size", "live transports").set(3)
    text = reg.prometheus_text()
    assert "# HELP jobs_total jobs" in text
    assert "# TYPE jobs_total counter" in text
    assert 'jobs_total{state="done"} 2' in text
    assert "# TYPE pool_size gauge" in text
    assert "pool_size 3" in text


def test_prometheus_text_histogram_format():
    reg = Registry()
    h = reg.histogram("rt_seconds", "round trips", buckets=(0.5, 2.0))
    h.observe(0.1)
    h.observe(1.0)
    h.observe(100.0)
    text = reg.prometheus_text()
    assert 'rt_seconds_bucket{le="0.5"} 1' in text
    assert 'rt_seconds_bucket{le="2"} 2' in text
    assert 'rt_seconds_bucket{le="+Inf"} 3' in text
    assert "rt_seconds_sum 101.1" in text
    assert "rt_seconds_count 3" in text


def test_prometheus_label_values_escaped():
    reg = Registry()
    reg.counter("e_total", "", label_names=("msg",)).labels(
        msg='bad "quote"\nline'
    ).inc()
    text = reg.prometheus_text()
    assert 'msg="bad \\"quote\\"\\nline"' in text


def test_dump_metrics_both_formats(tmp_path):
    reg = Registry()
    reg.counter("d_total", "").inc()
    json_path = tmp_path / "m.json"
    prom_path = tmp_path / "m.prom"
    dump_metrics(str(json_path), reg)
    dump_metrics(str(prom_path), reg)
    assert json.loads(json_path.read_text())["metrics"]["d_total"]
    assert "# TYPE d_total counter" in prom_path.read_text()


# --------------------------------------------------------------------- #
# Spans: nesting, ids, status, stage accounting
# --------------------------------------------------------------------- #


def test_span_nesting_and_parent_ids(events_file):
    with span("outer") as outer:
        assert current_span() is outer
        with span("middle") as middle:
            with span("inner.leaf") as inner:
                assert inner.trace_id == outer.trace_id
                assert inner.parent_id == middle.span_id
        assert middle.parent_id == outer.span_id
    assert current_span() is None
    assert outer.parent_id is None
    events = [e for e in read_events(events_file) if e["type"] == "span"]
    # Children end before parents: leaf-first order in the stream.
    assert [e["name"] for e in events] == ["inner.leaf", "middle", "outer"]
    assert len({e["trace_id"] for e in events}) == 1


def test_span_error_status_propagates(events_file):
    with pytest.raises(RuntimeError):
        with span("boom"):
            raise RuntimeError("bad")
    (event,) = [e for e in read_events(events_file) if e["type"] == "span"]
    assert event["status"] == "ERROR"
    assert "bad" in event["attributes"]["error"]


def test_span_stage_durations_accumulate():
    with Span("root", emit=False) as root:
        with Span("root.step", emit=False):
            time.sleep(0.01)
        with Span("root.step", emit=False):
            time.sleep(0.01)
        with Span("root.execute", emit=False):
            time.sleep(0.01)
    # Same leaf name accumulates; overhead excludes the execute stage.
    assert root.stage_durations["step"] >= 0.02
    assert root.overhead() == pytest.approx(
        root.stage_durations["step"], rel=0.01
    )
    summary = root.summary()
    assert set(summary) == {"step", "execute", "total", "overhead"}


def test_span_durations_land_in_histogram():
    from covalent_tpu_plugin.obs.metrics import REGISTRY

    with span("obs-test-unique-span"):
        pass
    hist = REGISTRY.get(SPAN_HISTOGRAM)
    child = hist.labels(span="obs-test-unique-span")
    assert child.count >= 1


# --------------------------------------------------------------------- #
# Event sink round-trip
# --------------------------------------------------------------------- #


def test_event_sink_roundtrip(events_file):
    obs_events.emit("custom.event", key="value", n=3)
    (event,) = read_events(events_file)
    assert event["type"] == "custom.event"
    assert event["key"] == "value"
    assert event["n"] == 3
    assert event["ts"] > 0 and event["pid"] > 0


def test_event_sink_disabled_is_noop(tmp_path):
    # The no-op contract is "disabled AND unobserved": once any executor
    # has wired the flight recorder's process-wide listener, events are
    # observed and must be built even with no JSONL path configured.
    obs_events.configure(None)
    listeners = obs_events._listeners[:]
    obs_events._listeners[:] = []
    try:
        assert obs_events.emit("ignored") is None
    finally:
        obs_events._listeners[:] = listeners
        obs_events.reset()


def test_event_sink_serializes_unserializable_payloads(events_file):
    obs_events.emit("weird", obj=object())
    (event,) = read_events(events_file)
    assert event["type"] == "weird"  # repr fallback, never a crash


def test_terminal_events_survive_sigkill(tmp_path):
    """Terminal ``task.state`` and ``slo.burn`` events fsync inline: a
    process SIGKILLed the instant after the emit still leaves them on
    disk (the whole point of a crash record)."""
    import subprocess
    import sys as sys_mod

    path = tmp_path / "events.jsonl"
    code = (
        "import os, signal\n"
        "from covalent_tpu_plugin.obs import events\n"
        f"events.configure({str(path)!r})\n"
        "events.emit('task.state', operation_id='op-1', state='starting')\n"
        "events.emit('task.state', operation_id='op-1', state='failed')\n"
        "events.emit('slo.burn', slo='serve_ttft', burn=14.4)\n"
        "os.kill(os.getpid(), signal.SIGKILL)\n"
    )
    proc = subprocess.run(
        [sys_mod.executable, "-c", code], timeout=60,
        cwd="/root/repo", capture_output=True,
    )
    assert proc.returncode == -9  # died by SIGKILL, no cleanup ran
    types = [e["type"] for e in read_events(path)]
    assert "slo.burn" in types
    states = [
        e["state"] for e in read_events(path) if e["type"] == "task.state"
    ]
    assert "failed" in states


def test_event_listener_sees_events_without_a_path():
    obs_events.configure(None)
    seen: list[dict] = []
    obs_events.add_listener(seen.append)
    try:
        obs_events.emit("listener.test", x=1)
    finally:
        obs_events.remove_listener(seen.append)
        obs_events.reset()
    assert seen and seen[0]["type"] == "listener.test"


def test_event_sink_reset_restores_env_path(tmp_path, monkeypatch):
    """reset() after a configure() resumes the env-configured stream."""
    env_path = tmp_path / "env.jsonl"
    monkeypatch.setenv("COVALENT_TPU_EVENTS_PATH", str(env_path))
    obs_events.configure(str(tmp_path / "override.jsonl"))
    obs_events.emit("to.override")
    sink = obs_events.reset()
    try:
        assert sink.path == str(env_path)
        obs_events.emit("to.env")
        assert [e["type"] for e in read_events(env_path)] == ["to.env"]
    finally:
        monkeypatch.delenv("COVALENT_TPU_EVENTS_PATH")
        obs_events.reset()


def test_metrics_env_dump_at_exit(tmp_path):
    """COVALENT_TPU_METRICS dumps a snapshot at interpreter exit."""
    import subprocess
    import sys

    out = tmp_path / "exit_metrics.json"
    code = (
        "from covalent_tpu_plugin.obs.metrics import REGISTRY\n"
        "REGISTRY.counter('exit_probe_total', '').inc(7)\n"
    )
    env = dict(__import__("os").environ)
    env["COVALENT_TPU_METRICS"] = str(out)
    subprocess.run(
        [sys.executable, "-c", code], check=True, env=env,
        cwd="/root/repo", timeout=60,
    )
    snap = json.loads(out.read_text())
    assert snap["metrics"]["exit_probe_total"]["series"][0]["value"] == 7


# --------------------------------------------------------------------- #
# Integration: one full run() over the local transport
# --------------------------------------------------------------------- #

EXPECTED_LIFECYCLE = [
    "executor.validate",
    "executor.connect",
    "executor.preflight",
    "executor.stage",
    "executor.upload",
    "executor.submit",
    "executor.execute",
    "executor.fetch",
    "executor.cleanup",
]


def test_full_run_produces_ordered_span_set(tmp_path, run_async, events_file):
    ex = make_local_executor(tmp_path)
    out = run_async(ex.run(lambda x: x + 1, [1], {},
                           {"dispatch_id": "obs", "node_id": 0}))
    assert out == 2
    events = read_events(events_file)
    spans = [e for e in events if e["type"] == "span"]
    (root,) = [s for s in spans if s["name"] == "executor.run"]
    assert root["attributes"]["outcome"] == "completed"
    children = [s for s in spans if s.get("parent_id") == root["span_id"]]
    # Every lifecycle stage present, all in the root's trace.  The stage
    # span is pipelined (serialization overlaps the connect/pre-flight
    # round trips), so only the strictly-sequential stages keep a fixed
    # completion order.
    # The worker's own five spans come home with the result and hang
    # under the same root (tests/test_worker_trace.py covers them).
    brought_home = [s for s in children if s["name"].startswith("worker.")]
    assert len(brought_home) == 5
    children = [s for s in children if s not in brought_home]
    assert sorted(s["name"] for s in children) == sorted(EXPECTED_LIFECYCLE)
    sequential = [
        s["name"] for s in children if s["name"] != "executor.stage"
    ]
    assert sequential == [
        n for n in EXPECTED_LIFECYCLE if n != "executor.stage"
    ]
    assert all(s["trace_id"] == root["trace_id"] for s in children)
    assert all(s["status"] == "OK" for s in children)
    # Task-state transitions bracket the trace.
    states = [e["state"] for e in events if e["type"] == "task.state"]
    assert states == ["starting", "submitted", "completed"]
    # The worker harness joined the same JSONL stream (shared fs).
    # Heartbeats interleave on their own cadence (covered in
    # test_fleetobs); the lifecycle pair must bracket them.
    worker = [e for e in events if e["type"].startswith("worker.")]
    assert [
        e["type"] for e in worker if e["type"] != "worker.heartbeat"
    ] == ["worker.task_started", "worker.task_finished"]
    assert all(e["operation_id"] == "obs_0" for e in worker)
    # Trace propagation: every worker-side record joined the dispatch
    # trace stamped into the task spec.
    assert all(e["trace_id"] == root["trace_id"] for e in worker)
    # last_timings kept its pre-obs contract, fed by the same spans.
    assert ex.last_timings["overhead"] == pytest.approx(
        sum(s["duration_s"] for s in children if s["name"] != "executor.execute"),
        rel=0.05,
    )


def test_failed_run_still_accounts(tmp_path, run_async, events_file):
    """Error paths populate last_timings, the outcome counter, and a
    terminal failure event (ISSUE 1 satellite)."""
    from covalent_tpu_plugin.obs.metrics import REGISTRY

    # Defined in-test so cloudpickle serializes it by value — the harness
    # subprocess cannot import the tests package.
    def exploding_electron():
        raise ValueError("electron exploded")

    ex = make_local_executor(tmp_path)
    before = REGISTRY.counter(
        "covalent_tpu_tasks_total", "", ("outcome",)
    ).labels(outcome="remote_exception").value
    with pytest.raises(ValueError, match="electron exploded"):
        run_async(ex.run(exploding_electron, [], {},
                         {"dispatch_id": "obsfail", "node_id": 0}))
    assert "overhead" in ex.last_timings and ex.last_timings["overhead"] > 0
    after = REGISTRY.counter(
        "covalent_tpu_tasks_total", "", ("outcome",)
    ).labels(outcome="remote_exception").value
    assert after == before + 1
    events = read_events(events_file)
    (root,) = [e for e in events if e["type"] == "span"
               and e["name"] == "executor.run"]
    assert root["status"] == "ERROR"
    terminal = [e for e in events if e["type"] == "task.state"][-1]
    assert terminal["state"] == "remote_exception"
    assert terminal["overhead_s"] > 0


def test_workflow_nodes_emit_events(tmp_path, events_file):
    """Dispatch + node state transitions ride the same stream."""
    from covalent_tpu_plugin.workflow import electron, lattice
    from covalent_tpu_plugin.workflow.runner import dispatch_sync

    @electron
    def add(a, b):
        return a + b

    @lattice
    def flow(a, b):
        return add(add(a, b), b)

    result = dispatch_sync(flow)(1, 2)
    assert result.status.value == "COMPLETED"
    assert result.result == 5
    events = read_events(events_file)
    node_states = [e["state"] for e in events if e["type"] == "node.state"]
    assert node_states.count("running") == 2
    assert node_states.count("completed") == 2
    dispatch_states = [e["state"] for e in events if e["type"] == "dispatch.state"]
    assert dispatch_states == ["running", "COMPLETED"]
    node_spans = [e for e in events if e["type"] == "span"
                  and e["name"] == "workflow.node"]
    dispatch_spans = [e for e in events if e["type"] == "span"
                      and e["name"] == "workflow.dispatch"]
    assert len(node_spans) == 2 and len(dispatch_spans) == 1
    # One trace per dispatch: nodes parent under the dispatch root.
    assert {s["trace_id"] for s in node_spans} == {
        dispatch_spans[0]["trace_id"]
    }
    assert all(
        s["parent_id"] == dispatch_spans[0]["span_id"] for s in node_spans
    )


def test_pool_metrics_hit_and_miss(tmp_path, run_async, events_file):
    from covalent_tpu_plugin.obs.metrics import REGISTRY
    from covalent_tpu_plugin.transport import LocalTransport, TransportPool

    hits = REGISTRY.counter(
        "covalent_tpu_pool_acquires_total", "", ("result",)
    )
    h0, m0 = hits.labels(result="hit").value, hits.labels(result="miss").value

    async def flow():
        pool = TransportPool()

        async def factory():
            return LocalTransport()

        first = await pool.acquire("k", factory)
        second = await pool.acquire("k", factory)
        assert first is second
        await pool.close_all()

    run_async(flow())
    assert hits.labels(result="miss").value == m0 + 1
    assert hits.labels(result="hit").value == h0 + 1
