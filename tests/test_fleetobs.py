"""Fleet observability plane (ISSUE 6): distributed trace propagation,
heartbeat liveness + stall detection, the telemetry backhaul side-band, the
ops status endpoint, and the event-stream bounds (rotation, swallow-and-
count worker emission)."""

from __future__ import annotations

import asyncio
import json
import os
import shutil
import signal
import sys
import time
import urllib.request

import pytest

from covalent_tpu_plugin import harness
from covalent_tpu_plugin.obs import events as obs_events
from covalent_tpu_plugin.obs.heartbeat import HeartbeatMonitor, MONITOR
from covalent_tpu_plugin.obs.metrics import REGISTRY
from covalent_tpu_plugin.obs.opsserver import (
    OpsServer,
    register_status_provider,
    unregister_status_provider,
)
from covalent_tpu_plugin.resilience import (
    FaultClass,
    WorkerStalledError,
    classify_error,
)

from .helpers import make_local_executor


@pytest.fixture()
def events_file(tmp_path):
    path = tmp_path / "events.jsonl"
    obs_events.configure(str(path))
    yield path
    obs_events.reset()


def read_events(path) -> list[dict]:
    if not path.exists():
        return []
    return [json.loads(line) for line in path.read_text().splitlines()]


# --------------------------------------------------------------------- #
# Heartbeat monitor: cadence, dedup, stall detection (fake clock)
# --------------------------------------------------------------------- #


def test_monitor_records_and_ages_heartbeats():
    now = [100.0]
    monitor = HeartbeatMonitor(clock=lambda: now[0])
    monitor.watch("op", stall_after=3.0)
    assert monitor.record("op", "w0", {"seq": 1, "step": 5})
    now[0] += 1.0
    view = monitor.last("op")
    assert view["w0"]["age_s"] == pytest.approx(1.0)
    assert view["w0"]["step"] == 5
    # Same seq re-delivered (snapshot re-read): not fresh, clock untouched.
    assert not monitor.record("op", "w0", {"seq": 1, "step": 5})
    assert monitor.last("op")["w0"]["age_s"] == pytest.approx(1.0)


def test_monitor_stall_detection_fake_clock():
    now = [0.0]
    monitor = HeartbeatMonitor(clock=lambda: now[0])
    monitor.watch("op", stall_after=2.0)
    monitor.record("op", "w0", {"seq": 1})
    monitor.record("op", "w1", {"seq": 1})
    now[0] = 1.5
    monitor.record("op", "w1", {"seq": 2})  # w1 keeps beating
    assert monitor.stalled("op") == []
    now[0] = 2.5  # w0 silent for 2.5s, w1 for 1.0s
    stalled = monitor.stalled("op")
    assert [w for w, _ in stalled] == ["w0"]
    assert stalled[0][1] == pytest.approx(2.5)
    # A worker that never beat can never stall; forget clears everything.
    monitor.forget("op")
    assert monitor.stalled("op") == []
    assert monitor.last("op") == {}


def test_monitor_nobeat_worker_stalls_after_launch_slack():
    """A worker wedged BEFORE its first beat (e.g. frozen mid-write) must
    still stall once the launch slack (stall_after + one interval) runs
    out — silence-from-birth is not blindness."""
    now = [0.0]
    monitor = HeartbeatMonitor(clock=lambda: now[0])
    monitor.watch("op", stall_after=2.0, workers=("w0", "w1"),
                  interval=0.5, launch_slack=0.0)
    monitor.record("op", "w1", {"seq": 1})
    now[0] = 2.4  # inside the no-beat deadline (2.5): not yet
    assert [w for w, _ in monitor.stalled("op")] == ["w1"]  # w1 silent 2.4
    monitor.record("op", "w1", {"seq": 2})  # w1 recovers
    now[0] = 2.6  # w0 never beat and the slack is spent
    assert [w for w, _ in monitor.stalled("op")] == ["w0"]
    monitor.forget("op")


def test_monitor_disabled_threshold_never_stalls():
    now = [0.0]
    monitor = HeartbeatMonitor(clock=lambda: now[0])
    monitor.watch("op", stall_after=0.0)
    monitor.record("op", "w0", {"seq": 1})
    now[0] = 1e6
    assert monitor.stalled("op") == []


def test_monitor_jitter_adaptive_stall_threshold():
    """A worker whose beats arrive erratically widens its own stall
    deadline (3 x observed mean gap + K x std, floored at the configured
    stall_after) instead of tripping a false stall; a steady beater keeps
    the configured floor; and a genuinely wedged erratic worker still
    trips once its silence outgrows the learned statistics."""
    now = [0.0]
    monitor = HeartbeatMonitor(clock=lambda: now[0])
    monitor.watch("op", stall_after=2.0)
    # Fewer than ADAPTIVE_MIN_BEATS gaps: the configured floor rules.
    monitor.record("op", "steady", {"seq": 1})
    assert monitor.effective_stall_after("op", "steady") == 2.0
    for seq, gap in enumerate([0.5] * 6, start=2):
        now[0] += gap
        monitor.record("op", "steady", {"seq": seq})
    # Steady cadence (0.5s gaps, ~zero std): 3 x 0.5 < 2.0 -> floor.
    assert monitor.effective_stall_after("op", "steady") == 2.0
    # An erratic-but-alive worker: gaps oscillating around 1.5s with
    # ~1.4s swings learn a deadline well past the configured 2s.
    monitor.record("op", "erratic", {"seq": 1})
    for seq, gap in enumerate([0.1, 2.9, 0.2, 2.8, 0.1, 2.9], start=2):
        now[0] += gap
        monitor.record("op", "erratic", {"seq": seq})
    widened = monitor.effective_stall_after("op", "erratic")
    assert widened > 2.0
    # Silence past the FLOOR but inside the widened deadline: no stall —
    # this exact pattern used to false-positive under fixed thresholds.
    now[0] += 2.5
    assert "erratic" not in [w for w, _ in monitor.stalled("op")]
    # Silence past the widened deadline: the detector still fires — the
    # learned statistics freeze while the silence keeps growing.
    now[0] += widened
    assert "erratic" in [w for w, _ in monitor.stalled("op")]
    monitor.forget("op")


def test_worker_stalled_error_classification():
    fault, reason = classify_error(WorkerStalledError("silent"))
    assert fault is FaultClass.TRANSIENT
    assert reason == "worker_stalled"


# --------------------------------------------------------------------- #
# Event stream bounds: rotation + worker-side swallow-and-count
# --------------------------------------------------------------------- #


def test_event_sink_size_rotation(tmp_path):
    path = tmp_path / "rot.jsonl"
    sink = obs_events.EventSink(str(path), max_bytes=512, backups=2)
    for i in range(64):
        sink.emit("spam", i=i, pad="x" * 64)
    sink.close()
    assert path.exists()
    assert (tmp_path / "rot.jsonl.1").exists()
    assert (tmp_path / "rot.jsonl.2").exists()
    assert not (tmp_path / "rot.jsonl.3").exists()  # bounded generations
    # Live file stays under the cap (+ one line of slack at rotation).
    assert path.stat().st_size < 1024
    # Rotated generations hold valid JSONL.
    for line in (tmp_path / "rot.jsonl.1").read_text().splitlines():
        json.loads(line)


def test_event_sink_rotation_disabled(tmp_path):
    path = tmp_path / "flat.jsonl"
    sink = obs_events.EventSink(str(path), max_bytes=0, backups=2)
    for i in range(32):
        sink.emit("spam", i=i, pad="y" * 64)
    sink.close()
    assert not (tmp_path / "flat.jsonl.1").exists()
    assert len(path.read_text().splitlines()) == 32


def test_worker_event_unwritable_path_never_raises(capsys, monkeypatch):
    """Satellite: `_emit_worker_event` swallows ENOSPC-class failures,
    counts them, and notes the first on stderr."""
    monkeypatch.setattr(harness, "_worker_event_failures", 0)
    spec = {
        "operation_id": "op",
        "events_file": "/nonexistent-dir-xyz/events.jsonl",
    }
    harness._emit_worker_event(spec, "worker.task_started", process_id=0)
    harness._emit_worker_event(spec, "worker.task_finished", process_id=0)
    assert harness._worker_event_failures == 2
    err = capsys.readouterr().err
    assert err.count("worker events unwritable") == 1  # one-line, once


def test_worker_event_carries_trace_and_seq(tmp_path):
    path = tmp_path / "worker.jsonl"
    spec = {
        "operation_id": "op",
        "events_file": str(path),
        "trace": {"trace_id": "t" * 32, "span_id": "s" * 16, "attempt": 2},
    }
    harness._emit_worker_event(spec, "worker.task_started", process_id=0)
    (event,) = [json.loads(line) for line in path.read_text().splitlines()]
    assert event["trace_id"] == "t" * 32
    assert event["parent_id"] == "s" * 16
    assert event["attempt"] == 2
    assert isinstance(event["seq"], int)


# --------------------------------------------------------------------- #
# Ops status endpoint
# --------------------------------------------------------------------- #


def http_get(port: int, path: str) -> tuple[int, bytes]:
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=5
    ) as response:
        return response.status, response.read()


def test_ops_server_routes_and_status_shape():
    server = OpsServer(port=0)
    try:
        REGISTRY.counter("fleetobs_probe_total", "probe").inc(3)
        register_status_provider(
            "test-exec",
            lambda: {"in_flight": {"op_1": {"stage": "executing"}}},
        )
        MONITOR.watch("op_1", stall_after=60.0)
        MONITOR.record("op_1", "w0", {"seq": 9, "step": 7})

        code, body = http_get(server.port, "/metrics")
        assert code == 200
        assert b"fleetobs_probe_total 3" in body

        code, body = http_get(server.port, "/status")
        status = json.loads(body)
        assert status["pid"] == os.getpid()
        assert status["in_flight"]["op_1"]["stage"] == "executing"
        assert status["heartbeats"]["op_1"]["w0"]["step"] == 7
        assert status["providers"]["test-exec"]

        obs_events.emit  # stream may be disabled; feed the ring directly
        server._tail.append({"ts": 1.0, "type": "probe.event"})
        code, body = http_get(server.port, "/events?n=1")
        assert code == 200
        assert json.loads(body.splitlines()[-1])["type"] == "probe.event"

        code, _ = http_get(server.port, "/healthz")
        assert code == 200
    finally:
        unregister_status_provider("test-exec")
        MONITOR.forget("op_1")
        server.close()


def test_ops_server_prunes_dead_providers():
    server = OpsServer(port=0)
    try:
        register_status_provider("gone", lambda: None)
        status = server.status()
        assert "gone" not in status.get("providers", {})
        # Pruned on first read, not just skipped.
        from covalent_tpu_plugin.obs import opsserver as ops_mod

        assert "gone" not in ops_mod._providers
    finally:
        server.close()


def test_executor_registers_status_provider(tmp_path):
    from covalent_tpu_plugin.obs import opsserver as ops_mod

    ex = make_local_executor(tmp_path)
    assert ex._ops_provider_name in ops_mod._providers
    view = ops_mod._providers[ex._ops_provider_name]()
    assert view["transport"] == "local"
    assert "circuit_breakers" in view and "in_flight" in view


# --------------------------------------------------------------------- #
# End-to-end: trace across a retry, live heartbeats, stall recovery
# --------------------------------------------------------------------- #


def test_trace_id_survives_retry_with_attempt_attrs(
    tmp_path, run_async, events_file
):
    """Satellite: worker events carry the dispatcher's trace id across a
    gang retry — fresh attempt, same trace, attempt attr preserved."""
    from covalent_tpu_plugin.transport.chaos import ChaosPlan

    class DropOnceAttemptZeroStarted(ChaosPlan):
        """The probe channel dies only once attempt 0's worker has left a
        record: dropped earlier, the gang's teardown kills that worker
        before it writes anything."""

        def take_fault(self, kind, **detail):
            if not any(e["type"] == "worker.task_started"
                       for e in read_events(events_file)):
                return False
            return super().take_fault(kind, **detail)

    ex = make_local_executor(
        tmp_path,
        max_task_retries=2,
        retry_base_delay=0.01,
        heartbeat_interval=0.1,
        # Kill exactly one status-probe channel mid-poll: attempt 0 dies
        # transiently, attempt 1 completes.
        chaos=DropOnceAttemptZeroStarted(
            drop_match="if test -f", max_faults=1),
    )
    out = run_async(ex.run(lambda x: x + 1, [1], {},
                           {"dispatch_id": "ftrace", "node_id": 0}))
    assert out == 2
    assert ex.last_attempts == 2
    events = read_events(events_file)
    worker = [e for e in events if e["type"].startswith("worker.")
              and e.get("operation_id", "").startswith("ftrace_0")]
    assert worker, "no worker events reached the stream"
    attempts = {e.get("attempt") for e in worker}
    assert attempts == {0, 1}, attempts  # both attempts left records
    # ONE trace follows the electron across the retry...
    assert len({e["trace_id"] for e in worker}) == 1
    # ...and it is the dispatcher's own dispatch trace.
    (task_span,) = [e for e in events if e["type"] == "span"
                    and e["name"] == "executor.task"]
    assert {e["trace_id"] for e in worker} == {task_span["trace_id"]}
    run_spans = [e for e in events if e["type"] == "span"
                 and e["name"] == "executor.run"]
    assert len(run_spans) == 2
    assert {s["trace_id"] for s in run_spans} == {task_span["trace_id"]}
    assert sorted(s["attributes"]["attempt"] for s in run_spans) == [0, 1]


def test_heartbeats_reach_monitor_and_stream(tmp_path, run_async, events_file):
    ex = make_local_executor(tmp_path, heartbeat_interval=0.1)

    def slow(x):
        import time as _time

        _time.sleep(0.6)
        return x * 2

    out = run_async(ex.run(slow, [4], {},
                           {"dispatch_id": "fhb", "node_id": 0}))
    assert out == 8
    beats = [e for e in read_events(events_file)
             if e["type"] == "worker.heartbeat"]
    assert beats, "no heartbeats re-emitted on the dispatcher stream"
    assert all(e["worker"] == "localhost" for e in beats)
    assert all(e["trace_id"] for e in beats)
    assert all("rss_bytes" in e for e in beats)
    # Fresh beats moved the per-worker counter.
    total = REGISTRY.counter(
        "covalent_tpu_worker_heartbeats_total", "", ("worker",)
    ).labels(worker="localhost").value
    assert total >= len(beats)


def test_stalled_worker_classified_and_retried(tmp_path, run_async, events_file):
    """Acceptance: a silenced worker (alive but frozen) is classified
    `worker_stalled` and the gang retried before any hard deadline."""
    flag = tmp_path / "stalled_once"

    def freeze_once(flag_path):
        import os as _os
        import signal as _signal

        if not _os.path.exists(flag_path):
            with open(flag_path, "w") as f:
                f.write("1")
            # Freeze THIS harness process: heartbeat thread stops with it,
            # while kill -0 still reports the pid alive.
            _os.kill(_os.getpid(), _signal.SIGSTOP)
        return "recovered"

    retries = REGISTRY.counter(
        "covalent_tpu_task_retries_total", "", ("reason",)
    )
    before = retries.labels(reason="worker_stalled").value
    ex = make_local_executor(
        tmp_path,
        max_task_retries=1,
        retry_base_delay=0.01,
        heartbeat_interval=0.1,
        stall_threshold=0.8,
        task_timeout=60.0,  # the stall detector must win, not this
    )
    t0 = time.monotonic()
    out = run_async(ex.run(freeze_once, [str(flag)], {},
                           {"dispatch_id": "fstall", "node_id": 0}))
    elapsed = time.monotonic() - t0
    assert out == "recovered"
    assert ex.last_attempts == 2
    assert elapsed < 30.0, "stall detection did not beat the hard timeout"
    assert retries.labels(reason="worker_stalled").value == before + 1
    events = read_events(events_file)
    assert any(e["type"] == "task.stall_escalated" for e in events)
    (failed,) = [e for e in events if e["type"] == "task.failed"]
    assert failed["status"] == "STALLED"
    retry_events = [e for e in events if e["type"] == "task.retry"]
    assert retry_events and retry_events[0]["reason"] == "worker_stalled"


# --------------------------------------------------------------------- #
# Telemetry backhaul over the pool-server channel
# --------------------------------------------------------------------- #


def test_pool_server_watch_flushes_and_survives_channel_death(
    tmp_path, run_async
):
    """Satellite: events buffered on the worker while no channel is
    attached are flushed on the next (re-)watch and deduped by seq."""
    from covalent_tpu_plugin.agent import start_pool_server
    from covalent_tpu_plugin.transport import LocalTransport

    telemetry = tmp_path / "telemetry.jsonl"

    def write_lines(*seqs):
        with open(telemetry, "a", encoding="utf-8") as f:
            for seq in seqs:
                f.write(json.dumps(
                    {"seq": seq, "type": "worker.heartbeat", "step": seq}
                ) + "\n")

    async def flow():
        seen: list[dict] = []
        conn = LocalTransport()
        client = await start_pool_server(
            conn, str(tmp_path / "cache"), sys.executable
        )
        client.on_telemetry = lambda task_id, data: seen.append(data)
        write_lines(1, 2)  # buffered BEFORE any watch: backlog
        await client.watch("t1", str(telemetry))
        for _ in range(100):
            if len(seen) >= 2:
                break
            await asyncio.sleep(0.05)
        assert [d["seq"] for d in seen] == [1, 2]

        write_lines(3)  # live tail
        for _ in range(100):
            if len(seen) >= 3:
                break
            await asyncio.sleep(0.05)
        assert [d["seq"] for d in seen] == [1, 2, 3]

        # Channel death: the file (the buffer) survives the client.
        await client.close()
        write_lines(4)

        # Reconnect: a fresh server re-watches from offset 0 — the full
        # backlog replays and the client-side seq dedup drops 1..3.
        client2 = await start_pool_server(
            conn, str(tmp_path / "cache"), sys.executable
        )
        client2._telemetry_seq["t1"] = max(d["seq"] for d in seen)
        client2.on_telemetry = lambda task_id, data: seen.append(data)
        await client2.watch("t1", str(telemetry))
        for _ in range(100):
            if len(seen) >= 4:
                break
            await asyncio.sleep(0.05)
        await client2.close()
        return seen

    seen = run_async(flow())
    assert [d["seq"] for d in seen] == [1, 2, 3, 4]


def test_agent_launched_run_backhauls_heartbeats(tmp_path, run_async,
                                                 events_file):
    """Full executor path in pool-agent mode: heartbeats ride the channel
    side-band into the monitor and the dispatcher stream."""
    ex = make_local_executor(
        tmp_path, use_agent="pool", heartbeat_interval=0.1, poll_freq=0.1
    )

    def slow(x):
        import time as _time

        _time.sleep(0.5)
        return x + 10

    async def flow():
        try:
            return await ex.run(slow, [5], {},
                                {"dispatch_id": "fbackhaul", "node_id": 0})
        finally:
            await ex.close()  # same loop: pool-server channel lives here

    out = run_async(flow())
    assert out == 15
    beats = [e for e in read_events(events_file)
             if e["type"] == "worker.heartbeat"
             and e.get("operation_id") == "fbackhaul_0"]
    assert beats, "no backhauled heartbeats"
    # Channel-pushed AND probe-read copies dedup to one stream record per
    # worker-side seq.
    seqs = [e["seq"] for e in beats]
    assert len(seqs) == len(set(seqs))


def test_pool_server_auto_unwatches_on_task_exit(tmp_path, run_async):
    """A finished task's watcher is pruned (after a final flush): a
    long-lived server must not stat() dead tasks' files forever."""
    from covalent_tpu_plugin.agent import start_pool_server
    from covalent_tpu_plugin.transport import LocalTransport

    telemetry = tmp_path / "t.jsonl"
    spec = tmp_path / "spec.json"
    result = tmp_path / "r.pkl"
    spec.write_text(json.dumps({
        "operation_id": "t1",
        "function_file": str(tmp_path / "missing.pkl"),  # exits fast (rc 1)
        "result_file": str(result),
    }))

    async def flow():
        seen: list[dict] = []
        conn = LocalTransport()
        client = await start_pool_server(
            conn, str(tmp_path / "cache"), sys.executable
        )
        client.on_telemetry = lambda task_id, data: seen.append(data)
        await client.watch("t1", str(telemetry))
        with open(telemetry, "w") as f:
            f.write(json.dumps({"seq": 1, "type": "worker.x"}) + "\n")
        await client.run_task("t1", spec=str(spec),
                              log=str(tmp_path / "log.txt"))
        await client.wait_exit("t1", timeout=20.0)
        # The pre-exit line was flushed by the final pump at reap time.
        for _ in range(100):
            if seen:
                break
            await asyncio.sleep(0.05)
        assert [d["seq"] for d in seen] == [1]
        # Post-exit lines must NOT be forwarded: the watcher is gone.
        with open(telemetry, "a") as f:
            f.write(json.dumps({"seq": 2, "type": "worker.x"}) + "\n")
        await asyncio.sleep(0.8)  # > the 250ms watcher tick
        await client.close()
        return seen

    seen = run_async(flow())
    assert [d["seq"] for d in seen] == [1]


def test_agent_stall_suspicion_confirmed_against_hb_file(
    tmp_path, run_async, monkeypatch
):
    """A broken telemetry side-band must NOT kill a healthy gang: on
    stall suspicion the agent wait re-reads the .hb snapshot directly and
    a beating worker survives."""
    from covalent_tpu_plugin import agent as agent_mod
    from covalent_tpu_plugin import tpu as tpu_mod

    # No side-band at all: every watch fails (the worst case the review
    # flagged — agent mode with zero streaming feed into the monitor).
    async def broken_watch(self, task_id, path):
        raise agent_mod.AgentError("watch unsupported")

    monkeypatch.setattr(agent_mod.AgentClient, "watch", broken_watch)
    # The monitor's clock is the test's: the silence is declared once the
    # worker's first beat is on disk, not waited for against a worker
    # whose boot may take longer than any threshold worth waiting.
    now = [0.0]
    monitor = HeartbeatMonitor(clock=lambda: now[0])
    monkeypatch.setattr(tpu_mod, "MONITOR", monitor)
    ex = make_local_executor(
        tmp_path, use_agent="pool", heartbeat_interval=0.1,
        stall_threshold=1.6, max_task_retries=1, poll_freq=0.1,
    )
    release = tmp_path / "release"
    confirmed: list[dict] = []

    def held(x, release_path):
        import os as _os
        import time as _time

        give_up = _time.monotonic() + 60.0
        while not _os.path.exists(release_path) and _time.monotonic() < give_up:
            _time.sleep(0.01)
        return x * 3

    async def suspect_then_release():
        while not any(
            hb.stat().st_size for hb in (tmp_path / "remote").rglob("*.hb")
        ):
            await asyncio.sleep(0.02)
        now[0] += 3600.0  # an hour without a beat on the side-band
        # Only the confirmation's read of the .hb file can feed this
        # monitor; a gang killed instead shows as a second attempt.
        while not monitor.last("fconfirm_0") and ex.last_attempts == 1:
            await asyncio.sleep(0.02)
        confirmed.append(monitor.last("fconfirm_0"))
        release.write_text("go")

    async def flow():
        helper = asyncio.ensure_future(suspect_then_release())
        try:
            return await ex.run(held, [7, str(release)], {},
                                {"dispatch_id": "fconfirm", "node_id": 0})
        finally:
            helper.cancel()
            await ex.close()

    assert run_async(flow()) == 21
    assert ex.last_attempts == 1, "healthy gang was stall-killed"
    assert confirmed and "localhost" in confirmed[0]


NATIVE_AGENT_SKIP = pytest.mark.skipif(
    all(shutil.which(cc) is None for cc in ("g++", "c++", "clang++")),
    reason="no C++ compiler",
)


@NATIVE_AGENT_SKIP
def test_native_agent_watch_side_band(tmp_path, run_async):
    from covalent_tpu_plugin.agent import AgentClient, ensure_agent_binary
    from covalent_tpu_plugin.transport import LocalTransport

    telemetry = tmp_path / "native_telemetry.jsonl"
    telemetry.write_text(
        json.dumps({"seq": 1, "type": "worker.heartbeat"}) + "\n"
        + "not json\n"
        + json.dumps({"seq": 2, "type": "worker.task_finished"}) + "\n"
    )

    async def flow():
        seen: list[dict] = []
        conn = LocalTransport()
        binary = await ensure_agent_binary(conn, str(tmp_path / "cache"))
        client = await AgentClient.start(conn, binary)
        client.on_telemetry = lambda task_id, data: seen.append(data)
        await client.watch("t1", str(telemetry))
        for _ in range(100):
            if len(seen) >= 2:
                break
            await asyncio.sleep(0.05)
        await client.unwatch("t1")
        await client.close()
        return seen

    seen = run_async(flow())
    # Valid lines forwarded in order; the malformed line was dropped.
    assert [d["seq"] for d in seen] == [1, 2]
